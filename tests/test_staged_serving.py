"""Served tables staged once per version: rank and top-k against the staged
entity table equal the per-call programs that stage (or read) the table
inline, and the tier stages each version once per replica device.

Shapes are chosen so that nothing lines up: 300 and 1,000 entities, neither
a multiple of the entity block, at widths of 100 and 1,000 (ComplEx's table
2,000), none a multiple of 128 lanes. Ranks run on the XLA scan and on the
interpreted Pallas kernel.
"""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.triple_score.ops import stage_table, staged_ranks_graph
from repro.kge.eval import _side_counts_jit
from repro.kge.models import KGEModel, init_kge, lp_layout, score_all_tails
from repro.serving import KGECandidateRanker, KGEServingTier
from repro.serving.engine import _streaming_topk_jit, side_counts_dispatch
from repro.serving.tables import FilterPack, TableVersion

FAMILIES = ["transe", "distmult", "complex", "rotate"]
#: (entities, width, block_e): a block smaller than the table, and the
#: tier's default block, larger than it
SHAPES = [(300, 100, 256), (1000, 1000, 2048)]
R, B, K = 7, 8, 5
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _owner(family, e, d, seed=0):
    m = KGEModel(family, e, R, d)
    return m, init_kge(jax.random.PRNGKey(seed), m)


def _queries(e, seed):
    rng = np.random.default_rng(seed)
    h, r, t = rng.integers(0, e, B), rng.integers(0, R, B), rng.integers(0, e, B)
    filt = np.full((B, 6), -1, np.int32)
    filt[:, 0] = t  # the gold tail, as the tier's rank filter has it
    filt[:, 1:4] = rng.integers(0, e, (B, 3))
    return h, r, t, filt


def _version(params, m, block_e):
    return TableVersion(params, m, FilterPack(None, m.num_entities),
                        block_e=block_e)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"E{s[0]}-d{s[1]}")
@pytest.mark.parametrize("family", FAMILIES)
def test_staged_rank_counts_equal_unstaged(family, shape, impl):
    """The served rank program on the staged table counts exactly what
    ``side_counts_graph`` counts staging the published table per call."""
    e, d, block_e = shape
    m, params = _owner(family, e, d, seed=1)
    h, r, t, filt = _queries(e, seed=2)
    tv = _version(params, m, block_e)
    got = side_counts_dispatch(
        tv.on(None), m, jnp.asarray(h), jnp.asarray(r), jnp.asarray(t),
        jnp.asarray(filt), entities=tv.entities, block_e=block_e, impl=impl)
    want = _side_counts_jit(
        params, m, jnp.asarray(h), jnp.asarray(r), jnp.asarray(t),
        jnp.asarray(filt), side="tail", block_e=block_e, impl=impl)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert 0 < np.asarray(got).max() < e


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"E{s[0]}-d{s[1]}")
@pytest.mark.parametrize("family", FAMILIES)
def test_staged_topk_equals_dense(family, shape):
    """Top-k read block by block from the staged table: the ids of a dense
    filtered top-k over the published table, and its scores to 1e-6."""
    e, d, block_e = shape
    m, params = _owner(family, e, d, seed=3)
    h, r, _, filt = _queries(e, seed=4)
    filt = filt[:, 1:]
    tv = _version(params, m, block_e)
    vals, ids = _streaming_topk_jit(
        tv.on(None), m, jnp.asarray(h), jnp.asarray(r), jnp.asarray(filt),
        k=K, entities=tv.entities, block_e=block_e)
    dense = np.asarray(score_all_tails(params, m, jnp.asarray(h),
                                       jnp.asarray(r), via_kernel=False))
    for i in range(B):
        row = dense[i].copy()
        row[filt[i][filt[i] >= 0]] = -np.inf
        top = np.argsort(-row, kind="stable")[:K]
        np.testing.assert_array_equal(np.asarray(ids[i]), top)
        np.testing.assert_allclose(np.asarray(vals[i]), row[top], rtol=1e-6)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("d", [100, 1000])
def test_cl1_padded_components_add_exactly_zero(d, impl):
    """A RotatE query equal to an entity's row scores it -n 1e-6 (n real
    components, each sqrt(0 + 1e-12)). The planes pad each half with 28 or
    12 components, so were a padded one to add its sqrt(1e-12) too, the
    score would fall below a gold of -(n + 1/2) 1e-6: the entity beats that
    gold, and tops the top-k at -n 1e-6, only if they add exactly 0."""
    e, n, j = 300, d // 2, 17
    m, params = _owner("rotate", e, d, seed=5)
    params = dict(params, rel=jnp.zeros_like(params["rel"]))  # q = h exactly
    staged = stage_table(params["ent"], "cl1", 256)
    assert staged.shape[1] > d  # the halves are padded
    q = params["ent"][jnp.full((B,), j)]
    gold = jnp.full((B,), -(n + 0.5) * 1e-6, jnp.float32)
    filt = jnp.full((B, 1), -1, jnp.int32)
    counts = staged_ranks_graph(q, staged, gold, filt, mode="cl1",
                                num_entities=e, block_e=256, impl=impl)
    np.testing.assert_array_equal(np.asarray(counts), 1)
    tv = _version(params, m, 256)
    hj = jnp.full((B,), j, jnp.int32)
    vals, ids = _streaming_topk_jit(tv.on(None), m, hj, hj * 0, filt, k=1,
                                    entities=e, block_e=256)
    np.testing.assert_array_equal(np.asarray(ids)[:, 0], j)
    np.testing.assert_allclose(np.asarray(vals)[:, 0], -n * 1e-6, atol=0.25e-6)


def test_tier_stages_each_version_once_and_serves_from_it():
    """One staging per published version (none for a window of traffic),
    each version answering from its own staged table across a hot-swap."""
    e, d = 300, 100
    m, p0 = _owner("transe", e, d, seed=6)
    _, p1 = _owner("transe", e, d, seed=7)
    known = np.stack([np.arange(e) % e, np.arange(e) % R, (np.arange(e) * 7) % e], 1)
    tier = KGEServingTier(p0, m, known, block_e=128, max_batch=8, replicas=1)
    assert tier.stats["staged"] == 1
    v0 = tier._active
    staged0 = v0.on(tier.replicas[0].device)["staged"]
    assert v0.staged == 1 and v0.staged_bytes == staged0.nbytes
    assert staged0.shape == (384, 128)  # whole 128-row blocks, 128 lanes
    h, r, t, _ = _queries(e, seed=8)
    a = tier.submit_rank(h, r, t)
    ka = tier.submit_topk(h, r, k=K)
    tier.step()
    tier.step()  # both dispatched, pinned to version 0
    tier.publish(p1)
    b = tier.submit_rank(h, r, t)
    kb = tier.submit_topk(h, r, k=K)
    tier.run_until_drained()
    assert tier.stats["staged"] == 2 and tier.stats["failed"] == 0
    assert (a.version, ka.version, b.version, kb.version) == (0, 0, 1, 1)
    staged1 = tier._active.on(tier.replicas[0].device)["staged"]
    assert staged1 is not staged0
    for req, topk, params in ((a, ka, p0), (b, kb, p1)):
        ranker = KGECandidateRanker(params, m, known, block_e=128)
        np.testing.assert_array_equal(req.result, ranker.rank_tails(h, r, t))
        ids, vals = ranker.topk_tails(h, r, k=K)
        np.testing.assert_array_equal(topk.result[0], ids)
        np.testing.assert_array_equal(topk.result[1], vals)
    # a window of traffic that publishes nothing stages nothing
    for i in range(3):
        tier.submit_rank(h[i:i + 1], r[i:i + 1], t[i:i + 1])
        tier.submit_topk(h[i:i + 1], r[i:i + 1], k=K)
    tier.run_until_drained()
    assert tier.stats["staged"] == 2


def test_generic_family_is_served_unstaged():
    """TransH projects its entity table per relation: nothing to stage."""
    assert lp_layout(KGEModel("transh", 10, 2, 4)) is None
    m, params = _owner("transh", 50, 8)
    tv = _version(params, m, 16)
    assert "staged" not in tv.on(None) and tv.staged == 0 == tv.staged_bytes


def test_tier_stages_once_per_replica_device():
    """Two replicas on two devices: each version is staged on both, row-major,
    committed to its replica's device."""
    code = textwrap.dedent("""
        import jax, numpy as np
        from repro.kge.models import KGEModel, init_kge
        from repro.serving import KGEServingTier

        assert len(jax.devices()) == 2
        m = KGEModel("rotate", 300, 5, 200)
        p = init_kge(jax.random.PRNGKey(0), m)
        known = np.array([[1, 2, 3]])
        tier = KGEServingTier(p, m, known, block_e=256, replicas=2)
        assert tier.stats["staged"] == 2
        tier.publish(init_kge(jax.random.PRNGKey(1), m))
        assert tier.stats["staged"] == 4 and tier._active.staged == 2
        for rep in tier.replicas:
            s = tier._active.on(rep.device)["staged"]
            assert s.devices() == {rep.device}, (s.devices(), rep.device)
            assert s.format.layout.major_to_minor == (0, 1)
            assert s.shape == (512, 256)
        assert tier._active.staged_bytes == 2 * 512 * 256 * 4
        a = tier.submit_rank([1], [2], [3])
        tier.run_until_drained()
        assert a.state == "served"
        print("ok")
    """)
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr
