"""RotatE through the fused-rank kernel's ``cl1`` path and the serving tier,
against plain references on seeded random weights.

The kernel runs interpreted here, at a width whose half is not a multiple of
128 lanes (its planes pad each half) and an entity count that is not a
multiple of its block (the last block holds padding rows). The tier serves
rank and top-k answers of a small RotatE owner, compared with the
benchmark's plain reference, ``bench/reference/serve_rotate.py``.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.triple_score.ops import fused_ranks_pallas_graph
from repro.kernels.triple_score.triple_score import cl1_block_e, cl1_planes
from repro.kge.models import KGEModel, init_kge, lp_gold_scores, lp_query_tails
from repro.serving import KGEServingTier

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.reference import serve_rotate as ref  # noqa: E402

U = 2.0 ** -24


def _owner(e, r, d, seed=0):
    m = KGEModel("rotate", e, r, d, margin=24.0)
    return m, init_kge(jax.random.PRNGKey(seed), m)


def _plain_tail_scores(params, h, r):
    """float64 NumPy RotatE tail scores -sum_k |h_k e^{i theta_k} - t_k|
    over every entity, from the complex numbers themselves."""
    ent = np.asarray(params["ent"], np.float64)
    n = ent.shape[1] // 2
    z = ent[:, :n] + 1j * ent[:, n:]
    q = z[h] * np.exp(1j * np.asarray(params["rel"], np.float64)[r])
    return -np.abs(q[:, None, :] - z[None, :, :]).sum(-1)


def test_cl1_planes_pad_each_half_to_whole_lane_tiles():
    x = jnp.arange(2 * 200, dtype=jnp.float32).reshape(2, 200)
    p = np.asarray(cl1_planes(x, 5))
    assert p.shape == (5, 256)
    np.testing.assert_array_equal(p[:2, :100], np.asarray(x[:, :100]))
    np.testing.assert_array_equal(p[:2, 128:228], np.asarray(x[:, 100:]))
    assert not p[:, 100:128].any() and not p[:, 228:].any() and not p[2:].any()
    # the block holds whole 128-row chunks once it is larger than one, also
    # where the table is smaller than the block (padded up, never cut short)
    assert cl1_block_e(2048, 1000, 491078) == 1024
    assert cl1_block_e(64, 200, 600) == 64
    assert cl1_block_e(2048, 1000, 1000) == 1024
    assert cl1_block_e(2048, 200, 300) == 384
    assert cl1_block_e(2048, 200, 100) == 100


@pytest.mark.parametrize(
    "b,e,block_e", [(13, 700, 256), (8, 517, 128), (8, 300, 2048), (5, 1000, 2048)]
)
def test_cl1_kernel_counts_match_plain_rotate(b, e, block_e):
    """Interpreted kernel == float64 RotatE counts, up to entities within
    the float32 band of the gold score. The kernel's score of each pair is
    off from the exact one by at most (n - 1) u |S| from its sums (n terms,
    non-negative) plus a few u per term from the rotation, differences,
    squares and root, plus the 1e-12 under each root (at most 1e-6 a
    term): 2 (n + 8) u (|S| + 1) + n 1e-6 bounds both."""
    d, r = 200, 9
    m, params = _owner(e, r, d)
    rng = np.random.default_rng(1)
    h, rr, t = (rng.integers(0, n, b) for n in (e, r, e))
    filt = np.full((b, 5), -1, np.int32)
    filt[:, 0] = t
    filt[:, 1:3] = rng.integers(0, e, (b, 2))
    q, table, mode = lp_query_tails(params, m, jnp.asarray(h), jnp.asarray(rr))
    gold = lp_gold_scores(q, table, jnp.asarray(t), mode)
    out = np.asarray(fused_ranks_pallas_graph(
        q, table, gold, jnp.asarray(filt), mode="cl1", block_q=8,
        block_e=block_e, interpret=True,
    ))
    s = _plain_tail_scores(params, h, rr)
    n = d // 2
    for i in range(b):
        g = s[i, t[i]]
        tol = 2 * (n + 8) * U * (abs(g) + 1) + n * 1e-6
        keep = np.ones(e, bool)
        keep[filt[i][filt[i] >= 0]] = False
        lo = int(np.sum(keep & (s[i] > g + tol)))
        hi = int(np.sum(keep & (s[i] >= g - tol)))
        assert lo <= out[i] <= hi, (i, lo, out[i], hi)


@pytest.mark.parametrize("rank_impl", ["pallas", "xla"])
def test_rotate_tier_answers_match_reference(rank_impl):
    """A small RotatE owner served by the tier (the kernel interpreted, or
    the XLA scan) gives the reference's rank counts within its band and its
    top-k within the band of the largest top-k score."""
    e, r, d = 600, 12, 200
    m, params = _owner(e, r, d, seed=3)
    rng = np.random.default_rng(2)
    known = np.stack([rng.integers(0, e, 2000), rng.integers(0, r, 2000),
                      rng.integers(0, e, 2000)], 1)
    tier = KGEServingTier(params, m, known, max_batch=16, min_bucket=8,
                          block_e=256, rank_impl=rank_impl, replicas=1)
    q = known[:20]
    ranks = [tier.submit_rank(x[:1], x[1:2], x[2:3]) for x in q]
    tops = [tier.submit_topk(x[:1], x[1:2], k=10) for x in q]
    tier.run_until_drained()
    assert all(x.state == "served" for x in ranks + tops)
    h, rr, t = q[:, 0], q[:, 1], q[:, 2]
    filt = ref.known_tails(known, h, rr)
    s = ref.scores(params, h, rr)
    bound = ref.bound_of(params)
    counts = np.array([int(x.result[0]) - 1 for x in ranks])
    assert ref.rank_miss(s, t, filt, counts, d, bound) == 0.0
    ids = np.array([np.asarray(x.result[0][0]) for x in tops])
    vals = np.array([np.asarray(x.result[1][0]) for x in tops])
    band = float(ref.tolerance(np.abs(vals).max(), d, bound))
    assert ref.topk_gap(s, filt, ids, vals, d) <= band
