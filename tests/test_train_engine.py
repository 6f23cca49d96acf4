"""Device-resident training engine: sparse-vs-dense parity, bucket padding,
fused-kernel oracle checks, and the retrace-free federation invariant."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.federation as federation_mod
from repro.core.federation import FederationScheduler
from repro.core.ppat import PPATConfig
from repro.kernels.dispatch import resolve_train_impl
from repro.kernels.sparse_update import fused_sparse_step, sparse_step_ref
from repro.kge.data import synthesize_universe
from repro.kge.engine import (
    ENT_BUCKET,
    ENT_KEYS,
    _train_scan,
    bucket,
    pad_tables,
    pad_triples,
    shape_spec,
    sparse_epoch,
    train_epochs_device,
    train_scan_cache_size,
)
from repro.kge.models import KGEModel, MODEL_FAMILIES, init_kge
from repro.kge.trainer import KGETrainer, _epoch


# ---------------------------------------------------------------- helpers
def _batches(rng, e, r, nb, b, *, duplicates=True):
    """(nb, B, 3) positive + 1:1-corrupted negative batches; every batch
    carries duplicated rows so the segment-sum composition is exercised."""
    pos = np.stack(
        [
            rng.integers(0, e, (nb, b)),
            rng.integers(0, r, (nb, b)),
            rng.integers(0, e, (nb, b)),
        ],
        axis=-1,
    ).astype(np.int32)
    neg = pos.copy()
    ch = rng.random((nb, b)) < 0.5
    rand = rng.integers(0, e, (nb, b))
    neg[..., 0] = np.where(ch, rand, neg[..., 0])
    neg[..., 2] = np.where(~ch, rand, neg[..., 2])
    if duplicates:
        pos[:, 0] = pos[:, 1]  # row 0 duplicates row 1 in every batch
        neg[:, 0] = neg[:, 1]
    return jnp.asarray(pos), jnp.asarray(neg)


def _sparse_epochs(params, model, pos, neg, lr, epochs):
    """Sparse trajectory on fixed batches via the jitted ``sparse_epoch``
    twin of the dense ``_epoch``."""
    spec = shape_spec(model)
    losses = []
    for _ in range(epochs):
        params, loss = sparse_epoch(params, spec, pos, neg, lr)
        losses.append(float(loss))
    return params, np.asarray(losses)


# ------------------------------------------------- sparse vs dense, bit-level
@pytest.mark.parametrize("family", MODEL_FAMILIES)
def test_sparse_step_bit_parity_all_families(family):
    """3-epoch loss trajectory AND final params bit-identical to the dense
    reference, with duplicate rows in every batch."""
    e, r, d, nb, b, epochs = 60, 6, 16, 4, 10, 3
    m = KGEModel(family, e, r, d, margin=2.0)
    p = init_kge(jax.random.PRNGKey(0), m)
    rng = np.random.default_rng(0)
    pos, neg = _batches(rng, e, r, nb, b)
    lr = jnp.float32(0.25)

    dense, sparse = p, p
    for _ in range(epochs):
        dense, dl = _epoch(dense, m, pos, neg, lr)
    sparse, sl = _sparse_epochs(p, m, pos, neg, lr, epochs)
    for k in dense:
        np.testing.assert_array_equal(
            np.asarray(dense[k]), np.asarray(sparse[k]),
            err_msg=f"{family}:{k} diverged from the dense update",
        )
    # _epoch returns the LAST epoch's mean loss; trajectories must agree too
    np.testing.assert_array_equal(np.asarray(dl), sl[-1])


def test_sparse_step_bit_parity_with_virtual_extension():
    """Batches referencing virtual rows (ids ≥ base E) update the extended
    tables exactly like the dense step."""
    e0, r0, d, b = 40, 4, 16, 12
    m = KGEModel("transe", e0, r0, d)
    p = init_kge(jax.random.PRNGKey(1), m)
    # virtual extension: +6 entity rows, +2 relation rows
    p = dict(p)
    p["ent"] = jnp.concatenate([p["ent"], jnp.full((6, d), 0.1, jnp.float32)])
    p["rel"] = jnp.concatenate([p["rel"], jnp.full((2, d), 0.2, jnp.float32)])
    m = dataclasses.replace(m, num_entities=e0 + 6, num_relations=r0 + 2)
    rng = np.random.default_rng(2)
    pos, neg = _batches(rng, e0 + 6, r0 + 2, 3, b)
    # force several virtual-row hits
    pos = pos.at[:, 2, 0].set(e0 + 1)
    pos = pos.at[:, 3, 1].set(r0)
    lr = jnp.float32(0.5)

    dense, _ = _epoch(p, m, pos, neg, lr)
    sparse, _ = _sparse_epochs(p, m, pos, neg, lr, 1)
    for k in dense:
        np.testing.assert_array_equal(np.asarray(dense[k]), np.asarray(sparse[k]))


# --------------------------------------------------------- fused pallas step
@pytest.mark.parametrize("mode,margin", [("l1", 4.0), ("l2", 2.0), ("dot", 2.0)])
def test_fused_kernel_step_matches_dense_oracle(mode, margin):
    rng = np.random.default_rng(0)
    e, r, d, b = 50, 5, 16, 10
    ent = jnp.asarray(rng.normal(0, 0.3, (e, d)).astype(np.float32))
    rel = jnp.asarray(rng.normal(0, 0.3, (r, d)).astype(np.float32))
    pos, neg = _batches(rng, e, r, 1, b)
    ne, nr, loss = fused_sparse_step(
        ent, rel, pos[0], neg[0], 0.1, mode=mode, margin=margin, interpret=True
    )
    re_, rr_, rl = sparse_step_ref(ent, rel, pos[0], neg[0], 0.1,
                                   mode=mode, margin=margin)
    np.testing.assert_allclose(float(loss), float(rl), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ne), np.asarray(re_), atol=1e-6)
    np.testing.assert_allclose(np.asarray(nr), np.asarray(rr_), atol=1e-6)


def test_engine_pallas_impl_trains(monkeypatch):
    """The fused-kernel impl runs end-to-end through the multi-epoch scan."""
    kgs = synthesize_universe(
        seed=3, kg_stats=[("A", 6, 60000, 220000)], alignments=[]
    )
    tr = KGETrainer(kgs["A"], "transe", dim=16, seed=0, margin=2.0)
    first = tr.train_epochs(2, impl="pallas")
    last = tr.train_epochs(10, impl="pallas")
    assert np.isfinite(first) and np.isfinite(last)
    assert last < first


# ------------------------------------------------------------ bucket padding
def test_bucket_rounding():
    assert bucket(1, 256) == 256
    assert bucket(256, 256) == 256
    assert bucket(257, 256) == 512


def test_pad_triples_pow2_batches_bounded_oversampling():
    """Triple padding rounds the minibatch COUNT to a power of two: < 2×
    oversampling (no full-bucket floor for small stores), every padded row a
    real triple."""
    rng = np.random.default_rng(0)
    tri = jnp.asarray(rng.integers(0, 50, (90, 3)).astype(np.int32))
    out = pad_triples(tri, 30)
    assert out.shape[0] == 120  # nb 3 → 4, NOT 8×30·bucket
    assert out.shape[0] < 2 * 90 + 30
    # padded rows cycle the real store
    np.testing.assert_array_equal(np.asarray(out[90:]), np.asarray(tri[:30]))
    assert pad_triples(tri[:64], 16).shape[0] == 64  # already pow2 → untouched


def test_train_ppat_rejects_empty_aligned_sets():
    from repro.core.ppat import train_ppat

    with pytest.raises(ValueError, match="non-empty aligned sets"):
        train_ppat(jnp.zeros((0, 8)), jnp.ones((5, 8)), PPATConfig(steps=2))


def test_padded_rows_stay_inert():
    """Bucket-padding rows are never sampled as negatives and never touched:
    they remain exactly zero through a full multi-epoch scan."""
    e, r, d = 70, 5, 16
    m = KGEModel("transe", e, r, d)
    p = init_kge(jax.random.PRNGKey(0), m)
    rng = np.random.default_rng(0)
    tri = np.stack(
        [rng.integers(0, e, 600), rng.integers(0, r, 600), rng.integers(0, e, 600)],
        axis=1,
    ).astype(np.int32)
    padded, e_pad, r_pad = pad_tables(p, m)
    assert e_pad == ENT_BUCKET and padded["ent"].shape[0] == ENT_BUCKET
    out, losses = _train_scan(
        padded, pad_triples(jnp.asarray(tri), 50), jax.random.PRNGKey(1),
        jnp.float32(0.5), jnp.int32(e),
        spec=shape_spec(m), epochs=4, batch=50, impl="xla", interpret=True,
    )
    assert np.asarray(losses).shape == (4,)
    np.testing.assert_array_equal(np.asarray(out["ent"][e:]), 0.0)
    np.testing.assert_array_equal(np.asarray(out["rel"][r:]), 0.0)
    # the real rows DID train
    assert not np.array_equal(np.asarray(out["ent"][:e]), np.asarray(padded["ent"][:e]))


def test_padding_does_not_change_training():
    """Growing the physical table (same logical count, same key) leaves the
    logical result bit-identical: scores see no padding."""
    e, r, d = 64, 4, 8
    m = KGEModel("transe", e, r, d)
    p = init_kge(jax.random.PRNGKey(0), m)
    rng = np.random.default_rng(1)
    tri = np.stack(
        [rng.integers(0, e, 400), rng.integers(0, r, 400), rng.integers(0, e, 400)],
        axis=1,
    ).astype(np.int32)
    kw = dict(
        spec=shape_spec(m), epochs=3, batch=40, impl="xla", interpret=True
    )
    args = (pad_triples(jnp.asarray(tri), 40), jax.random.PRNGKey(2),
            jnp.float32(0.5), jnp.int32(e))
    small, l_small = _train_scan(p, *args, **kw)
    grown = {
        k: jnp.pad(v, ((0, 128 if k in ENT_KEYS else 32),) + ((0, 0),) * (v.ndim - 1))
        for k, v in p.items()
    }
    big, l_big = _train_scan(grown, *args, **kw)
    np.testing.assert_array_equal(np.asarray(l_small), np.asarray(l_big))
    for k in p:
        n = p[k].shape[0]
        np.testing.assert_array_equal(np.asarray(small[k]), np.asarray(big[k][:n]))


def test_train_epochs_device_roundtrip_shapes():
    """The trainer-facing wrapper pads and strips: logical shapes in, logical
    shapes out, regardless of bucket size."""
    e, r, d = 130, 7, 12
    m = KGEModel("transe", e, r, d)
    p = init_kge(jax.random.PRNGKey(0), m)
    rng = np.random.default_rng(0)
    tri = np.stack(
        [rng.integers(0, e, 90), rng.integers(0, r, 90), rng.integers(0, e, 90)],
        axis=1,
    ).astype(np.int32)
    out, losses = train_epochs_device(
        p, m, tri, jax.random.PRNGKey(1),
        epochs=2, batch_size=30, lr=0.5, impl="xla", interpret=True,
    )
    assert out["ent"].shape == (e, d) and out["rel"].shape == (r, d)
    assert losses.shape == (2,)


# --------------------------------------------------------- dispatch + retrace
def test_resolve_train_impl(monkeypatch):
    monkeypatch.delenv("REPRO_TRAIN_IMPL", raising=False)
    # the autodiff step on every backend: the kernel does not lower on TPU
    assert resolve_train_impl(None, "transe") == "xla"
    assert resolve_train_impl(None, "distmult") == "xla"
    assert resolve_train_impl("reference") == "reference"
    assert resolve_train_impl("xla", "transh") == "xla"
    # the kernel only covers the decomposable hot path → fall back
    assert resolve_train_impl("pallas", "transh") == "xla"
    assert resolve_train_impl("pallas", "transe") == "pallas"
    with pytest.raises(ValueError):
        resolve_train_impl("nope")


@pytest.fixture(scope="module")
def fed_universe():
    stats = [("A", 10, 80000, 260000), ("B", 8, 70000, 220000)]
    aligns = [("A", "B", 24000)]
    return synthesize_universe(seed=5, scale=1 / 500, kg_stats=stats,
                               alignments=aligns)


def test_federate_once_does_not_retrace(fed_universe, monkeypatch):
    """≥3 consecutive handshakes with virtual extensions active reuse the
    compiled multi-epoch scan — zero retraces after the warm-up call."""
    fed = FederationScheduler(
        fed_universe, dim=16, ppat_cfg=PPATConfig(steps=4, seed=0),
        local_epochs=3, update_epochs=2, seed=0, use_virtual=True,
    )
    ve_seen = []
    real_ve = federation_mod.virtual_extension

    def spy(*a, **k):
        out = real_ve(*a, **k)
        ve_seen.append(out)
        return out

    monkeypatch.setattr(federation_mod, "virtual_extension", spy)
    fed.initial_training()
    fed.federate_once("A", "B")  # warm-up: compiles the update-epoch scan
    n_compiled = train_scan_cache_size()
    for _ in range(3):
        fed.federate_once("A", "B")
    assert ve_seen and all(v is not None for v in ve_seen), (
        "virtual extension must be active for the invariant to be meaningful"
    )
    assert train_scan_cache_size() == n_compiled, (
        "federate_once retraced the training scan across handshakes"
    )


# ------------------------------------------------------------- broadcast fix
def test_broadcast_dedupes_offers(fed_universe):
    fed = FederationScheduler(fed_universe, dim=16, local_epochs=1, seed=0)
    for _ in range(5):
        fed.broadcast("A")
    assert list(fed.queue["B"]).count("A") == 1
    assert fed._queued["B"] == {"A"}
    client = fed._pop_offer("B")
    assert client == "A" and fed._queued["B"] == set()
    fed.broadcast("A")  # re-offer after pop must queue again
    assert list(fed.queue["B"]) == ["A"]
