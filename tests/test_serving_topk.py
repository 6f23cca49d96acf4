"""Streaming top-k tails: one jitted program per (rows, k) bucket that builds
its own query.

Parity: ``KGECandidateRanker.topk_tails`` and the tier's served top-k against
a dense float64 NumPy filtered top-k written out per family here, for every
decomposable family and one generic (TransH) — ids exact outside f32
near-ties, scores to f32 tolerance, tier bit-equal to the ranker.

Mechanism: the query projection (``lp_tail_query``) only ever runs while
the top-k program traces — never eagerly on concrete arrays, at warm-up,
steady-state tier batches or ranker calls."""
import jax
import numpy as np
import pytest

import repro.kge.models as kge_models
from repro.kge.models import KGEModel
from repro.kge.trainer import init_kge
from repro.serving import KGECandidateRanker, KGEServingTier
from repro.serving.engine import _streaming_topk_jit

E, R, D, K = 300, 6, 16, 7
#: scores of two candidates closer than this may swap places in f32
NEAR_TIE = 1e-4


def _tri(n, seed):
    rng = np.random.default_rng(seed)
    return np.stack(
        [rng.integers(0, E, n), rng.integers(0, R, n), rng.integers(0, E, n)],
        axis=1,
    ).astype(np.int64)


def _dense_tail_scores(params, m, h, r):
    """(B, E) float64 scores of every candidate tail, from the family's
    formula on the raw tables."""
    p = {k: np.asarray(v, np.float64) for k, v in params.items()}
    he, t = p["ent"][h][:, None, :], p["ent"][None, :, :]
    if m.family == "transe":
        diff = he + p["rel"][r][:, None, :] - t
        return -np.linalg.norm(diff, ord=m.norm_ord, axis=-1)
    if m.family == "distmult":
        return np.sum(he * p["rel"][r][:, None, :] * t, axis=-1)
    if m.family == "complex":
        him, tim = p["ent_im"][h][:, None, :], p["ent_im"][None, :, :]
        rre, rim = p["rel"][r][:, None, :], p["rel_im"][r][:, None, :]
        return np.sum(he * rre * t + him * rre * tim + he * rim * tim
                      - him * rim * t, axis=-1)
    if m.family == "rotate":
        d2 = m.dim // 2
        ph = p["rel"][r][:, None, :]
        rot = (he[..., :d2] + 1j * he[..., d2:]) * np.exp(1j * ph)
        return -np.sum(np.abs(rot - (t[..., :d2] + 1j * t[..., d2:])), axis=-1)
    if m.family == "transh":
        w = p["norm_vec"][r][:, None, :]
        w = w / (np.linalg.norm(w, axis=-1, keepdims=True) + 1e-9)
        hp = he - np.sum(w * he, -1, keepdims=True) * w
        tp = t - np.sum(w * t, -1, keepdims=True) * w
        diff = hp + p["rel"][r][:, None, :] - tp
        return -np.linalg.norm(diff, ord=m.norm_ord, axis=-1)
    raise ValueError(m.family)


def _check_against_dense(params, m, known, h, r, ids, vals):
    dense = _dense_tail_scores(params, m, h, r)
    for j in range(len(h)):
        row = dense[j].copy()
        hit = (known[:, 0] == h[j]) & (known[:, 1] == r[j])
        row[known[hit, 2]] = -np.inf
        expect = np.argsort(-row, kind="stable")[:K]
        np.testing.assert_allclose(vals[j], row[expect], rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(vals[j], row[ids[j]], rtol=1e-5, atol=1e-5)
        off = ids[j] != expect
        assert np.all(np.abs(row[ids[j][off]] - row[expect[off]]) < NEAR_TIE), (
            j, ids[j], expect)
        assert len(set(ids[j].tolist())) == K
        assert not set(ids[j].tolist()) & set(known[hit, 2].tolist())


@pytest.mark.parametrize(
    "family,norm_ord",
    [("transe", 1), ("transe", 2), ("distmult", 1), ("complex", 1),
     ("rotate", 1), ("transh", 1)],
    ids=["transe-l1", "transe-l2", "distmult", "complex", "rotate", "transh"],
)
def test_topk_parity_vs_dense_numpy(family, norm_ord):
    m = KGEModel(family, E, R, D, norm_ord=norm_ord)
    params = init_kge(jax.random.PRNGKey(5), m)
    known = _tri(600, seed=200)
    # keys with known tails (the filter matters) and random ones
    q = np.concatenate([known[:5], _tri(3, seed=201)])
    h, r = q[:, 0], q[:, 1]

    ranker = KGECandidateRanker(params, m, known, block_e=64)
    ids, vals = ranker.topk_tails(h, r, k=K)
    _check_against_dense(params, m, known, h, r, ids, vals)

    # the tier coalesces the two requests into one 8-row batch: the same
    # program and the same rows as the ranker's call
    tier = KGEServingTier(params, m, known, block_e=64, max_batch=16)
    a = tier.submit_topk(h[:3], r[:3], k=K)
    b = tier.submit_topk(h[3:], r[3:], k=K)
    tier.run_until_drained()
    assert tier.stats["failed"] == 0 and tier.stats["batches"] == 1
    np.testing.assert_array_equal(np.concatenate([a.result[0], b.result[0]]), ids)
    np.testing.assert_array_equal(np.concatenate([a.result[1], b.result[1]]), vals)


def test_topk_query_projection_only_traced(monkeypatch):
    """``lp_tail_query`` on the top-k path sees tracers only: the tier's
    warm-up, its steady-state batches and the ranker all dispatch one
    program that projects the query inside it, from the head rows it
    gathers out of the staged table."""
    traced = []
    real = kge_models.lp_tail_query

    def only_traced(params, m, rows, r):
        if not all(isinstance(x, jax.core.Tracer) for x in (rows, r)):
            raise AssertionError("lp_tail_query called eagerly on the top-k path")
        traced.append(rows.shape)
        return real(params, m, rows, r)

    monkeypatch.setattr(kge_models, "lp_tail_query", only_traced)
    m = KGEModel("transe", E, R, D)
    params = init_kge(jax.random.PRNGKey(6), m)
    known = _tri(400, seed=210)
    # a block size no other test uses, so the program traces here
    tier = KGEServingTier(params, m, known, block_e=40, max_batch=16,
                          warm_buckets=[("topk", 8, 5), ("topk", 16, 5)])
    assert traced  # warm-up traced the programs, through the wrapper
    warm = _streaming_topk_jit._cache_size()
    reqs = []
    for i, n in enumerate((2, 5, 1, 9, 3)):
        q = _tri(n, seed=220 + i)
        reqs.append(tier.submit_topk(q[:, 0], q[:, 1], k=5))
        tier.step()
    tier.run_until_drained()
    assert tier.stats["failed"] == 0 and all(q.done for q in reqs)
    assert _streaming_topk_jit._cache_size() == warm  # warm-up = steady state
    q = _tri(4, seed=230)
    ranker = KGECandidateRanker(params, m, known, block_e=40)
    ranker.topk_tails(q[:, 0], q[:, 1], k=5)
    ranker.topk_tails(q[:, 0], q[:, 1], k=5, exclude_known=False)
