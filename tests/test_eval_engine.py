"""Streaming fused-rank evaluation engine: kernel-vs-ref + end-to-end parity.

The engine must reproduce the seed per-triple numpy ranking EXACTLY (filtered
and raw, head and tail corruption, L1 and L2, non-divisible tail blocks) while
never materializing a (B, E) score matrix on host.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.dispatch import resolve_interpret, resolve_rank_impl
from repro.kernels.triple_score import (
    fused_ranks,
    fused_ranks_ref,
    pairwise_scores,
    pairwise_scores_ref,
)
from repro.kernels.triple_score.ops import fused_ranks_pallas_graph
from repro.kge.data import synthesize_universe
from repro.kge.eval import (
    best_threshold_accuracy,
    build_filter_arrays,
    link_prediction,
    streaming_rank_counts,
)
from repro.kge.trainer import KGETrainer
from repro.serving.engine import KGECandidateRanker


@pytest.fixture(scope="module")
def tiny_kg():
    stats = [("A", 10, 80000, 280000)]
    kgs = synthesize_universe(seed=0, scale=1 / 400, kg_stats=stats, alignments=[])
    return kgs["A"]


def _trained(kg, family="transe", norm_ord=1, epochs=3, dim=24):
    tr = KGETrainer(kg, family, dim=dim, seed=0, margin=2.0)
    if norm_ord != 1:
        tr.model = dataclasses.replace(tr.model, norm_ord=norm_ord)
    tr.train_epochs(epochs)
    return tr


# ------------------------------------------------------- kernel vs ref oracle
@pytest.mark.parametrize("mode", ["l1", "l2", "dot", "cl1"])
@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize(
    "b,e,d,block_e", [(8, 256, 32, 64), (13, 300, 48, 128), (5, 97, 16, 32)]
)
def test_fused_ranks_matches_ref(b, e, d, block_e, impl, mode):
    """Both implementations == the (B, E)-materializing oracle, including
    non-divisible B/E tail blocks and in-kernel filter exclusion."""
    q = jax.random.normal(jax.random.PRNGKey(0), (b, d))
    ent = jax.random.normal(jax.random.PRNGKey(1), (e, d))
    gold_idx = np.arange(b) % e
    filt = np.full((b, 4), -1, np.int32)
    filt[:, 0] = gold_idx
    filt[:, 1] = (gold_idx + 7) % e
    filt[::2, 2] = (gold_idx[::2] + 11) % e
    scores = pairwise_scores_ref(q, ent, mode=mode)
    gold = scores[jnp.arange(b), jnp.asarray(gold_idx)]
    ref = np.asarray(fused_ranks_ref(q, ent, gold, jnp.asarray(filt), mode=mode))
    if impl == "pallas":
        # the kernel itself, interpreted (cl1 on its [re | im] planes)
        out = fused_ranks_pallas_graph(
            q, ent, gold, jnp.asarray(filt), mode=mode, block_q=8,
            block_e=block_e, interpret=True,
        )
    else:
        out = fused_ranks(q, ent, gold, jnp.asarray(filt), mode=mode,
                          block_e=block_e, impl=impl)
    np.testing.assert_array_equal(np.asarray(out), ref)


@pytest.mark.parametrize("mode", ["l1", "l2", "dot", "cl1"])
def test_pairwise_scores_dot_and_minkowski(mode):
    q = jax.random.normal(jax.random.PRNGKey(0), (9, 40))
    ent = jax.random.normal(jax.random.PRNGKey(1), (130, 40))
    out = pairwise_scores(q, ent, mode=mode, interpret=True)
    ref = pairwise_scores_ref(q, ent, mode=mode)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-4, rtol=1e-4)


# ------------------------------------------------------- end-to-end parity
@pytest.mark.parametrize("filtered", [True, False])
@pytest.mark.parametrize(
    "family,norm_ord",
    [
        ("transe", 1),
        ("transe", 2),
        ("distmult", 1),
        # ComplEx/RotatE route through the dot / cl1 decompositions (ROADMAP
        # follow-up from PR 1) — they must hit the fused engine, not the
        # generic score_triples fallback, and still match the seed ranking
        ("complex", 1),
        ("rotate", 1),
    ],
)
def test_link_prediction_engine_parity(tiny_kg, family, norm_ord, filtered):
    """Engine metrics == seed reference metrics, bit-identical, on a fixed-seed
    universe — batch 16 does not divide the 50-triple test slice."""
    tr = _trained(tiny_kg, family, norm_ord)
    kw = dict(filtered=filtered, max_test=50, batch=16)
    ref = link_prediction(tr.params, tr.model, tiny_kg, engine="reference", **kw)
    fused = link_prediction(tr.params, tr.model, tiny_kg, engine="fused",
                            block_e=48, **kw)
    assert ref == fused


@pytest.mark.parametrize("filtered", [True, False])
def test_link_prediction_generic_family_parity(tiny_kg, filtered):
    """Non-decomposable families stream through score_triples blockwise and
    must match the reference too (transh exercises the generic path)."""
    tr = _trained(tiny_kg, "transh")
    kw = dict(filtered=filtered, max_test=30, batch=16)
    ref = link_prediction(tr.params, tr.model, tiny_kg, engine="reference", **kw)
    fused = link_prediction(tr.params, tr.model, tiny_kg, engine="fused", **kw)
    assert ref == fused


def test_head_and_tail_counts_separately(tiny_kg):
    """Per-side rank counts match a hand-rolled numpy ranking, head AND tail."""
    tr = _trained(tiny_kg)
    test = np.asarray(tiny_kg.test)[:20]
    all_triples = np.concatenate([tiny_kg.train, tiny_kg.valid, tiny_kg.test])
    filt_t, filt_h = build_filter_arrays(test, all_triples, filtered=True)
    c_tail, c_head = streaming_rank_counts(
        tr.params, tr.model, test, filt_t, filt_h, block_e=64
    )

    from repro.kge.models import score_all_heads, score_all_tails

    h, r, t = (jnp.asarray(test[:, i]) for i in range(3))
    s_tail = np.asarray(score_all_tails(tr.params, tr.model, h, r, via_kernel=False))
    s_head = np.asarray(score_all_heads(tr.params, tr.model, r, t, via_kernel=False))
    for j, (hh, rr, tt) in enumerate(test):
        row = s_tail[j].copy()
        row[filt_t[j][filt_t[j] >= 0]] = -np.inf
        assert int(c_tail[j]) == int((row > s_tail[j, int(tt)]).sum())
        row = s_head[j].copy()
        row[filt_h[j][filt_h[j] >= 0]] = -np.inf
        assert int(c_head[j]) == int((row > s_head[j, int(hh)]).sum())


def test_no_full_score_matrix_on_host(tiny_kg, monkeypatch):
    """The engine path must never call the (B, E)-materializing scorers."""
    import repro.kge.eval as eval_mod

    def _boom(*a, **k):  # pragma: no cover - should never run
        raise AssertionError("engine materialized a (B, E) score matrix")

    monkeypatch.setattr(eval_mod, "score_all_tails", _boom)
    monkeypatch.setattr(eval_mod, "score_all_heads", _boom)
    tr = _trained(tiny_kg)
    lp = link_prediction(tr.params, tr.model, tiny_kg, max_test=20, engine="fused")
    assert 1.0 <= lp["mean_rank"] <= tiny_kg.num_entities


# ------------------------------------------------------------ dispatch rules
def test_resolve_interpret_backend_and_env(monkeypatch):
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    # CPU CI default backend → interpreter
    assert resolve_interpret(None) is (jax.default_backend() not in
                                       ("tpu", "gpu", "cuda", "rocm"))
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    assert resolve_interpret(None) is False
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "true")
    assert resolve_interpret(None) is True
    # explicit argument still wins over the env override
    assert resolve_interpret(False) is False


def test_resolve_rank_impl(monkeypatch):
    monkeypatch.delenv("REPRO_RANK_IMPL", raising=False)
    assert resolve_rank_impl("pallas") == "pallas"
    assert resolve_rank_impl(None) in ("pallas", "xla")
    monkeypatch.setenv("REPRO_RANK_IMPL", "pallas")
    assert resolve_rank_impl(None) == "pallas"
    # RotatE's cl1 ranks take the kernel too, on its [re | im] planes
    assert resolve_rank_impl(None, "cl1") == "pallas"
    assert resolve_rank_impl("pallas", "cl1") == "pallas"
    assert resolve_rank_impl("pallas", "dot") == "pallas"
    with pytest.raises(ValueError):
        resolve_rank_impl("tensorflow")


# ------------------------------------------------- vectorized threshold scan
def test_best_threshold_accuracy_matches_loop():
    rng = np.random.default_rng(0)
    pos = rng.normal(1.0, 1.0, 400)
    neg = rng.normal(-1.0, 1.0, 400)
    thr, acc = best_threshold_accuracy(pos, neg)
    cand = np.unique(np.concatenate([pos, neg]))
    ref = [((pos >= c).mean() + (neg < c).mean()) / 2.0 for c in cand]
    assert acc == pytest.approx(np.max(ref))
    assert acc > 0.8


# ----------------------------------------------- virtual-entity negatives fix
def test_trainer_corrupts_against_extended_entities(tiny_kg, monkeypatch):
    tr = KGETrainer(tiny_kg, "transe", dim=16, seed=0)
    e0, r0 = tr.model.num_entities, tr.model.num_relations
    tr.extend_tables(
        jnp.ones((5, 16)) * 0.1, jnp.ones((2, 16)) * 0.2,
        np.array([[e0, r0, 3], [1, r0 + 1, e0 + 4]], dtype=np.int64),
    )
    seen = {}
    import repro.kge.data as data_mod

    real = data_mod.corrupt_triples

    def spy(rng, triples, num_entities):
        seen["num_entities"] = num_entities
        return real(rng, triples, num_entities)

    monkeypatch.setattr(data_mod, "corrupt_triples", spy)
    # impl="reference" pins the host numpy-sampling path this spy observes;
    # the device engine's equivalent (traced corruption bound = extended
    # count, bucket-padding rows excluded) is covered in test_train_engine.
    tr.train_epochs(1, impl="reference")
    assert seen["num_entities"] == e0 + 5  # extended count, not kg.num_entities
    tr.strip_virtual()
    tr.train_epochs(1, impl="reference")
    assert seen["num_entities"] == e0


# ------------------------------------------------------------ serving ranker
def test_candidate_ranker_rank_and_topk(tiny_kg):
    tr = _trained(tiny_kg)
    known = np.concatenate([tiny_kg.train, tiny_kg.valid, tiny_kg.test])
    ranker = KGECandidateRanker(tr.params, tr.model, known, block_e=64)
    test = np.asarray(tiny_kg.test)[:12]
    ranks = ranker.rank_tails(test[:, 0], test[:, 1], test[:, 2])
    assert ranks.shape == (12,)
    assert (ranks >= 1).all() and (ranks <= tr.model.num_entities).all()

    # streaming top-k == full argsort of the dense scores with known excluded
    from repro.kge.models import score_all_tails

    h, r = jnp.asarray(test[:, 0]), jnp.asarray(test[:, 1])
    ids, scores = ranker.topk_tails(test[:, 0], test[:, 1], k=5)
    dense = np.asarray(score_all_tails(tr.params, tr.model, h, r, via_kernel=False))
    for j in range(len(test)):
        row = dense[j].copy()
        key = (int(test[j, 0]), int(test[j, 1]))
        for known_t in ranker._hr_t.get(key, ()):
            row[known_t] = -np.inf
        expect = np.argsort(-row, kind="stable")[:5]
        np.testing.assert_allclose(row[expect], scores[j], rtol=1e-6, atol=1e-6)


def test_candidate_ranker_rejects_bad_ids(tiny_kg):
    """Serving boundary: out-of-range / negative ids are refused with a
    clear ValueError instead of wrapping into the wrong table row."""
    tr = _trained(tiny_kg)
    ranker = KGECandidateRanker(tr.params, tr.model, tiny_kg.train, block_e=64)
    e, r = tr.model.num_entities, tr.model.num_relations
    with pytest.raises(ValueError, match=r"head entity ids .*\[-1\]"):
        ranker.rank_tails([-1], [0], [1])
    with pytest.raises(ValueError, match=rf"tail entity ids .*\[{e}\]"):
        ranker.rank_tails([0], [0], [e])
    with pytest.raises(ValueError, match="relation ids"):
        ranker.rank_tails([0], [r + 3], [1])
    with pytest.raises(ValueError, match="head entity ids"):
        ranker.topk_tails([0, e + 7], [0], k=3)
    with pytest.raises(ValueError, match="relation ids"):
        ranker.topk_tails([0], [-2], k=3)
    # in-range requests still serve
    assert ranker.rank_tails([0], [0], [1]).shape == (1,)


def test_candidate_ranker_rejects_non_finite_query(tiny_kg):
    """A NaN/Inf embedding row poisons every rank it participates in — a
    query that would serve from one is refused, naming the offending id."""
    tr = _trained(tiny_kg)
    params = {k: np.asarray(v).copy() for k, v in tr.params.items()}
    params["ent"][3, 0] = np.nan
    params["rel"][1, 2] = np.inf
    ranker = KGECandidateRanker(params, tr.model, tiny_kg.train, block_e=64)
    with pytest.raises(ValueError, match=r"non-finite query embedding: entity ids \[3\]"):
        ranker.rank_tails([3], [0], [1])
    with pytest.raises(ValueError, match=r"relation ids \[1\]"):
        ranker.topk_tails([0], [1], k=3)
    # untouched ids still serve fine
    assert ranker.rank_tails([0], [0], [1]).shape == (1,)
