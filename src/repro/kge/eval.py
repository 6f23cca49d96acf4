"""Evaluation: triple classification and (filtered) link prediction.

Triple classification (§4.1.3): corrupt each valid/test triple 1:1; learn a
global score threshold on the valid set; report accuracy on test.

Link prediction: for each test triple rank the true tail (and head) against
all entities, removing other true triples in Filter mode; report Mean Rank and
Hit@1/3/10 — the metrics of Tab. 4 / Tab. 6.

The default path is the **streaming fused-rank engine**: known-true entities
are packed once into padded CSR-style index tensors, queries are decomposed
into (query vector, entity table, mode) via ``lp_query_*``, and per-query
filtered rank counts come back from ``kernels.triple_score.fused_ranks`` —
tile-accumulated on device, so the (B, E) score matrix never materializes on
host and there is no per-triple Python ranking loop. Families without a
query/table decomposition stream through ``score_triples`` one entity block
at a time (same memory bound, generic math). ``engine="reference"`` keeps the
seed implementation for parity testing.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kge.data import corrupt_triples
from repro.kge.models import (
    KGEModel,
    lp_gold_scores,
    lp_query_heads,
    lp_query_tails,
    score_all_heads,
    score_all_tails,
    score_triples,
)


def best_threshold_accuracy(
    pos: np.ndarray, neg: np.ndarray, *, max_candidates: int = 512
) -> Tuple[float, float]:
    """(threshold, accuracy) maximizing ((pos ≥ thr) + (neg < thr)) / 2 over
    candidate thresholds — one broadcasted (C, N) comparison, no Python loop."""
    cand = np.unique(np.concatenate([pos, neg]))
    if len(cand) > max_candidates:
        cand = cand[:: len(cand) // max_candidates]
    acc = (
        (pos[None, :] >= cand[:, None]).mean(axis=1)
        + (neg[None, :] < cand[:, None]).mean(axis=1)
    ) / 2.0
    best = int(np.argmax(acc))
    return float(cand[best]), float(acc[best])


def triple_classification_accuracy(
    params, model: KGEModel, kg, *, seed: int = 0
) -> float:
    rng = np.random.default_rng(seed)
    va, te = kg.valid, kg.test
    va_neg = corrupt_triples(rng, va, kg.num_entities)
    te_neg = corrupt_triples(rng, te, kg.num_entities)

    def scores(t):
        t = jnp.asarray(t)
        return np.asarray(score_triples(params, model, t[:, 0], t[:, 1], t[:, 2]))

    sv_pos, sv_neg = scores(va), scores(va_neg)
    thr, _ = best_threshold_accuracy(sv_pos, sv_neg)
    st_pos, st_neg = scores(te), scores(te_neg)
    return float(((st_pos >= thr).mean() + (st_neg < thr).mean()) / 2.0)


# ---------------------------------------------------------------------------
# filter construction: padded CSR-style known-true index tensors
# ---------------------------------------------------------------------------
def _filter_mask(all_triples: np.ndarray, num_entities: int):
    """Dicts mapping (h, r) → {t} and (r, t) → {h} for Filter mode."""
    hr_t: Dict[Tuple[int, int], set] = {}
    rt_h: Dict[Tuple[int, int], set] = {}
    for h, r, t in all_triples:
        hr_t.setdefault((int(h), int(r)), set()).add(int(t))
        rt_h.setdefault((int(r), int(t)), set()).add(int(h))
    return hr_t, rt_h


def pack_padded_filters(rows, *, width: Optional[int] = None) -> np.ndarray:
    """Pack variable-length known-true id lists into one padded (N, W) int32
    array (pad −1, W ≥ 1). ``width`` pins W (e.g. a pow-2 bucket so downstream
    jits see a fixed filter shape); rows longer than ``width`` are an error
    rather than a silent truncation — a dropped filter id would silently
    stop excluding a known-true entity."""
    rows = [np.asarray(x, np.int64).reshape(-1) for x in rows]
    w = max(1, max((len(x) for x in rows), default=1))
    if width is not None:
        if w > width:
            raise ValueError(f"filter row of {w} ids exceeds width {width}")
        w = max(1, width)
    out = np.full((len(rows), w), -1, np.int32)
    for i, x in enumerate(rows):
        out[i, : len(x)] = x
    return out


def build_filter_arrays(
    test: np.ndarray, all_triples: Optional[np.ndarray], *, filtered: bool
) -> Tuple[np.ndarray, np.ndarray]:
    """Pack per-query known-true entity ids into padded (B, F) int32 arrays
    (pad −1), computed ONCE per evaluation — the engine applies them in-kernel.

    The gold entity is always row member #0 (also in raw mode): excluding it
    from the count is a no-op on exact scores (gold is never > itself) and
    makes the rank invariant to gather-vs-tile fp noise on the gold score.
    """
    b = len(test)
    if not filtered:
        filt_t = np.full((b, 1), -1, np.int64)
        filt_h = np.full((b, 1), -1, np.int64)
        filt_t[:, 0] = test[:, 2]
        filt_h[:, 0] = test[:, 0]
        return filt_t.astype(np.int32), filt_h.astype(np.int32)

    hr_t, rt_h = _filter_mask(all_triples, 0)
    tails = [sorted(hr_t[(int(h), int(r))]) for h, r, _ in test]
    heads = [sorted(rt_h[(int(r), int(t))]) for _, r, t in test]
    return pack_padded_filters(tails), pack_padded_filters(heads)


def build_score_inputs(
    kg, *, split: str = "test", max_test: int = 2000, filtered: bool = True
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(test, filt_t, filt_h) for ``link_prediction(..., precomputed=...)``.

    ``kg.train/valid/test`` are immutable, so these arrays are too — build
    them once per (kg, split, max_test) and reuse across evaluations. The
    federation scheduler caches them per owner: rebuilding the CSR filters is
    a Python pass over every triple, and letting the filter width float per
    call also retraced the rank kernels every tick.
    """
    test = np.asarray(getattr(kg, split))[:max_test]
    all_triples = (
        np.concatenate([kg.train, kg.valid, kg.test]) if filtered else None
    )
    filt_t, filt_h = build_filter_arrays(test, all_triples, filtered=filtered)
    return test, filt_t, filt_h


# ---------------------------------------------------------------------------
# streaming rank engine
# ---------------------------------------------------------------------------
def generic_counts_graph(
    params, model: KGEModel, fixed_a, fixed_b, gold, filt, *, side: str, block_e: int
):
    """Rank counts via blockwise ``score_triples`` for non-decomposable
    families: streams entity blocks (never materializes (B, E)); ``side`` is
    "tail" (fixed h, r) or "head" (fixed r, t)."""
    b = fixed_a.shape[0]
    e = model.num_entities
    be = min(block_e, e)
    n_blocks = -(-e // be)
    cols = jnp.arange(n_blocks * be, dtype=jnp.int32).reshape(n_blocks, be)
    gold = gold.astype(jnp.float32)[:, None]

    def step(acc, cb):
        ids = jnp.clip(cb, 0, e - 1)  # (Be,)
        aa = jnp.repeat(fixed_a[:, None], be, axis=1).reshape(-1)
        bb = jnp.repeat(fixed_b[:, None], be, axis=1).reshape(-1)
        cc = jnp.tile(ids[None], (b, 1)).reshape(-1)
        if side == "tail":
            s = score_triples(params, model, aa, bb, cc)
        else:
            s = score_triples(params, model, cc, aa, bb)
        s = s.reshape(b, be)
        excl = jnp.any(filt[:, :, None] == cb[None, None, :], axis=1)
        beats = (s > gold) & (cb < e)[None, :] & jnp.logical_not(excl)
        return acc + jnp.sum(beats.astype(jnp.int32), axis=1), None

    counts, _ = jax.lax.scan(step, jnp.zeros((b,), jnp.int32), cols)
    return counts


def side_counts_graph(
    params,
    model: KGEModel,
    h: jnp.ndarray,
    r: jnp.ndarray,
    t: jnp.ndarray,
    filt: jnp.ndarray,
    *,
    side: str,
    block_e: int = 512,
    impl: Optional[str] = None,
) -> jnp.ndarray:
    """``streaming_side_counts`` as a pure graph (device in, device out, no
    jit boundary, no host sync) — the exact per-side count math, for callers
    that embed scoring inside a larger compiled program (the federation tick
    engine batches every owner's backtrack scoring into one tick dispatch
    through this)."""
    from repro.kernels.triple_score import fused_ranks_graph

    qd = (
        lp_query_tails(params, model, h, r)
        if side == "tail"
        else lp_query_heads(params, model, r, t)
    )
    if qd is not None:
        q, table, mode = qd
        gold = lp_gold_scores(q, table, t if side == "tail" else h, mode)
        return fused_ranks_graph(q, table, gold, filt, mode=mode,
                                 block_e=block_e, impl=impl)
    gold = score_triples(params, model, h, r, t)
    fixed = (h, r) if side == "tail" else (r, t)
    return generic_counts_graph(
        params, model, *fixed, gold, filt, side=side, block_e=block_e
    )


_side_counts_jit = functools.partial(
    jax.jit, static_argnames=("model", "side", "block_e", "impl")
)(side_counts_graph)


def streaming_side_counts(
    params,
    model: KGEModel,
    chunk: np.ndarray,   # (B, 3) test triples
    filt: np.ndarray,    # (B, F) known-true ids for this side (pad −1)
    *,
    side: str,           # "tail" | "head"
    block_e: int = 512,
    impl: Optional[str] = None,
) -> np.ndarray:
    """Filtered rank counts for ONE corruption side — the engine core.

    One jitted call of the SAME ``side_counts_graph`` the federation tick
    engine embeds in its tick programs: one copy of the decomposition /
    gold-score / fallback selection, and no eager query-building dispatches.
    The implementation is resolved here (host-side) so the env overrides
    keep taking effect per call.

    ``chunk``/``filt`` may be host numpy (uploaded once per call — one
    transfer for the whole chunk, not one per column) or device arrays
    (e.g. the federation scheduler's owner-resident scoring caches — zero
    per-call uploads); with params committed to an owner's home device the
    whole rank computation runs there.
    """
    from repro.kernels.dispatch import resolve_rank_impl

    tri = jnp.asarray(chunk)
    counts = _side_counts_jit(
        params, model,
        tri[:, 0], tri[:, 1], tri[:, 2], jnp.asarray(filt),
        side=side, block_e=block_e, impl=resolve_rank_impl(impl),
    )
    return np.asarray(counts)


def streaming_rank_counts(
    params,
    model: KGEModel,
    chunk: np.ndarray,      # (B, 3) test triples
    filt_t: np.ndarray,     # (B, Ft) known-true tails (pad −1)
    filt_h: np.ndarray,     # (B, Fh) known-true heads (pad −1)
    *,
    block_e: int = 512,
    impl: Optional[str] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Filtered rank counts (tail, head) for one chunk."""
    kw = dict(block_e=block_e, impl=impl)
    return (
        streaming_side_counts(params, model, chunk, filt_t, side="tail", **kw),
        streaming_side_counts(params, model, chunk, filt_h, side="head", **kw),
    )


def _metrics(ranks: np.ndarray) -> Dict[str, float]:
    ranks = ranks.astype(np.float64)
    return {
        "mean_rank": float(ranks.mean()),
        "hit@1": float((ranks <= 1).mean()),
        "hit@3": float((ranks <= 3).mean()),
        "hit@10": float((ranks <= 10).mean()),
    }


def link_prediction(
    params,
    model: KGEModel,
    kg,
    *,
    filtered: bool = True,
    max_test: int = 2000,
    batch: int = 128,
    split: str = "test",
    engine: str = "auto",
    block_e: int = 512,
    impl: Optional[str] = None,
    precomputed: Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]] = None,
) -> Dict[str, float]:
    """Filtered/raw link prediction. ``engine``: "auto" | "fused" | "reference".

    "fused"/"auto" run the streaming rank engine (device-side accumulation, no
    (B, E) on host); "reference" is the seed per-triple numpy path, kept as
    the parity oracle. ``precomputed`` takes a cached
    ``build_score_inputs(...)`` triple and skips the per-call test-slice and
    filter construction (the split arrays are immutable, so callers that
    evaluate repeatedly — the federation backtrack — build them once).
    """
    if engine not in ("auto", "fused", "reference"):
        raise ValueError(f"unknown engine {engine!r} (auto|fused|reference)")
    if precomputed is not None and engine != "reference":
        test, filt_t, filt_h = precomputed
    else:
        test = np.asarray(getattr(kg, split))[:max_test]
        all_triples = (
            np.concatenate([kg.train, kg.valid, kg.test]) if filtered else None
        )
        if engine == "reference":
            return _link_prediction_reference(
                params, model, kg, test, all_triples,
                filtered=filtered, batch=batch,
            )
        filt_t, filt_h = build_filter_arrays(test, all_triples, filtered=filtered)
    ranks = np.empty(2 * len(test), dtype=np.int64)
    for i in range(0, len(test), batch):
        chunk = test[i : i + batch]
        c_tail, c_head = streaming_rank_counts(
            params, model, chunk, filt_t[i : i + batch], filt_h[i : i + batch],
            block_e=block_e, impl=impl,
        )
        # same interleaving as the seed loop: tail rank, then head rank
        ranks[2 * i : 2 * (i + len(chunk)) : 2] = c_tail + 1
        ranks[2 * i + 1 : 2 * (i + len(chunk)) : 2] = c_head + 1
    return _metrics(ranks)


def _link_prediction_reference(
    params, model: KGEModel, kg, test, all_triples, *, filtered: bool, batch: int
) -> Dict[str, float]:
    """Seed implementation: host-side (B, E) matrices + per-triple ranking."""
    hr_t, rt_h = _filter_mask(all_triples, kg.num_entities) if filtered else ({}, {})

    ranks = []
    for i in range(0, len(test), batch):
        chunk = test[i : i + batch]
        h = jnp.asarray(chunk[:, 0])
        r = jnp.asarray(chunk[:, 1])
        t = jnp.asarray(chunk[:, 2])
        s_tail = np.asarray(
            score_all_tails(params, model, h, r, via_kernel=False)
        )  # (B, E)
        s_head = np.asarray(score_all_heads(params, model, r, t, via_kernel=False))
        for j, (hh, rr, tt) in enumerate(chunk):
            row = s_tail[j].copy()
            if filtered:
                for other_t in hr_t.get((int(hh), int(rr)), ()):
                    if other_t != int(tt):
                        row[other_t] = -np.inf
            ranks.append(1 + int((row > row[int(tt)]).sum()))
            row = s_head[j].copy()
            if filtered:
                for other_h in rt_h.get((int(rr), int(tt)), ()):
                    if other_h != int(hh):
                        row[other_h] = -np.inf
            ranks.append(1 + int((row > row[int(hh)]).sum()))
    return _metrics(np.array(ranks))
