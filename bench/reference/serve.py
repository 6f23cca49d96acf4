"""Plain reference for served TransE (L1) link-prediction answers.

Independent of the program: it imports nothing of ``repro`` and builds its
own known-tail filters from the generated triples. Scores are
``-sum_k |e_h + r - e_t|_k`` over the whole entity table, computed in blocks
of entities in ``dtype`` (float32 for the reference; bfloat16 for the
control that stands in for a lower-precision program).

A rank answer is checked against a band: the count of unfiltered entities
that beat the gold score by more than the f32 rounding of a d-term L1 sum
(4 d 2^-24 (|gold| + 1): both sides may each be off by d 2^-24 |score|),
and the count that come within it. A top-k answer is checked id by id: its
score against the reference's score of that id, and that id against the
reference's k-th best score.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def tolerance(gold: np.ndarray, dim: int) -> np.ndarray:
    return 4.0 * dim * 2.0 ** -24 * (np.abs(gold) + 1.0)


def known_tails(known: np.ndarray, h: np.ndarray, r: np.ndarray) -> List[np.ndarray]:
    """The known-true tails of each (h, r), from the known triples (sorted
    once by (h, r), then one binary search per query)."""
    key = known[:, 0].astype(np.int64) * (1 << 32) + known[:, 1].astype(np.int64)
    order = np.argsort(key, kind="stable")
    skey, tails = key[order], known[order, 2]
    q = h.astype(np.int64) * (1 << 32) + r.astype(np.int64)
    lo = np.searchsorted(skey, q, "left")
    hi = np.searchsorted(skey, q, "right")
    return [tails[a:b] for a, b in zip(lo, hi)]


def _scores_fn(dtype, block: int):
    @jax.jit
    def scores(ent, rel, h, r):
        ent = ent.astype(dtype)
        q = ent[h] + rel.astype(dtype)[r]                      # (B, d)
        e = ent.shape[0]
        nb = -(-e // block)
        pad = jnp.pad(ent, ((0, nb * block - e), (0, 0)))

        def one(eb):
            return -jnp.sum(jnp.abs(q[:, None, :] - eb[None]), axis=-1,
                            dtype=dtype)

        s = jax.lax.map(one, pad.reshape(nb, block, -1))       # (nb, B, blk)
        return jnp.transpose(s, (1, 0, 2)).reshape(q.shape[0], -1)[:, :e]

    return scores


def scores(params: Dict, h: np.ndarray, r: np.ndarray, *, dtype=jnp.float32,
           block: int = 4096, rows: int = 32) -> np.ndarray:
    """(B, E) float32 host array of reference scores, ``rows`` queries at a
    time (a (rows, E) block is what the device holds at once)."""
    fn = _scores_fn(dtype, block)
    out = []
    for i in range(0, len(h), rows):
        hh, rr = h[i:i + rows], r[i:i + rows]
        n = len(hh)
        if n < rows:  # one compiled shape
            hh = np.concatenate([hh, np.zeros(rows - n, hh.dtype)])
            rr = np.concatenate([rr, np.zeros(rows - n, rr.dtype)])
        s = fn(params["ent"], params["rel"], jnp.asarray(hh, jnp.int32),
               jnp.asarray(rr, jnp.int32))
        out.append(np.asarray(s.astype(jnp.float32))[:n])
    return np.concatenate(out) if out else np.zeros((0, 0), np.float32)


def rank_answers(s: np.ndarray, t: np.ndarray, filt: List[np.ndarray]) -> np.ndarray:
    """Filtered rank counts a program computing ``s`` would serve (the
    control's answers): unfiltered entities scoring above gold."""
    out = np.empty(len(t), np.int64)
    for i, (row, gold_id, f) in enumerate(zip(s, t, filt)):
        beats = row > row[gold_id]
        beats[f] = False
        beats[gold_id] = False
        out[i] = int(beats.sum())
    return out


def topk_answers(s: np.ndarray, filt: List[np.ndarray], k: int) -> Tuple[np.ndarray, np.ndarray]:
    ids = np.empty((len(s), k), np.int64)
    vals = np.empty((len(s), k), np.float32)
    for i, (row, f) in enumerate(zip(s, filt)):
        row = row.copy()
        row[f] = -np.inf
        top = np.argsort(-row, kind="stable")[:k]
        ids[i], vals[i] = top, row[top]
    return ids, vals


def rank_miss(s: np.ndarray, t: np.ndarray, filt: List[np.ndarray],
              counts: np.ndarray, dim: int) -> float:
    """Largest distance, in entities, of a served count (rank - 1) outside
    the reference band [beat by more than the tolerance, within it]."""
    worst = 0
    for row, gold_id, f, c in zip(s, t, filt, counts):
        gold = row[gold_id]
        tol = tolerance(gold, dim)
        keep = np.ones(row.shape[0], bool)
        keep[f] = False
        keep[gold_id] = False
        lo = int(np.sum(keep & (row > gold + tol)))
        hi = int(np.sum(keep & (row >= gold - tol)))
        worst = max(worst, lo - int(c), int(c) - hi, 0)
    return float(worst)


def topk_gap(s: np.ndarray, filt: List[np.ndarray], ids: np.ndarray,
             vals: np.ndarray, dim: int) -> float:
    """Largest gap, in score units, of a served top-k answer: a served
    score's distance from the reference score of its id, or how far a
    served id's reference score lies below the reference's k-th best.
    A filtered, repeated or out-of-range id reads as infinitely far."""
    worst = 0.0
    for row, f, ii, vv in zip(s, filt, ids, vals):
        k = len(ii)
        if len(set(ii.tolist())) != k or (ii < 0).any() or (ii >= len(row)).any():
            return float("inf")
        if np.isin(ii, f).any():
            return float("inf")
        masked = row.copy()
        masked[f] = -np.inf
        kth = np.partition(masked, -k)[-k]
        ref = row[ii]
        worst = max(worst, float(np.max(np.abs(vv - ref))),
                    float(kth - ref.min()))
    return worst
