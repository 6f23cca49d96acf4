"""Plain reference for served RotatE link-prediction answers.

Independent of the program: it imports nothing of ``repro``. RotatE (Sun et
al., ICLR 2019, arXiv 1902.10197, Sec. 3.1): an entity row holds n = d/2
complex components as its [re | im] halves, a relation row n phases, and the
tail score of (h, r, t) is ``-sum_k |h_k * exp(i theta_k) - t_k|``. Scores
are computed over the whole entity table, in blocks of entities, in
``dtype`` (float32 for the reference; bfloat16 for the control that stands
in for a lower-precision program), under ``default_matmul_precision
("highest")`` (the score has no product for the MXU; the setting keeps one
that a later edit adds from running at bfloat16 passes). The known-tail
filters, the control's answers and the top-k check are ``serve.py``'s.

One departure from the paper, the program's own: it adds 1e-12 under each
square root (a gradient guard), which moves a term by at most
sqrt(1e-12) = 1e-6; the band below allows n of them.

The band. Let u = 2^-24 and b the largest magnitude in the table, so
|h_k|, |t_k| <= sqrt(2) b. On either side, each term m_k = |q_k - t_k| is
off by at most 3 u m_k (two squares, a sum, a square root, each rounded)
plus 8 u (|h_k| + |t_k|) <= 8 u 2 sqrt(2) b (the rotation's cosine, sine,
two products and sum, then the two differences); summing n non-negative
terms in any order adds (n - 1) u |S|. So each side is within
u ((n + 2) |S| + 16 sqrt(2) n b) of the exact score, and a rank answer is
checked against the band of twice that, plus n 1e-6: the count of
unfiltered entities that beat the gold score by more than the band, and the
count that come within it. At the cell's width (n = 500, b = 6/sqrt(1000),
|S| about 97) the band is about 6.6e-3; computed in bfloat16 (u = 2^-8) the
same scores move by about 0.4, tens of thousands of entities' worth.
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.serve import (  # noqa: F401 - the shared checks
    known_tails,
    rank_answers,
    topk_answers,
    topk_gap,
)

U = 2.0 ** -24
#: the program's guard under each square root, sqrt of it per term
SQRT_EPS = 1e-6


def tolerance(gold: np.ndarray, dim: int, bound: float) -> np.ndarray:
    """The band around a gold score ``gold`` of a table of width ``dim``
    whose entries lie within +-``bound`` (module docstring)."""
    n = dim // 2
    side = U * ((n + 2) * np.abs(gold) + 16.0 * math.sqrt(2.0) * n * bound)
    return 2.0 * side + n * SQRT_EPS


def _scores_fn(dtype, block: int):
    @jax.jit
    def scores(ent, rel, h, r):
        ent = ent.astype(dtype)
        half = ent.shape[1] // 2
        theta = rel.astype(dtype)[r]                             # (B, n)
        c, s = jnp.cos(theta), jnp.sin(theta)
        hr, hi = ent[h, :half], ent[h, half:]
        qr, qi = hr * c - hi * s, hr * s + hi * c                # h o r
        e = ent.shape[0]
        nb = -(-e // block)
        pad = jnp.pad(ent, ((0, nb * block - e), (0, 0)))

        def one(eb):
            dr = qr[:, None, :] - eb[None, :, :half]
            di = qi[:, None, :] - eb[None, :, half:]
            return -jnp.sum(jnp.sqrt(dr * dr + di * di), axis=-1, dtype=dtype)

        out = jax.lax.map(one, pad.reshape(nb, block, -1))       # (nb, B, blk)
        return jnp.transpose(out, (1, 0, 2)).reshape(qr.shape[0], -1)[:, :e]

    return scores


def scores(params: Dict, h: np.ndarray, r: np.ndarray, *, dtype=jnp.float32,
           block: int = 1024, rows: int = 32) -> np.ndarray:
    """(B, E) float32 host array of reference tail scores, ``rows`` queries
    at a time (a (rows, E) block is what the device holds at once)."""
    fn = _scores_fn(dtype, block)
    out = []
    with jax.default_matmul_precision("highest"):
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


def bound_of(params: Dict) -> float:
    """The largest magnitude in the entity table (the band's ``b``)."""
    return float(jnp.max(jnp.abs(params["ent"])))


def rank_miss(s: np.ndarray, t: np.ndarray, filt: List[np.ndarray],
              counts: np.ndarray, dim: int, bound: float) -> float:
    """Largest distance, in entities, of a served count (rank - 1) outside
    the reference band [beat by more than the band, within it]."""
    worst = 0
    for row, gold_id, f, c in zip(s, t, filt, counts):
        gold = row[gold_id]
        tol = tolerance(gold, dim, bound)
        keep = np.ones(row.shape[0], bool)
        keep[f] = False
        keep[gold_id] = False
        lo = int(np.sum(keep & (row > gold + tol)))
        hi = int(np.sum(keep & (row >= gold - tol)))
        worst = max(worst, lo - int(c), int(c) - hi, 0)
    return float(worst)
