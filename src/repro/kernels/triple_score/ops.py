"""Public wrappers: pad to block multiples, resolve the backend, dispatch.

``pairwise_scores`` keeps the seed API (full (B, E) matrix — training-scale
uses). ``fused_ranks`` is the streaming rank engine: it returns per-query
filtered rank *counts* without ever materializing (B, E). Two interchangeable
implementations sit behind ``kernels.dispatch.resolve_rank_impl``:

  * ``pallas`` — the fused accumulation-grid kernel (TPU/GPU);
  * ``xla``    — a ``lax.scan`` over entity blocks with identical tile math
    (CPU CI: one compiled loop instead of interpret-mode Pallas).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import resolve_interpret, resolve_rank_impl
from repro.kernels.triple_score.triple_score import (
    SCORE_MODES,
    _tile_scores,
    cl1_block_e,
    cl1_planes,
    fused_rank_fwd,
    pairwise_scores_fwd,
)


@functools.partial(
    jax.jit, static_argnames=("mode", "block_q", "block_e", "interpret")
)
def _pairwise_scores_jit(
    q: jnp.ndarray,
    ent: jnp.ndarray,
    *,
    mode: str,
    block_q: int,
    block_e: int,
    interpret: bool,
) -> jnp.ndarray:
    b, d = q.shape
    e = ent.shape[0]
    be = min(block_e, e)
    bq = min(block_q, b)
    pad_e = (-e) % be
    pad_b = (-b) % bq
    if pad_e:
        ent = jnp.pad(ent, ((0, pad_e), (0, 0)))
    if pad_b:
        q = jnp.pad(q, ((0, pad_b), (0, 0)))
    out = pairwise_scores_fwd(
        q, ent, mode=mode, block_q=bq, block_e=be, interpret=interpret
    )
    return out[:b, :e]


def pairwise_scores(
    q: jnp.ndarray,
    ent: jnp.ndarray,
    *,
    ord_: int = 1,
    mode: Optional[str] = None,
    block_q: int = 8,
    block_e: int = 256,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """(B, d) × (E, d) → (B, E) scores. ``mode`` (l1|l2|dot) wins over ``ord_``."""
    mode = mode or ("l2" if ord_ == 2 else "l1")
    assert mode in SCORE_MODES, mode
    return _pairwise_scores_jit(
        q, ent, mode=mode, block_q=block_q, block_e=block_e,
        interpret=resolve_interpret(interpret),
    )


# ------------------------------------------------------------- fused ranks
def fused_ranks_pallas_graph(
    q: jnp.ndarray,
    ent: jnp.ndarray,
    gold: jnp.ndarray,
    filt: jnp.ndarray,
    *,
    mode: str,
    block_q: int,
    block_e: int,
    interpret: bool,
) -> jnp.ndarray:
    b, d = q.shape
    e = ent.shape[0]
    be = cl1_block_e(block_e, d, e) if mode == "cl1" else min(block_e, e)
    bq = min(block_q, b)
    pad_e = (-e) % be
    pad_b = (-b) % bq
    if mode == "cl1":
        # the pad to a block multiple, as below, is the call's one
        # table-sized copy: here it also lays out the [re | im] planes
        with jax.named_scope("rank_planes"):
            ent = cl1_planes(ent, e + pad_e)
            q = cl1_planes(q, b + pad_b)
    else:
        if pad_e:
            ent = jnp.pad(ent, ((0, pad_e), (0, 0)))
        if pad_b:
            q = jnp.pad(q, ((0, pad_b), (0, 0)))
    if pad_b:
        gold = jnp.pad(gold, (0, pad_b))
        filt = jnp.pad(filt, ((0, pad_b), (0, 0)), constant_values=-1)
    out = fused_rank_fwd(
        q, ent, gold[:, None].astype(jnp.float32), filt.astype(jnp.int32),
        mode=mode, num_entities=e, block_q=bq, block_e=be, interpret=interpret,
        half=d // 2,
    )
    return out[:b, 0]


_fused_ranks_pallas = functools.partial(
    jax.jit, static_argnames=("mode", "block_q", "block_e", "interpret")
)(fused_ranks_pallas_graph)


def fused_ranks_xla_graph(
    q: jnp.ndarray,
    ent: jnp.ndarray,
    gold: jnp.ndarray,
    filt: jnp.ndarray,
    *,
    mode: str,
    block_e: int,
) -> jnp.ndarray:
    b, d = q.shape
    e = ent.shape[0]
    be = min(block_e, e)
    pad_e = (-e) % be
    if pad_e:
        ent = jnp.pad(ent, ((0, pad_e), (0, 0)))
    blocks = ent.reshape(-1, be, d)
    cols = jnp.arange(blocks.shape[0] * be, dtype=jnp.int32).reshape(-1, be)
    q = q.astype(jnp.float32)
    gold = gold.astype(jnp.float32)[:, None]  # (B, 1)
    filt = filt.astype(jnp.int32)

    def step(acc, inp):
        eb, cb = inp  # (Be, d), (Be,)
        s = _tile_scores(q, eb.astype(jnp.float32), mode)  # (B, Be)
        excl = jnp.any(filt[:, :, None] == cb[None, None, :], axis=1)
        beats = (s > gold) & (cb < e)[None, :] & jnp.logical_not(excl)
        return acc + jnp.sum(beats.astype(jnp.int32), axis=1), None

    counts, _ = jax.lax.scan(step, jnp.zeros((b,), jnp.int32), (blocks, cols))
    return counts


_fused_ranks_xla = functools.partial(
    jax.jit, static_argnames=("mode", "block_e")
)(fused_ranks_xla_graph)


def fused_ranks_graph(
    q: jnp.ndarray,
    ent: jnp.ndarray,
    gold: jnp.ndarray,
    filt: jnp.ndarray,
    *,
    mode: str = "l1",
    block_q: int = 8,
    block_e: int = 512,
    impl: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """``fused_ranks`` as a pure graph (no jit boundary) — for callers that
    embed rank counting inside a larger compiled program (the federation tick
    engine). Resolves the implementation exactly like ``fused_ranks``."""
    assert mode in SCORE_MODES, mode
    impl = resolve_rank_impl(impl, mode)
    if impl == "pallas":
        return fused_ranks_pallas_graph(
            q, ent, gold, filt, mode=mode, block_q=block_q, block_e=block_e,
            interpret=resolve_interpret(interpret),
        )
    return fused_ranks_xla_graph(q, ent, gold, filt, mode=mode, block_e=block_e)


def fused_ranks(
    q: jnp.ndarray,     # (B, d) queries
    ent: jnp.ndarray,   # (E, d) entity table
    gold: jnp.ndarray,  # (B,) gold score per query
    filt: jnp.ndarray,  # (B, F) int32 known-true entity ids, pad −1
    *,
    mode: str = "l1",
    block_q: int = 8,
    block_e: int = 512,
    impl: Optional[str] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Streaming filtered rank counts; filtered rank = ``fused_ranks(...) + 1``.

    The gold entity id should appear in its own filter row: exclusion makes
    the count invariant to fp noise between the gathered gold score and the
    tile-computed score of the same entity.
    """
    assert mode in SCORE_MODES, mode
    impl = resolve_rank_impl(impl, mode)
    if impl == "pallas":
        return _fused_ranks_pallas(
            q, ent, gold, filt, mode=mode, block_q=block_q, block_e=block_e,
            interpret=resolve_interpret(interpret),
        )
    return _fused_ranks_xla(q, ent, gold, filt, mode=mode, block_e=block_e)
