"""Public wrappers: pad to block multiples, resolve the backend, dispatch.

``pairwise_scores`` keeps the seed API (full (B, E) matrix — training-scale
uses). ``fused_ranks`` is the streaming rank engine: it returns per-query
filtered rank *counts* without ever materializing (B, E). Two interchangeable
implementations sit behind ``kernels.dispatch.resolve_rank_impl``:

  * ``pallas`` — the fused accumulation-grid kernel (TPU/GPU);
  * ``xla``    — a ``lax.scan`` over entity blocks with identical tile math
    (CPU CI: one compiled loop instead of interpret-mode Pallas).

The kernel reads its entity table in one layout, which ``stage_rows``
builds: rows padded to whole entity blocks and, for ``cl1``, the
lane-aligned ``[re | im]`` planes. ``fused_ranks`` stages the table inline
on every call, since the federation tick's tables change every tick; a
served table version is staged once (``stage_table``, lanes padded to whole
128-lane tiles as well) and ranked as it is by ``staged_ranks_graph``.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.dispatch import resolve_interpret, resolve_rank_impl
from repro.kernels.triple_score.triple_score import (
    LANES,
    SCORE_MODES,
    _tile_scores,
    cl1_block_e,
    cl1_plane_width,
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
def stage_rows(x: jnp.ndarray, mode: str, rows: int,
               width: Optional[int] = None) -> jnp.ndarray:
    """``x`` (n, d) in the layout the fused-rank kernel reads, ``rows`` rows:
    for ``cl1`` the lane-aligned ``[re | im]`` planes (``cl1_planes``); for
    ``l1``/``l2``/``dot`` ``x`` with zero rows appended and zero lanes up to
    ``width`` (default: ``x``'s own), which add exactly 0 to each score."""
    if mode == "cl1":
        return cl1_planes(x, rows)
    n, d = x.shape
    width = d if width is None else width
    if rows == n and width == d:
        return x
    return jnp.pad(x, ((0, rows - n), (0, width - d)))


def unstage_rows(x: jnp.ndarray, mode: str, d: int) -> jnp.ndarray:
    """Rows of a ``stage_rows`` layout back as (n, d) rows: the real lanes,
    for ``cl1`` the re and im halves taken out of their planes."""
    if mode == "cl1":
        half, w = d // 2, x.shape[1] // 2
        return jnp.concatenate([x[:, :half], x[:, w:w + half]], axis=1)
    return x if x.shape[1] == d else x[:, :d]


def staged_blocks(n: int, d: int, mode: str, block_e: int) -> Tuple[int, int]:
    """(kernel block, scan block) of ``n`` entities of width ``d`` staged for
    ``block_e``: the fused-rank kernel's entity block, and the largest
    multiple of it within ``min(block_e, n)`` (at least one), which the
    XLA scans read. A staged table holds whole scan blocks."""
    kb = cl1_block_e(block_e, d, n) if mode == "cl1" else min(block_e, n)
    return kb, kb * max(1, min(block_e, n) // kb)


def stage_table(table: jnp.ndarray, mode: str, block_e: int) -> jnp.ndarray:
    """The served layout of an (n, d) entity table: ``stage_rows`` to whole
    scan blocks (``staged_blocks``) and whole 128-lane tiles, so that the
    device keeps it row-major and a rank or top-k call reads it as it is."""
    n, d = table.shape
    _, sb = staged_blocks(n, d, mode, block_e)
    width = cl1_plane_width(d) if mode == "cl1" else pl.cdiv(d, LANES) * LANES
    return stage_rows(table.astype(jnp.float32), mode, pl.cdiv(n, sb) * sb,
                      width)


def _ranks_pallas(q, ent, gold, filt, *, mode, num_entities, block_q, block_e,
                  interpret):
    """Kernel ranks of ``q`` against an ``ent`` already in ``stage_rows``'s
    layout: only the B query rows (and their gold scores and filters) are
    padded here."""
    b, d = q.shape
    bq = min(block_q, b)
    pad_b = (-b) % bq
    q = stage_rows(q, mode, b + pad_b, ent.shape[1])
    if pad_b:
        gold = jnp.pad(gold, (0, pad_b))
        filt = jnp.pad(filt, ((0, pad_b), (0, 0)), constant_values=-1)
    out = fused_rank_fwd(
        q, ent, gold[:, None].astype(jnp.float32), filt.astype(jnp.int32),
        mode=mode, num_entities=num_entities, block_q=bq, block_e=block_e,
        interpret=interpret, half=d // 2,
    )
    return out[:b, 0]


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
    d = q.shape[1]
    e = ent.shape[0]
    be = cl1_block_e(block_e, d, e) if mode == "cl1" else min(block_e, e)
    # the call's one table-sized copy: the table staged to a block multiple
    # (for cl1, laid out as the [re | im] planes)
    with jax.named_scope("rank_planes"):
        ent = stage_rows(ent, mode, e + (-e) % be)
    return _ranks_pallas(q, ent, gold, filt, mode=mode, num_entities=e,
                         block_q=block_q, block_e=be, interpret=interpret)


_fused_ranks_pallas = functools.partial(
    jax.jit, static_argnames=("mode", "block_q", "block_e", "interpret")
)(fused_ranks_pallas_graph)


def _ranks_scan(q, blocks, gold, filt, *, mode, num_entities, rows=None):
    """Rank counts of ``q`` by a ``lax.scan`` over the (n_blocks, Be, w)
    entity ``blocks``; ``rows(block)`` gives a block's (Be, d) rows."""
    b = q.shape[0]
    be = blocks.shape[1]
    cols = jnp.arange(blocks.shape[0] * be, dtype=jnp.int32).reshape(-1, be)
    q = q.astype(jnp.float32)
    gold = gold.astype(jnp.float32)[:, None]  # (B, 1)
    filt = filt.astype(jnp.int32)

    def step(acc, inp):
        eb, cb = inp  # (Be, w), (Be,)
        eb = eb if rows is None else rows(eb)
        s = _tile_scores(q, eb.astype(jnp.float32), mode)  # (B, Be)
        excl = jnp.any(filt[:, :, None] == cb[None, None, :], axis=1)
        beats = (s > gold) & (cb < num_entities)[None, :] & jnp.logical_not(excl)
        return acc + jnp.sum(beats.astype(jnp.int32), axis=1), None

    counts, _ = jax.lax.scan(step, jnp.zeros((b,), jnp.int32), (blocks, cols))
    return counts


def fused_ranks_xla_graph(
    q: jnp.ndarray,
    ent: jnp.ndarray,
    gold: jnp.ndarray,
    filt: jnp.ndarray,
    *,
    mode: str,
    block_e: int,
) -> jnp.ndarray:
    d = q.shape[1]
    e = ent.shape[0]
    be = min(block_e, e)
    pad_e = (-e) % be
    if pad_e:
        ent = jnp.pad(ent, ((0, pad_e), (0, 0)))
    return _ranks_scan(q, ent.reshape(-1, be, d), gold, filt, mode=mode,
                       num_entities=e)


_fused_ranks_xla = functools.partial(
    jax.jit, static_argnames=("mode", "block_e")
)(fused_ranks_xla_graph)


def staged_ranks_graph(
    q: jnp.ndarray,       # (B, d) queries
    staged: jnp.ndarray,  # (R, W) entity table from ``stage_table``
    gold: jnp.ndarray,    # (B,) gold score per query
    filt: jnp.ndarray,    # (B, F) int32 known-true entity ids, pad −1
    *,
    mode: str,
    num_entities: int,
    block_e: int,
    impl: Optional[str] = None,
) -> jnp.ndarray:
    """``fused_ranks_graph`` against a table ``stage_table(table, mode,
    block_e)`` staged once: it reads ``staged`` as it is, with no
    table-sized pad, copy or planes op, and pads only the B query rows.
    ``num_entities`` is the table's own row count; staged rows past it are
    padding and never counted."""
    assert mode in SCORE_MODES, mode
    d = q.shape[1]
    kb, sb = staged_blocks(num_entities, d, mode, block_e)
    assert staged.shape[0] % sb == 0, (staged.shape, sb)
    if resolve_rank_impl(impl, mode) == "pallas":
        return _ranks_pallas(q, staged, gold, filt, mode=mode,
                             num_entities=num_entities, block_q=8,
                             block_e=kb, interpret=resolve_interpret(None))
    return _ranks_scan(q, staged.reshape(-1, sb, staged.shape[1]), gold, filt,
                       mode=mode, num_entities=num_entities,
                       rows=lambda eb: unstage_rows(eb, mode, d))


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
