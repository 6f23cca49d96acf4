"""Blocked pairwise translational scoring — the link-prediction hot spot.

Link prediction scores every test query q = h + r against EVERY entity
embedding: (B, E) Minkowski distances with E up to millions. The kernel tiles
(B, E) into (block_q × block_e) VMEM blocks; the query block and entity block
are resident in VMEM and the (Bq, Be, d) broadcast-difference never
materializes in HBM.

VMEM per step: Bq·d + Be·d + Bq·Be·d (intermediate) fp32. Defaults
(8, 256, d≤256) → ~2 MB. For L2 the expansion ||q−e||² = |q|²−2q·e+|e|² routes
the dominant term through the MXU.

Two kernels share the tile math:

  * ``pairwise_scores_fwd`` — writes the (B, E) score matrix (training-time
    uses, small E);
  * ``fused_rank_fwd`` — the streaming rank engine: each grid step compares
    its tile against the per-query gold score and accumulates
    ``rank_j += Σ 1[score > gold]`` into a (B, 1) int32 output that is
    revisited across the entity grid axis (index_map ignores j), with filter
    exclusion applied in-kernel from a padded known-true index tensor. The
    (B, E) matrix never exists anywhere.

The fused-rank kernel's ``cl1`` (RotatE) path takes its rows as lane-aligned
planes (``cl1_planes``): the re and im halves each padded to a multiple of
128 lanes, so no row is sliced inside a lane tile (Mosaic refuses the
half-width slices of ``_tile_scores``). A grid step scores its entity block
``CL1_ROWS`` rows at a time and reduces the d/2 components in 128-lane
chunks, accumulated before one lane reduction: the (Bq, Be, d/2) difference
tensor never exists, and the padded lanes add exactly 0. The entity block
is sized from the row width (``cl1_block_e``) so that its two pipeline
buffers fit scoped VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: score tile modes: L1/L2 Minkowski (negated distance), plain dot product,
#: or complex-L1 ("cl1": rows are [re | im] halves, per-component modulus —
#: the RotatE distance)
SCORE_MODES = ("l1", "l2", "dot", "cl1")


def _tile_scores(q: jnp.ndarray, e: jnp.ndarray, mode: str) -> jnp.ndarray:
    """(Bq, d) × (Be, d) → (Bq, Be) scores, higher = better."""
    if mode == "dot":
        return jax.lax.dot_general(
            q, e, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
    if mode == "l2":
        qq = jnp.sum(q * q, axis=1)[:, None]
        ee = jnp.sum(e * e, axis=1)[None, :]
        qe = jax.lax.dot_general(
            q, e, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        d2 = jnp.maximum(qq - 2.0 * qe + ee, 0.0)
        return -jnp.sqrt(d2 + 1e-12)
    if mode == "cl1":
        d2 = q.shape[1] // 2
        dr = q[:, None, :d2] - e[None, :, :d2]  # (Bq, Be, d/2)
        di = q[:, None, d2:] - e[None, :, d2:]
        return -jnp.sum(jnp.sqrt(dr * dr + di * di + 1e-12), axis=-1)
    diff = jnp.abs(q[:, None, :] - e[None, :, :])  # (Bq, Be, d)
    return -jnp.sum(diff, axis=-1)


def _score_kernel(q_ref, e_ref, o_ref, *, mode: str):
    q = q_ref[...].astype(jnp.float32)  # (Bq, d)
    e = e_ref[...].astype(jnp.float32)  # (Be, d)
    o_ref[...] = _tile_scores(q, e, mode).astype(o_ref.dtype)


def pairwise_scores_fwd(
    q: jnp.ndarray,  # (B, d) queries (h + r)
    ent: jnp.ndarray,  # (E, d) entity table
    *,
    mode: str = "l1",
    block_q: int = 8,
    block_e: int = 256,
    interpret: bool = True,
) -> jnp.ndarray:
    b, d = q.shape
    e, _ = ent.shape
    assert mode in SCORE_MODES, mode
    block_q = min(block_q, b)
    block_e = min(block_e, e)
    assert b % block_q == 0 and e % block_e == 0, (b, e, block_q, block_e)
    kernel = functools.partial(_score_kernel, mode=mode)
    return pl.pallas_call(
        kernel,
        grid=(b // block_q, e // block_e),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_e, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_e), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, e), jnp.float32),
        interpret=interpret,
    )(q, ent)


# --------------------------------------------------------------------------
# streaming fused-rank kernel
# --------------------------------------------------------------------------
def _fused_rank_kernel(
    q_ref,      # (Bq, d) query block
    g_ref,      # (Bq, 1) gold score per query
    f_ref,      # (Bq, F) known-true entity ids (pad −1; gold always present)
    e_ref,      # (Be, d) entity block
    o_ref,      # (Bq, 1) int32 rank counts — revisited across j
    *,
    mode: str,
    block_e: int,
    num_entities: int,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    q = q_ref[...].astype(jnp.float32)
    e = e_ref[...].astype(jnp.float32)
    s = _tile_scores(q, e, mode)  # (Bq, Be)
    bq, be = s.shape

    # global entity ids of this tile's columns; ids ≥ num_entities are padding
    col = j * block_e + jax.lax.broadcasted_iota(jnp.int32, (bq, be), 1)
    beats = _beats(s, col, g_ref, f_ref, num_entities)
    o_ref[...] += jnp.sum(beats.astype(jnp.int32), axis=1, keepdims=True)


def _beats(s, col, g_ref, f_ref, num_entities: int):
    """(Bq, n) bool: column ``col`` is a real entity, outside the query's
    known-true ids, that scores above the query's gold score."""
    valid = col < num_entities
    # in-kernel filter: exclude every known-true id listed for each query
    filt = f_ref[...]  # (Bq, F) int32
    excl = jnp.any(filt[:, :, None] == col[:, None, :], axis=1)  # (Bq, n)
    return (s > g_ref[...]) & valid & jnp.logical_not(excl)


#: lanes of one vector register; each cl1 plane is padded to a multiple
LANES = 128
#: entity rows a cl1 grid step scores at a time inside its block
CL1_ROWS = 128
#: bytes of one cl1 entity block; the pipeline holds two
CL1_BLOCK_BYTES = 4 << 20


def cl1_plane_width(dim: int) -> int:
    """Lanes of a row of cl1 planes: [re | im], each half padded to 128."""
    return 2 * pl.cdiv(dim // 2, LANES) * LANES


def cl1_block_e(block_e: int, dim: int, n: int) -> int:
    """The cl1 entity block for ``n`` entities: at most ``block_e`` rows and
    ``CL1_BLOCK_BYTES`` of planes, and a multiple of ``CL1_ROWS`` when it
    holds more than that (a table smaller than the block is padded up to
    whole chunks, never cut to its own length)."""
    cap = min(block_e, CL1_BLOCK_BYTES // (4 * cl1_plane_width(dim)))
    if min(cap, n) <= CL1_ROWS:
        return min(cap, n)
    return min(cap // CL1_ROWS, pl.cdiv(n, CL1_ROWS)) * CL1_ROWS


def cl1_planes(x: jnp.ndarray, rows: int) -> jnp.ndarray:
    """(n, d) [re | im] rows → (rows, cl1_plane_width(d)) float32 planes:
    rows past n and lanes past d/2 in each half are zero. Built as a select
    between two pads of the whole row (re at lane 0, im shifted to the
    second plane), which XLA fuses into one pass over the table; slicing the
    halves first costs a second pass."""
    n, d = x.shape
    half, w = d // 2, cl1_plane_width(d) // 2
    x = x.astype(jnp.float32)
    re = jnp.pad(x, ((0, rows - n), (0, 2 * w - d)))
    im = jnp.pad(x, ((0, rows - n), (w - half, w - half)))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * w), 1)
    return jnp.where(lane < half, re,
                     jnp.where((lane >= w) & (lane < w + half), im, 0.0))


def _cl1_plane_scores(q_ref, e_ref, r0, rows: int, half: int) -> jnp.ndarray:
    """(Bq, rows) RotatE scores −Σ_k |q_k − e_k| of the query planes against
    entity planes ``r0 : r0 + rows``, over ``half`` complex components in
    128-lane chunks."""
    dh = q_ref.shape[1] // 2
    acc = None
    for c in range(0, dh, LANES):
        re, im = pl.ds(c, LANES), pl.ds(dh + c, LANES)
        ents = pl.ds(r0, rows)
        dr = q_ref[:, re][:, None, :] - e_ref[ents, re][None]  # (Bq, rows, 128)
        di = q_ref[:, im][:, None, :] - e_ref[ents, im][None]
        m = jnp.sqrt(dr * dr + di * di + 1e-12)
        if c + LANES > half:  # padding lanes: exactly 0, not sqrt(1e-12)
            lane = jax.lax.broadcasted_iota(jnp.int32, m.shape, 2)
            m = jnp.where(lane < half - c, m, 0.0)
        acc = m if acc is None else acc + m
    return -jnp.sum(acc, axis=-1)


def _fused_rank_cl1_kernel(
    q_ref,      # (Bq, W) query planes
    g_ref,      # (Bq, 1) gold score per query
    f_ref,      # (Bq, F) known-true entity ids (pad −1; gold always present)
    e_ref,      # (Be, W) entity planes
    o_ref,      # (Bq, 1) int32 rank counts — revisited across j
    *,
    half: int,
    block_e: int,
    num_entities: int,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bq = q_ref.shape[0]
    rows = min(CL1_ROWS, block_e)

    def chunk(k, acc):
        r0 = pl.multiple_of(k * rows, rows)
        s = _cl1_plane_scores(q_ref, e_ref, r0, rows, half)
        col = j * block_e + r0 + jax.lax.broadcasted_iota(jnp.int32, (bq, rows), 1)
        beats = _beats(s, col, g_ref, f_ref, num_entities)
        return acc + jnp.sum(beats.astype(jnp.int32), axis=1, keepdims=True)

    o_ref[...] += jax.lax.fori_loop(
        0, block_e // rows, chunk, jnp.zeros((bq, 1), jnp.int32)
    )


def fused_rank_fwd(
    q: jnp.ndarray,     # (B, d)
    ent: jnp.ndarray,   # (E_pad, d) entity table (rows ≥ num_entities ignored)
    gold: jnp.ndarray,  # (B, 1) float32 gold scores
    filt: jnp.ndarray,  # (B, F) int32 known-true ids, pad −1
    *,
    mode: str = "l1",
    num_entities: int,
    block_q: int = 8,
    block_e: int = 256,
    interpret: bool = True,
    half: int = 0,
) -> jnp.ndarray:
    """Filtered rank counts: out[i] = Σ_e 1[score(q_i, e) > gold_i], with
    entities listed in ``filt[i]`` and padding rows excluded. Rank = out + 1.

    For ``cl1``, ``q`` and ``ent`` are ``cl1_planes`` of rows with ``half``
    complex components, and ``block_e`` divides by ``CL1_ROWS`` when larger.
    """
    b, d = q.shape
    e, _ = ent.shape
    assert mode in SCORE_MODES, mode
    block_q = min(block_q, b)
    block_e = min(block_e, e)
    assert b % block_q == 0 and e % block_e == 0, (b, e, block_q, block_e)
    # the cl1 kernel scores its block in whole CL1_ROWS chunks
    assert mode != "cl1" or block_e <= CL1_ROWS or block_e % CL1_ROWS == 0, block_e
    f = filt.shape[1]
    name = None
    if mode == "cl1":
        kernel = functools.partial(
            _fused_rank_cl1_kernel, half=half, block_e=block_e,
            num_entities=num_entities,
        )
        name = "fused_rank_cl1"
    else:
        kernel = functools.partial(
            _fused_rank_kernel, mode=mode, block_e=block_e,
            num_entities=num_entities,
        )
    return pl.pallas_call(
        kernel,
        # j (entity axis) is the minormost grid dim → the (i, 0) output block
        # is revisited across consecutive steps: the accumulation grid.
        grid=(b // block_q, e // block_e),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_q, f), lambda i, j: (i, 0)),
            pl.BlockSpec((block_e, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1), jnp.int32),
        interpret=interpret,
        name=name,
    )(q, gold, filt, ent)
