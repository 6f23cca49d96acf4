"""The work of RotatE scoring, counted from logical shapes as ``work.py``
counts TransE's: real query rows, real entities, the table's d = 2n floats a
row, nothing of planes, lane padding, buckets or blocks.

A complex-modulus term |q_k - t_k| counts as 7 operations: two differences,
two squares, their sum, the square root and the accumulation. A query's
rotation h o r counts as 8 n (cosine, sine, four products, two sums).

Beside the counts, how the device trace names the kernel and the planes op
that the RotatE metric readers read.
"""
from __future__ import annotations

import math
import re

from bench.work import F32, Work


def cl1_pairs(queries: int, entities: int, dim: int) -> float:
    """Operations of every (query, entity) score plus one comparison each
    (against the gold score or the running k-th best)."""
    return queries * entities * (7 * (dim // 2) + 1)


def cl1_rank(queries: int, entities: int, dim: int, filter_ids: int) -> Work:
    """Filtered rank counts of ``queries`` real queries against the whole
    entity table: each query's rotation and gold score, then every pair.
    Reads the table, each query's head row and phases, the gold entities
    and the ``filter_ids`` known-true ids once, writes one count a query."""
    n = dim // 2
    flops = cl1_pairs(queries, entities, dim) + queries * (8 * n + 7 * n)
    byts = F32 * (entities * dim + queries * (dim + n) + queries * dim
                  + filter_ids + queries)
    return Work(flops, byts)


# How the device trace names the RotatE rank path's operations (an event's
# name is its HLO text, without scopes).
_CL1_KERNEL = re.compile(r"^%fused_rank_cl1(\.\d+)? = .*custom_call_target=\"tpu_custom_call\"")
_RESULT = re.compile(r"^%[\w.\-]+ = f32\[(\d+),(\d+)\]\{")


def is_cl1_kernel(hlo: str) -> bool:
    """The Pallas call named ``fused_rank_cl1``: its HLO instruction is
    ``%fused_rank_cl1`` or ``%fused_rank_cl1.<n>``."""
    return bool(_CL1_KERNEL.match(hlo))


def planes_width(dim: int) -> int:
    """Lanes of a row of the kernel's [re | im] planes: each half of d/2
    components padded to a multiple of 128."""
    return 2 * 128 * math.ceil((dim // 2) / 128)


def is_planes_op(hlo: str, entities: int, dim: int) -> bool:
    """The op that writes the table's planes: result ``f32[R,W]`` with W
    ``planes_width(dim)`` and R the entity count padded to a block (at
    least the entity count, less than it plus 2,048), not a custom call."""
    m = _RESULT.match(hlo)
    if not m or "custom-call(" in hlo:
        return False
    rows, width = int(m.group(1)), int(m.group(2))
    return width == planes_width(dim) and entities <= rows < entities + 2048
