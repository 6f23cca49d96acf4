"""The work an algorithm requires, counted from logical shapes.

Counts are of the mathematics, not of an implementation: they take real
query rows, real triples and real table sizes, and nothing of tiling,
bucket padding, block sizes or which kernel runs, so a kernel's roofline
share reads the same work whatever implements it. Floats are 4 bytes.
Multiply-adds count as 2 operations; an L1 distance term (subtract, absolute
value, accumulate) counts as 3.

A least time is max(operations / peak operations per second, bytes / peak
bytes per second) with the peaks of ``peaks.json``.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

F32 = 4
PEAKS = Path(__file__).resolve().parent / "peaks.json"


@dataclass(frozen=True)
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in {PEAKS.name}")
    return table[device_kind]


def least_seconds(w: Work, device_kind: str):
    """(least seconds, "flops" | "bytes"): which peak bounds the work."""
    p = peaks(device_kind)
    tf = w.flops / p["flops_per_s"]
    tb = w.bytes / p["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")


def l1_rank(queries: int, entities: int, dim: int, filter_ids: int) -> Work:
    """Filtered rank counts of ``queries`` real queries against the whole
    entity table: every (query, entity) L1 distance, the gold score, and
    one comparison per entity. Reads the table, the queries, the gold
    entities and the ``filter_ids`` known-true ids once, writes one count
    per query."""
    flops = queries * entities * (3 * dim + 1) + queries * 3 * dim
    byts = F32 * (entities * dim + queries * dim + queries + filter_ids + queries)
    return Work(flops, byts)


def l1_topk(queries: int, entities: int, dim: int, filter_ids: int, k: int) -> Work:
    """Top-k tails of ``queries`` real queries: every L1 distance plus one
    comparison per entity against the running k-th best; reads as
    ``l1_rank`` and writes k ids and k scores per query."""
    flops = queries * entities * (3 * dim + 1)
    byts = F32 * (entities * dim + queries * dim + filter_ids + 2 * k * queries)
    return Work(flops, byts)


def _mlp(rows: int, dim: int, hidden: int) -> float:
    """Forward pass of a PPAT discriminator (d -> hidden -> 1) on ``rows``."""
    return 2.0 * rows * (dim * hidden + hidden)


def ppat(steps: int, batch: int, dim: int, hidden: int, teachers: int) -> Work:
    """Alg. 2: per round the generator maps a batch (B x d @ d x d), each
    teacher trains on its B/T fake and B/T real rows (forward and backward:
    3 forward passes), every teacher votes on the batch, the student trains
    on it, the generator's gradient goes back through the student, and W
    takes its update and the orthogonality step (W - b (W W^T) W)."""
    per = batch // teachers
    step = (
        2.0 * batch * dim * dim                      # adv = X_b W
        + teachers * 3 * _mlp(2 * per, dim, hidden)  # teacher updates
        + teachers * _mlp(batch, dim, hidden)        # votes
        + 3 * _mlp(batch, dim, hidden)               # student update
        + 3 * _mlp(batch, dim, hidden)               # generator gradient
        + 2.0 * dim * batch * dim                    # X_b^T dL/dG
        + 4.0 * dim ** 3                             # orthogonality step
    )
    params = dim * dim + (teachers + 1) * (dim * hidden + 2 * hidden + 1)
    # the rows drawn each round, and the parameters read and written once
    # per round (weights and momentum)
    byts = F32 * steps * (2 * batch * dim + 4 * params)
    return Work(steps * step, byts)


def align(aligned: int, dim: int, virtual_ent: int, virtual_rel: int) -> Work:
    """After PPAT: synthesize the aligned rows (X W), the procrustes
    refinement (M = S^T Y, its SVD counted as 22 d^3, S R), the averaging
    write into the host table, and the translated virtual rows."""
    flops = (
        3 * 2.0 * aligned * dim * dim + 22.0 * dim ** 3
        + aligned * dim * 2
        + 2 * 2.0 * (virtual_ent + virtual_rel) * dim * dim
    )
    byts = F32 * dim * (
        3 * aligned                               # X, Y read; S written
        + 2 * aligned                             # host rows read and written
        + 2 * (virtual_ent + virtual_rel)         # virtual rows read, written
    )
    return Work(flops, byts)


def transe_epoch(triples: int, batch: int, entities: int, dim: int) -> Work:
    """One epoch of margin SGD over ``triples`` real triples with 1:1
    corruption: per minibatch the B positive and B negative L1 scores and
    their gradients, reading and writing the at most 3B entity and B
    relation rows a batch touches; then one pass that keeps every entity
    row inside the unit ball."""
    steps = -(-triples // batch)
    per_step = Work(
        flops=2 * batch * 2 * (4.0 * dim) + 2 * batch * dim,
        bytes=F32 * (2 * 4 * batch * dim + 2 * 3 * batch),
    )
    renorm = Work(flops=3.0 * entities * dim, bytes=2.0 * F32 * entities * dim)
    return per_step * steps + renorm


def hit10(queries: int, entities: int, dim: int, filter_ids: int) -> Work:
    """Backtrack scoring: filtered tail and head ranks of ``queries``
    validation triples."""
    return l1_rank(queries, entities, dim, filter_ids) * 2


def triple_scores(triples: int, dim: int) -> Work:
    """Accuracy scoring: one L1 score per (valid or corrupted) triple,
    reading its three rows."""
    return Work(flops=triples * 4.0 * dim, bytes=F32 * triples * (3 * dim + 1))
