"""Versioned serving tables: what a KGE serving process actually holds.

Two pieces, split by lifetime:

``FilterPack`` — the padded CSR known-true filter over (h, r) keys, built
ONCE from the owner's known triples. The pad width is a power-of-two bucket
over the longest row, so every batch sliced from it has the same trailing
extent and the rank/top-k jits never retrace on filter width (the seed
ranker recomputed ``max(len(v) for v in hr_t.values())`` and rebuilt Python
row lists per request). Known triples outlive table versions — the same
pack serves every published version.

``TableVersion`` — one immutable published snapshot of an owner's embedding
tables: the params dict, a per-version non-finite-row bitmask (computed once
at publish with one on-device reduction per table; request validation is an
O(B) host lookup instead of pulling embedding rows per call), and, per
device it is committed to (``on(device)``), the tables the serving programs
read. Because the owner-sticky federation keeps accepted params committed
to the owner's home device, committing a fresh version to replica 0 needs
no transfer.

**The staged entity table.** For the decomposable families (TransE,
DistMult, ComplEx, RotatE) ``on(device)`` stages the entity table once, in
the layout the serving programs read it in
(``kernels.triple_score.stage_table``): rows padded with zeros to whole
entity blocks, lanes to whole 128-lane tiles — for RotatE the lane-aligned
``[re | im]`` planes of the ``cl1`` kernel, for ComplEx ``[ent | ent_im]``
— which, being whole lane tiles wide, the device holds row-major
(``on`` checks it). The device keeps a table whose width is not a whole
number of lanes column-major, so a program that read the published table
by rows would transpose it first, and pad or lay out the planes, on every
call; staged, no rank or top-k call writes a table-sized array.

The cost is one more entity table in device memory per version and device
(``staged_bytes``: 2.01 GB for Dbpedia's 491,078 RotatE rows of 1,000
reals, 0.25 GB for its TransE rows of 100), written once per version and
device (the serving tier counts them in ``stats["staged"]``). It is the
only extra table: the served dict drops ``ent``/``ent_im``, so a replica
that received the params by transfer frees them once staged, and only the
published params (``TableVersion.params``, which the owner holds anyway)
keep the table as trained. A ``KGECandidateRanker``, which holds its
params itself, therefore keeps two entity tables. While a publish flips
versions, the old and the new staged table are both held. TransH/R/D
project the entity table per relation and are served from their published
tables.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributed import committed_device
from repro.kernels.triple_score.ops import stage_table
from repro.kge.eval import _filter_mask, pack_padded_filters
from repro.kge.models import lp_layout, lp_table


def _pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


class FilterPack:
    """Padded CSR filter rows for tail queries, one row per known (h, r) key
    plus a trailing all(−1) sentinel row for unknown keys."""

    def __init__(self, known_triples, num_entities: int):
        known = (
            np.zeros((0, 3), np.int64) if known_triples is None
            else np.asarray(known_triples)
        )
        self.num_entities = int(num_entities)
        self.hr_t, self.rt_h = _filter_mask(known, num_entities)
        rows: List[List[int]] = [sorted(v) for v in self.hr_t.values()]
        self._row_of: Dict[Tuple[int, int], int] = {
            k: i for i, k in enumerate(self.hr_t)
        }
        maxw = max((len(x) for x in rows), default=1)
        self.width = _pow2(maxw)
        # sentinel row (all −1) appended so unknown keys index real storage
        self.rows = pack_padded_filters(rows + [[]], width=self.width)

    def row_index(self, h: np.ndarray, r: np.ndarray) -> np.ndarray:
        sentinel = len(self.rows) - 1
        get = self._row_of.get
        return np.fromiter(
            (get((int(hh), int(rr)), sentinel) for hh, rr in zip(h, r)),
            np.int64, count=len(h),
        )

    def rows_for(self, h: np.ndarray, r: np.ndarray) -> np.ndarray:
        """(B, width) int32 known-tail filter rows for (h, r) queries — one
        fancy-index slice, no per-request Python row building."""
        return self.rows[self.row_index(h, r)]


def check_id_range(name: str, ids, limit: int) -> np.ndarray:
    """Serving boundary: ids arrive from untrusted callers, and an
    out-of-range id would otherwise gather from the wrong row (negative
    wraps) or crash deep inside a jitted kernel with a shape error."""
    ids = np.asarray(ids, np.int64).reshape(-1)
    bad = ids[(ids < 0) | (ids >= limit)]
    if bad.size:
        raise ValueError(
            f"{name} ids must be in [0, {limit}); got "
            f"{bad[:5].tolist()}{'…' if bad.size > 5 else ''}"
        )
    return ids


def _bad_row_mask(params, keys, n: int) -> np.ndarray:
    """(n,) bool: rows with any NaN/Inf in any of the named tables. The
    finiteness reduction runs on device; only the boolean vector lands on
    host. Tables longer than ``n`` (virtual-entity extensions) only
    contribute their first ``n`` rows — ids beyond ``n`` are rejected by
    range validation before this mask is ever consulted."""
    bad = np.zeros(n, np.bool_)
    for k in keys:
        tab = params.get(k)
        if tab is None:
            continue
        m = np.asarray(jnp.logical_not(jnp.isfinite(tab).all(axis=-1)))
        bad[: m.shape[0]] |= m[:n]
    return bad


@functools.partial(jax.jit, static_argnames=("model", "block_e"))
def _stage(params, model, block_e: int) -> jnp.ndarray:
    return stage_table(lp_table(params, model), lp_layout(model)[0], block_e)


class TableVersion:
    """One immutable published (owner, version) snapshot of serving tables,
    staged for the serving programs' ``block_e`` on each device it serves
    from (module docstring)."""

    def __init__(self, params, model, filters: FilterPack, *,
                 block_e: int, version: int = 0,
                 owner: Optional[str] = None):
        self.params = dict(params)
        self.model = model
        self.filters = filters
        self.version = int(version)
        self.owner = owner
        self.block_e = int(block_e)
        #: rows of the entity table the programs rank (virtual-entity
        #: extensions included); staged rows past it are padding
        self.entities = int(self.params["ent"].shape[0])
        self.ent_bad = _bad_row_mask(self.params, ("ent", "ent_im"),
                                     model.num_entities)
        self.rel_bad = _bad_row_mask(self.params, ("rel", "rel_im"),
                                     model.num_relations)
        #: the served tables per device (the tick engine's ``_resident_on``
        #: idiom); populated lazily by ``on()`` / eagerly by tier publish
        self._ondev: Dict = {}
        #: explicit cross-device copies made for this version — stays 0 for
        #: the device the params are already committed to (zero-copy flip)
        self.transfers = 0
        #: devices this version's entity table is staged on, and the device
        #: bytes of those staged tables
        self.staged = 0
        self.staged_bytes = 0

    def on(self, device) -> Dict[str, jnp.ndarray]:
        """The tables the serving programs read on ``device`` (``None``:
        wherever the params are), built on first use — one explicit
        transfer where the params live elsewhere, and for decomposable
        families one staging of the entity table — and referenced in place
        afterwards."""
        got = self._ondev.get(device)
        if got is None:
            home = committed_device(self.params)
            if device is None or home == device:
                got = self.params  # already resident — zero-copy
            else:
                got = jax.device_put(self.params, device)
                self.transfers += 1
            if lp_layout(self.model) is not None:
                staged = _stage(got, self.model, self.block_e)
                # whole lane tiles wide, the staged table is row-major:
                # the layout the serving programs take without a copy
                assert staged.format.layout.major_to_minor == (0, 1)
                got = {k: v for k, v in got.items()
                       if k not in ("ent", "ent_im")}
                got["staged"] = staged
                self.staged += 1
                self.staged_bytes += staged.nbytes
            self._ondev[device] = got
        return got

    def check_finite(self, name: str, bad_mask: np.ndarray,
                     ids: np.ndarray) -> None:
        """O(B) bitmask lookup replacing the per-request host pull of
        embedding rows; same refusal semantics, id named."""
        bad = ids[bad_mask[ids]]
        if bad.size:
            raise ValueError(
                f"non-finite query embedding: {name} ids "
                f"{bad[:5].tolist()}{'…' if bad.size > 5 else ''} "
                f"have NaN/Inf rows in this table version"
            )
