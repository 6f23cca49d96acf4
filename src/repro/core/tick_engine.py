"""Batched federation tick engine — one device program per scheduler tick.

After PR 1/PR 2 made eval and local training device-resident, a federation
tick was still a serial Python loop: each Ready owner got its own
``train_ppat`` call, its own retrain dispatch, and its own backtrack-score
call, with eager aggregation glue (gathers, procrustes, scatters, virtual
extension) and host syncs between every stage. Tick wall-clock grew linearly
in owner count and the device idled between handshakes.

This engine turns the scheduler into a *planner*: at tick start it collects
every Ready owner's pending work into a tick plan — (client → host)
handshake pairs plus self-train owners — and executes the whole tick on
device with host syncs only at the tick boundary. Each plan entry is an
independent program that chains the full pipeline in-graph:

    PPAT (init + all adversarial rounds) → synthesize + procrustes refine →
    KGEmb aggregation (entity/relation scatter) → virtual extension →
    bucket-padded retrain scan → strip → backtrack scoring
    (accuracy threshold scores or fused-rank hit@10 counts)

Host-side work per tick shrinks to: splitting keys, the accept/reject
decisions, snapshot/broadcast bookkeeping, and the moments accountant.

**Trace-time program dedup.** Entries are grouped by signature — the static
``EntrySpec`` plus the input pytree's shapes/dtypes (``entry_signature``) —
and one program is traced and compiled per unique signature, not per owner:
N equal-shaped owners (the paper's decentralized deployments are exactly
this) compile ONE tick-entry program where the PR 3 whole-tick mega-program
compiled N identical subgraph copies (~1 min one-time for 8 owners on CPU
CI). All entry dispatches are asynchronous; the engine blocks once, at the
end of the tick.

**Multi-device placement** (``kernels.dispatch.resolve_tick_placement`` /
``REPRO_TICK_PLACEMENT``): with ``placement="sharded"`` (the ``auto``
default whenever >1 device is visible — on CPU CI via
``XLA_FLAGS=--xla_force_host_platform_device_count=N``) a signature bucket
of equal-shaped owners is stacked along a leading owner axis and executed
by one ``shard_map`` SPMD program over the ``("owners",)`` mesh
(``core.distributed.owner_shard_map``) — one device per owner, still ONE
compile per bucket. ``placement="single"`` keeps every entry program on the
default device.

**Owner-sticky device residency** (``kernels.dispatch.resolve_tick_residency``
/ ``REPRO_TICK_RESIDENCY``): every owner gets a sticky home device from the
engine's ``core.distributed.OwnerPlacement`` registry — assigned once, in
registration order, and stable across plan recomposition — and its state
LIVES there across ticks:

  * immutable per-owner inputs (padded triple stores, aligned-index uploads,
    virtual-extension id sets, backtrack-scoring negatives/CSR filters, and
    the per-entry scalars) are cached per (owner, version, device): committed
    once on first use and re-referenced every subsequent tick — the
    steady-state tick performs ZERO ``device_put`` of cached immutable
    inputs (pinned by the transfer-guard regression test);
  * shard_map group operands are assembled zero-copy from the resident
    per-owner shards via ``jax.make_array_from_single_device_arrays``
    (``core.distributed.assemble_group``) instead of ``jnp.stack`` +
    re-shard, and group outputs are split back into still-committed
    per-owner shards (``disassemble_group``) — an owner's new embedding
    tables never leave its device; only the scalar decisions/scores/ε sync
    to host;
  * with ``residency="resident"`` (default) accepted params stay committed
    to the owner's device in trainer state — non-sharded consumers (the
    serial ``tick_impl="reference"`` path, eval, checkpointing, serving)
    accept committed arrays; ``residency="normalize"`` restores the old
    normalize-to-device-0 behavior;
  * signature buckets are cut into chunks whose extents are restricted to
    full-mesh or power-of-two sizes (``core.distributed.chunk_extents``),
    partial chunks padded with masked dummy entries (replicas of a real
    entry whose outputs are discarded) — a bucket shrinking by one owner
    re-pads into an already-compiled extent, capping group compiles per
    signature at ~log₂(devices) instead of one per exact bucket size;
  * the per-tick mutable leaves — params (already resident after the first
    tick), PPAT/train keys, and the tick-consistent client views (the
    paper's actual client → host communication) — move via explicit
    ``jax.device_put`` only.

Why per-entry programs / shard_map slices and not ``vmap``/``lax.map``
stacking: XLA recompiles a stacked body in a different fusion context,
which drifts results by ~1 ulp — enough to (rarely) flip an accept/reject
decision, and enough to break the bit-parity contract with the serial
reference path. The standalone entry program and the per-device shard_map
body, however, compile the SAME unstacked per-entry trace the serial path
jits (pinned by the tick parity tests at ≥4 simulated devices).

Everything immutable is cached across ticks per (client, host) pair or per
owner: aligned-index uploads, virtual-extension structure (neighbor ids,
joining relations, remapped adjacency triples), bucket-padded extended
triple stores, and backtrack-scoring inputs (fixed negatives, CSR filters).

**Level-aware streaming** (``tick_sync="stream"``): the streaming scheduler
calls ``execute`` once per dependency level instead of once per tick. The
engine is level-ready by construction: entry inputs (params, client views,
engine keys) are materialized at CALL time and every accept/restore is
applied before ``execute`` returns, so an update accepted at level k is
live state when level k+1's protos are built — the result feeding that
lets a same-pass re-offer handshake read a fresher version. Reaping stays
per-entry and asynchronous within a level (one ``block_until_ready`` per
entry, group fallback included), so a level's slowest entry bounds only
its own level, not the pass. Streamed passes carry pre-split PPAT keys on
their entries (``TickEntry.key_ppat``, assigned in plan order) so the
scheduler key stream is consumed in barrier order no matter how the level
cut interleaves owners.

Bit-parity contract (asserted by ``tests/test_tick_engine.py`` and the tick
benchmark): with the same per-pair keys, a batched tick produces the same
accept/reject decisions, the same scores, the same ε history, and
bit-identical embeddings as ``tick_impl="reference"`` — per level under
streaming exactly as per tick under the barrier.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.alignment import procrustes
from repro.core.ppat import PPAT_BUCKET, PPATConfig, _pad_rows, ppat_entry_graph
from repro.core.privacy import MomentsAccountant
from repro.kge.engine import (
    pad_tables,
    pad_triples,
    resolve_renorm,
    shape_spec,
    strip_tables,
    train_scan_graph,
)
from repro.kge.eval import side_counts_graph
from repro.kge.models import KGEModel, score_triples


# ---------------------------------------------------------------------------
# per-entry static spec + traced graph
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class EntrySpec:
    """Static (hashable) trace parameters for one tick-plan entry. Together
    with the input-array shapes it fully determines the entry subgraph; the
    tick program cache is keyed on the tuple of specs (jit re-specializes on
    shapes underneath)."""

    kind: str                  # "ppat" | "self-train"
    model: KGEModel            # logical-count model of the host owner
    epochs: int
    batch: int
    train_impl: str
    interpret: bool
    renorm: str                # entity-norm schedule, resolved at plan time
    cfg: Optional[PPATConfig]  # PPAT config (ppat entries only)
    aggregation: str
    refine: bool               # procrustes refinement on the DP release
    score: str                 # "accuracy" | "hit10" | "none"
    lp_batch: int              # hit10 chunk size (mirrors link_prediction)
    block_e: int
    #: Byzantine robust-acceptance mode over synthesized rows (ppat entries;
    #: "none" keeps the defenses-off trace byte-identical)
    robust: str = "none"
    #: whether the entry emits the cosine-shift screen statistic
    cos: bool = False


def _extend_params(
    p: Dict[str, jnp.ndarray], model: KGEModel, v_ent, v_rel
) -> Dict[str, jnp.ndarray]:
    """In-graph twin of ``KGETrainer.extend_tables`` — the per-family pad
    rules come from the same ``virtual_pad_rows`` definition."""
    from repro.kge.models import virtual_pad_rows

    p = dict(p)
    p["ent"] = jnp.concatenate([p["ent"], v_ent])
    p["rel"] = jnp.concatenate([p["rel"], v_rel])
    pads = virtual_pad_rows(p, model.dim, v_ent.shape[0], v_rel.shape[0])
    for k, pad in pads.items():
        p[k] = jnp.concatenate([p[k], pad])
    return p


def entry_graph(inp: Dict[str, jnp.ndarray], spec: EntrySpec) -> Dict:
    """One plan entry's full pipeline as a pure graph.

    Every stage calls the SAME functions the serial path traces
    (``ppat_entry_graph``, ``train_scan_graph``, ``side_counts_graph``,
    ``score_triples``) on identically-shaped inputs, so the per-entry
    subgraph is the serial path's compiled computation — the root of the
    batched-vs-reference bit-parity guarantee.
    """
    model = spec.model
    p = inp["params"]
    out: Dict = {}
    n_virt_e = n_virt_r = 0

    if spec.kind == "ppat":
        ce = inp["client_ent"]
        if "rel_c" in inp:
            # relation-aligned pairs keep exact-shape glue (rare; the
            # concatenated [ent | rel] layout cannot be segment-padded
            # without changing the PPAT sampling space)
            x = jnp.concatenate([ce[inp["idx_c"]],
                                 inp["client_rel"][inp["rel_c"]]])
            y = jnp.concatenate([p["ent"][inp["idx_h"]],
                                 p["rel"][inp["rel_h"]]])
            n_true = x.shape[0]
            x = _pad_rows(x, PPAT_BUCKET)
            y = _pad_rows(y, PPAT_BUCKET)
        else:
            # bucket-padded glue: index arrays are PPAT_BUCKET-padded at
            # plan time (client gathers clamp, host slots point one past the
            # table), rows beyond the true count are masked to the exact
            # zeros ``_pad_rows`` would produce — one compiled program
            # serves every pair whose alignment lands in the same bucket
            mask = (jnp.arange(inp["idx_c"].shape[0]) < inp["n_x"])[:, None]
            x = jnp.where(mask, ce[inp["idx_c"]], 0.0)
            y = jnp.where(mask, p["ent"][inp["idx_h"]], 0.0)
        hp, w, metrics, n0s, n1s = ppat_entry_graph(
            x, y, inp["n_x"], inp["n_y"], inp["key_ppat"], spec.cfg,
        )
        # hp is returned (not used host-side) so this subgraph keeps the
        # same live outputs as the serial _ppat_entry program
        out["ppat_host"], out["ppat_metrics"] = hp, metrics
        out["n0s"], out["n1s"] = n0s, n1s

        # DP-synthesized embeddings for the aligned set (host side); zero
        # padding rows synthesize to zero and add exact zeros to the
        # procrustes contraction — same shapes, same bits as the serial path
        synth = x @ w
        refine_mat = None
        if spec.refine:
            refine_mat = procrustes(synth, y)
            synth = synth @ refine_mat
        if spec.robust != "none" or spec.cos:
            # robust acceptance over the ENTITY rows of the synthesized
            # release (relation glue rows pass through untouched), on the
            # same padded shapes the serial path hands robust_rows — the
            # defenses-armed parity contract
            from repro.core.aggregation import robust_rows_graph

            n_rob = (
                jnp.int32(inp["idx_c"].shape[0]) if "rel_c" in inp
                else inp["n_x"]
            )
            synth, mean_cos = robust_rows_graph(
                y, synth, n_rob, mode=spec.robust, want_cos=spec.cos,
            )
            if spec.cos:
                out["mean_cos"] = mean_cos
        p = dict(p)
        if "rel_c" in inp:
            n_ent = inp["idx_c"].shape[0]
            new_ent = synth[:n_ent]
            if spec.aggregation == "average":
                new_ent = 0.5 * (p["ent"][inp["idx_h"]] + new_ent)
            p["ent"] = p["ent"].at[inp["idx_h"]].set(new_ent)
            cur = p["rel"][inp["rel_h"]]
            new = synth[n_ent:n_true]
            if spec.aggregation == "average":
                new = 0.5 * (cur + new)
            p["rel"] = p["rel"].at[inp["rel_h"]].set(new)
        else:
            new_ent = synth
            if spec.aggregation == "average":
                new_ent = 0.5 * (p["ent"][inp["idx_h"]] + new_ent)
            # padded slots index one past the table → dropped
            p["ent"] = p["ent"].at[inp["idx_h"]].set(new_ent, mode="drop")

        if "neigh" in inp:  # virtual extension: G(N(X)) in host space
            if refine_mat is None:
                gen = lambda e: e @ w                    # noqa: E731
            else:
                gen = lambda e: (e @ w) @ refine_mat     # noqa: E731
            # neigh/rels are bucket-padded; rows past the true virtual
            # counts hold garbage but are inert — no triple references
            # them, the corruption bound (traced true count) keeps them
            # out of negatives, and the final strip slices them away
            v_ent = gen(ce[inp["neigh"]])
            v_rel = gen(inp["client_rel_full"][inp["rels"]])
            p = _extend_params(p, model, v_ent, v_rel)
            n_virt_e, n_virt_r = v_ent.shape[0], v_rel.shape[0]

    # ---- retrain (KGEmb-Update / self-train) on bucket-padded tables ----
    counts = dataclasses.replace(
        model,
        num_entities=model.num_entities + n_virt_e,
        num_relations=model.num_relations + n_virt_r,
    )
    padded, _, _ = pad_tables(p, counts)
    padded, losses = train_scan_graph(
        padded, inp["triples"], inp["key_train"], inp["lr"],
        inp["num_entities"],
        spec=shape_spec(model), epochs=spec.epochs, batch=spec.batch,
        impl=spec.train_impl, interpret=spec.interpret, renorm=spec.renorm,
    )
    out["losses"] = losses
    p = strip_tables(padded, model)  # bucket padding AND virtual rows off
    out["params"] = p

    # ---- backtrack scoring ---------------------------------------------
    if spec.score == "accuracy":
        va, vn = inp["va"], inp["va_neg"]
        sp = score_triples(p, model, va[:, 0], va[:, 1], va[:, 2])
        sn = score_triples(p, model, vn[:, 0], vn[:, 1], vn[:, 2])
        out["score"] = (sp, sn)
    elif spec.score == "hit10":
        test, ft, fh = inp["test"], inp["filt_t"], inp["filt_h"]
        chunks = []
        for i in range(0, test.shape[0], spec.lp_batch):
            j = i + spec.lp_batch
            c = test[i:j]
            kw = dict(block_e=spec.block_e)
            ct = side_counts_graph(
                p, model, c[:, 0], c[:, 1], c[:, 2], ft[i:j], side="tail", **kw
            )
            ch = side_counts_graph(
                p, model, c[:, 0], c[:, 1], c[:, 2], fh[i:j], side="head", **kw
            )
            chunks.append((ct, ch))
        out["score"] = tuple(chunks)
    return out


#: compiled per-entry programs, keyed by EntrySpec (jit further specializes
#: on input shapes — bucket padding keeps those stable, so steady-state
#: federation reuses one program per entry signature). The caches are
#: deliberately module-global with process lifetime, like jax.jit's own
#: compilation cache: schedulers over the same universe (parity tests, the
#: tick benchmark's reference/batched/sharded trio) share programs instead
#: of paying the compile per instance.
_ENTRY_PROGRAMS: Dict[EntrySpec, "jax.stages.Wrapped"] = {}

#: shard_map'ed group programs, keyed by (EntrySpec, group extent): one SPMD
#: program serves a whole signature bucket of equal-shaped owners
_GROUP_PROGRAMS: Dict[Tuple[EntrySpec, int], "jax.stages.Wrapped"] = {}


def _entry_program(spec: EntrySpec):
    prog = _ENTRY_PROGRAMS.get(spec)
    if prog is None:
        prog = jax.jit(functools.partial(entry_graph, spec=spec))
        _ENTRY_PROGRAMS[spec] = prog
    return prog


def _group_entry_graph(stacked: Dict, spec: EntrySpec) -> Dict:
    """shard_map body: each mesh device holds a local extent-1 slice of the
    stacked group inputs; dropping it runs the UNSTACKED entry graph — the
    identical trace (hence identical fusion, hence identical bits) to the
    single-entry program, unlike vmap/lax.map stacking (see module doc)."""
    inp = jax.tree.map(lambda x: x[0], stacked)
    out = entry_graph(inp, spec)
    return jax.tree.map(lambda x: x[None], out)


def _group_program(spec: EntrySpec, extent: int):
    key = (spec, extent)
    prog = _GROUP_PROGRAMS.get(key)
    if prog is None:
        from repro.core.distributed import owner_shard_map

        prog = jax.jit(
            owner_shard_map(
                functools.partial(_group_entry_graph, spec=spec), extent
            )
        )
        _GROUP_PROGRAMS[key] = prog
    return prog


def entry_signature(spec: EntrySpec, inp: Dict) -> Tuple:
    """The trace-time dedup key: the static spec plus the input pytree's
    structure/shapes/dtypes. Two plan entries with equal signatures are
    served by ONE traced-and-compiled program — N equal-shaped owners cost
    one compile, not N."""
    leaves, treedef = jax.tree.flatten(inp)
    return (
        spec, treedef,
        tuple((x.shape, str(jnp.result_type(x))) for x in leaves),
    )


def tick_program_cache_size() -> int:
    """Number of compiled tick-entry program specializations (single-entry
    and shard_map group programs together). Both tick-level invariants are
    asserted against this counter: steady-state ticks must not retrace, and
    N equal-shaped owners must compile exactly one program per unique entry
    signature — not one per owner."""
    progs = list(_ENTRY_PROGRAMS.values()) + list(_GROUP_PROGRAMS.values())
    return sum(p._cache_size() for p in progs)


# ---------------------------------------------------------------------------
# the engine: per-scheduler caches + tick execution
# ---------------------------------------------------------------------------
class TickEngine:
    """Executes a scheduler's tick plan as asynchronously dispatched,
    signature-deduped entry programs (optionally placed across devices),
    with one host sync per tick.

    Holds the cross-tick caches; everything cached is immutable for the
    scheduler's lifetime (KG splits, aligned index sets, virtual-extension
    structure, padded triple stores) or version-keyed on the owner's
    scoring universe (scoring inputs). Each cache entry's device leaves live
    under ``info["arrays"]`` and are committed per device on first use
    (``_resident_on``) — the owner-sticky placement registry keeps an owner
    on one device, so in steady state every cached input is referenced
    in place, never re-staged.
    """

    def __init__(self, sched):
        from repro.core.distributed import OwnerPlacement

        self.sched = sched
        self._pair: Dict[Tuple[str, str], Dict] = {}
        self._own: Dict[str, Dict] = {}
        self._score: Dict[str, Dict] = {}
        self._misc: Dict[str, Dict] = {}
        #: sticky owner → home device assignments (stable across plan
        #: recomposition; see core.distributed.OwnerPlacement)
        self.placement = OwnerPlacement()
        #: device_put count for per-device cache population — grows only on
        #: cache misses (first tick per (owner, version, device)), pinned
        #: flat across steady-state ticks by the transfer-guard test
        self.resident_transfers = 0
        #: entries launched inside a shard_map ``group`` program vs as a
        #: ``single`` per-entry program (singletons, ``placement="single"``
        #: and execution-time group fallbacks) — what the multi-chip smoke
        #: reads to show sharded placement really ran group programs
        self.dispatched = {"group": 0, "single": 0}

    # ------------------------------------------------------------- caches
    def _resident_on(self, info: Dict, device) -> Dict[str, jnp.ndarray]:
        """The committed-per-``device`` copy of a cache entry's array leaves,
        built (with ONE explicit transfer) on first use and referenced in
        place afterwards — steady-state ticks touch no cached bytes."""
        ondev = info.setdefault("_ondev", {})
        got = ondev.get(device)
        if got is None:
            got = jax.device_put(info["arrays"], device)
            ondev[device] = got
            self.resident_transfers += 1
        return got

    def _pair_info(self, client: str, host: str) -> Dict:
        key = (client, host)
        info = self._pair.get(key)
        if info is not None:
            return info
        from repro.kge.engine import ENT_BUCKET, REL_BUCKET, bucket

        sched = self.sched
        idx_c, idx_h = sched.registry.entities(client, host)
        rel = sched.registry.relations(client, host)
        has_rel = rel is not None and len(rel[0])
        host_tr = sched.trainers[host]
        e_log = host_tr.model.num_entities
        n_true = len(idx_c) + (len(rel[0]) if has_rel else 0)
        arrays: Dict[str, jnp.ndarray] = {}
        info = {"n_aligned": n_true, "arrays": arrays}
        # client-entity rows this handshake actually reads (aligned set,
        # plus virtual neighbors below) — the receiver-side corrupt screen
        # checks exactly these, matching the serial path's gather screens
        screen_idx = np.asarray(idx_c, np.int64)
        if has_rel:
            # exact-shape glue (see entry_graph) — no index padding
            arrays["idx_c"] = jnp.asarray(idx_c, jnp.int32)
            arrays["idx_h"] = jnp.asarray(idx_h, jnp.int32)
            arrays["rel_c"] = jnp.asarray(rel[0], jnp.int32)
            arrays["rel_h"] = jnp.asarray(rel[1], jnp.int32)
        else:
            # PPAT_BUCKET-padded index arrays → one compiled tick program
            # per alignment bucket, not per exact alignment size. Client
            # slots clamp to row 0 (rows are masked to zero in-graph); host
            # slots point one past the table so scatters drop them.
            n_pad = bucket(n_true, PPAT_BUCKET)
            ic = np.zeros(n_pad, np.int32)
            ic[:n_true] = idx_c
            ih = np.full(n_pad, e_log, np.int32)
            ih[:n_true] = idx_h
            arrays["idx_c"] = jnp.asarray(ic)
            arrays["idx_h"] = jnp.asarray(ih)
        n_virt = 0
        extra = None
        if sched.use_virtual:
            from repro.core.aggregation import virtual_structure

            vs = virtual_structure(
                sched.kgs[client], idx_c, idx_h,
                e_log, host_tr.model.num_relations,
            )
            if vs is not None:
                neigh, rels, extra = vs
                n_virt = len(neigh)
                # bucket-pad the virtual id sets too (slots clamp to row 0;
                # the resulting table rows are inert and stripped). Neighbor
                # counts vary by hundreds across pairs, so they round to a
                # power-of-two bucket — pair-to-pair variation must not
                # recompile the tick program.
                nv_pad = max(PPAT_BUCKET, 1 << (n_virt - 1).bit_length())
                nr_pad = bucket(len(rels), REL_BUCKET)
                npad = np.zeros(nv_pad, np.int32)
                npad[:n_virt] = neigh
                rpad = np.zeros(nr_pad, np.int32)
                rpad[: len(rels)] = rels
                arrays["neigh"] = jnp.asarray(npad)
                arrays["rels"] = jnp.asarray(rpad)
                screen_idx = np.concatenate(
                    [screen_idx, np.asarray(neigh, np.int64)]
                )
        info["screen_idx"] = screen_idx
        # extended triple store: train + virtual adjacency, cycle-padded —
        # immutable per pair, so upload + pad once instead of per handshake
        tr = sched.kgs[host].train
        if extra is not None and len(extra):
            tr = np.concatenate([tr, extra])
        b = min(host_tr.batch_size, len(tr))
        info["batch"] = b
        arrays["triples"] = pad_triples(jnp.asarray(tr, jnp.int32), b)
        # per-entry scalars are cached device arrays too: rebuilding them
        # from Python numbers every tick is a per-tick host→device transfer
        arrays["n_x"] = jnp.int32(n_true)
        arrays["n_y"] = jnp.int32(n_true)
        arrays["num_entities"] = jnp.int32(e_log + n_virt)  # true ext. count
        # the schedule the serial path resolves for this store/table size
        info["renorm"] = resolve_renorm(
            arrays["triples"].shape[0], bucket(e_log + n_virt, ENT_BUCKET)
        )
        self._pair[key] = info
        return info

    def _own_info(self, name: str) -> Dict:
        info = self._own.get(name)
        if info is not None:
            return info
        from repro.kge.engine import ENT_BUCKET, bucket

        sched = self.sched
        tr = sched.kgs[name].train
        model = sched.trainers[name].model
        b = min(sched.trainers[name].batch_size, len(tr))
        arrays = {
            "triples": pad_triples(jnp.asarray(tr, jnp.int32), b),
            "num_entities": jnp.int32(model.num_entities),
        }
        info = {"batch": b, "arrays": arrays}
        info["renorm"] = resolve_renorm(
            arrays["triples"].shape[0], bucket(model.num_entities, ENT_BUCKET)
        )
        self._own[name] = info
        return info

    def _misc_info(self, name: str) -> Dict:
        """Per-owner scalar leaves that are constant across ticks (the
        learning rate) — version-keyed on the value so a user mutating
        ``trainer.lr`` between runs is still honored."""
        lr = self.sched.trainers[name].lr
        info = self._misc.get(name)
        if info is None or info["version"] != (lr,):
            info = {"version": (lr,), "arrays": {"lr": jnp.float32(lr)}}
            self._misc[name] = info
        return info

    def _score_info(self, name: str) -> Dict:
        metric = self._metric_kind()
        version = self.sched._score_universe(name)
        info = self._score.get(name)
        if info is not None and info["metric"] == metric \
                and info["version"] == version:
            return info
        # (re)build — covers a score_fn swapped after a previous run AND an
        # owner whose scoring universe changed (e.g. an accepted virtual
        # extension that grew the entity table)
        sched = self.sched
        arrays: Dict[str, jnp.ndarray] = {}
        info = {"metric": metric, "version": version, "arrays": arrays}
        if metric == "accuracy":
            va, va_neg = sched._accuracy_inputs(name)
            arrays["va"] = jnp.asarray(va, jnp.int32)
            arrays["va_neg"] = jnp.asarray(va_neg, jnp.int32)
        elif metric == "hit10":
            test, filt_t, filt_h = sched._hit10_inputs(name)
            arrays["test"] = jnp.asarray(test, jnp.int32)
            arrays["filt_t"] = jnp.asarray(filt_t, jnp.int32)
            arrays["filt_h"] = jnp.asarray(filt_h, jnp.int32)
            info["ntest"] = len(test)
        self._score[name] = info
        return info

    def _metric_kind(self) -> str:
        """"accuracy"/"hit10" when the scheduler uses its default score
        functions (batchable in-graph), "none" for custom ``score_fn`` —
        those are scored host-side on the candidate params instead."""
        sched = self.sched
        fn = sched.score_fn
        if getattr(fn, "__func__", None) is type(sched)._valid_accuracy:
            return "accuracy"
        if getattr(fn, "__func__", None) is type(sched)._valid_hit10:
            return "hit10"
        return "none"

    # ---------------------------------------------------------- execution
    def _materialize(self, proto: Tuple[Dict, List], device) -> Dict:
        """One entry's full input pytree, every leaf committed to
        ``device``: resident leaves are referenced from the per-device
        caches (zero bytes moved in steady state), the per-tick mutable
        leaves (params, keys, client views) move via ONE explicit
        ``device_put`` — params are already resident after the first tick,
        so that put is a no-op for them."""
        mut, res = proto
        inp: Dict = {}
        for info, names in res:
            ondev = self._resident_on(info, device)
            for tgt, src in names.items():
                inp[tgt] = ondev[src]
        inp.update(jax.device_put(mut, device))
        return inp

    @staticmethod
    def _base_view(proto: Tuple[Dict, List]) -> Dict:
        """Device-independent view of an entry's inputs (the base cache
        copies), for signature computation before placement is decided."""
        mut, res = proto
        inp = dict(mut)
        for info, names in res:
            for tgt, src in names.items():
                inp[tgt] = info["arrays"][src]
        return inp

    def _dispatch(
        self,
        specs: List[EntrySpec],
        protos: List[Tuple[Dict, List]],
        owners: List[str],
        placement: str,
        residency: str,
    ) -> Tuple[List[Optional[Dict]], List[Optional[Tuple[int, ...]]]]:
        """Launch every entry program asynchronously; returns
        ``(outs, groups)`` in plan order: per-entry output pytrees
        (unmaterialized), and for each entry the tuple of plan indices
        sharing its shard_map group output (``None`` for singletons) so the
        blocking phase can fall back when a group failure only surfaces at
        execution time.

        ``single``: every entry runs its signature's program on the default
        device. ``sharded``: entries are bucketed by signature and ordered
        by their owner's sticky home slot (``OwnerPlacement``); buckets are
        cut into ``chunk_extents`` chunks — full-mesh or power-of-two
        extents, partial chunks padded with masked dummy replicas of the
        chunk's last real entry — and each chunk runs as ONE shard_map
        program over the owner mesh, its operands assembled zero-copy from
        the resident per-owner shards. In the paper's symmetric deployment
        (N equal owners, N devices) every owner's chunk position IS its home
        slot, so nothing but keys and client views moves between devices;
        skewed buckets keep stable positions instead (an entry executing
        off-home leaves its params committed where it ran, so a stable
        bucket composition converges to zero per-tick movement too, with the
        per-device input caches absorbing the immutables). Group programs
        compile per (signature, chunk extent) — extents restricted to
        ``{devices} ∪ {2^k}`` cap that at ~log₂(devices) per signature.

        Nothing here is caught: an entry or group program that fails to
        trace or compile is a defect of the program, not a fault of an
        owner, so it raises out of ``run`` (the serial engine does the
        same). Execution-time failures are isolated per entry by
        ``execute``'s blocking phase. Entries whose spec is ``None`` were
        already isolated by the fault layer and are skipped."""
        n = len(specs)
        outs: List[Optional[Dict]] = [None] * n
        groups: List[Optional[Tuple[int, ...]]] = [None] * n
        devices = jax.devices()

        def single(i: int, device) -> None:
            outs[i] = _entry_program(specs[i])(
                self._materialize(protos[i], device)
            )
            self.dispatched["single"] += 1

        if placement == "single":
            for i, spec in enumerate(specs):
                if spec is not None:
                    single(i, devices[0])
            return outs, groups

        from repro.core.distributed import (
            assemble_group,
            chunk_extents,
            disassemble_group,
        )

        buckets: Dict[Tuple, List[int]] = {}
        for i, (spec, proto) in enumerate(zip(specs, protos)):
            if spec is None:
                continue
            sig = entry_signature(spec, self._base_view(proto))
            buckets.setdefault(sig, []).append(i)
        for sig, idxs in buckets.items():
            spec = specs[idxs[0]]
            # stable slot order: in the symmetric case chunk position k is
            # exactly home device k; ties (more owners than devices) break
            # by name so positions don't shuffle between equal-shaped ticks
            idxs = sorted(
                idxs, key=lambda i: (self.placement.slot(owners[i]), owners[i])
            )
            pos = 0
            for real, extent in chunk_extents(len(idxs), len(devices)):
                chunk = idxs[pos : pos + real]
                pos += real
                if extent == 1:
                    i = chunk[0]
                    # owner-sticky singleton: runs on (and leaves its
                    # results committed to) the owner's home device, no
                    # matter how the rest of the plan is composed
                    single(i, self.placement.device(owners[i]))
                    continue
                members = [
                    self._materialize(protos[i], devices[k])
                    for k, i in enumerate(chunk)
                ]
                for k in range(real, extent):  # masked dummy tail
                    members.append(
                        self._materialize(protos[chunk[-1]], devices[k])
                    )
                out = _group_program(spec, extent)(
                    assemble_group(members, extent)
                )
                self.dispatched["group"] += real
                # dummy-position outputs are simply never read
                for shard, i in zip(disassemble_group(out, extent), chunk):
                    outs[i] = shard
                    groups[i] = tuple(chunk)
        if residency == "normalize":
            # legacy behavior: stage every result back to the default device
            # (None is an empty pytree node, so failed slots pass through)
            outs = jax.device_put(outs, devices[0])
        return outs, groups

    def execute(
        self,
        entries: List,
        tick: int,
        *,
        placement: Optional[str] = None,
        residency: Optional[str] = None,
        faults=None,
        adversary=None,
        deadline: Optional[float] = None,
    ) -> List:
        """Run one planned tick batched; returns the FederationEvents, in
        plan order, with protocol side effects (accept/reject, snapshot,
        broadcast, ε accounting) applied exactly as the serial path does.

        ``faults`` (a ``core.faults.FaultInjector``, default ``None`` = the
        bit-identical pre-fault path) injects this tick's planned faults at
        the same protocol points as the serial engine: crash/drop isolate an
        entry BEFORE its PPAT key split and engine-key consume (so surviving
        entries draw from the same stream positions either engine would give
        them), corrupt client views are screened at proto-build time over
        exactly the rows the serial gathers read, and straggles add their
        simulated delay to the entry's measured wall-clock, tripping
        ``deadline`` — late results are discarded through the normal
        backtrack restore and the handshake deferred. One entry failing
        while it executes never aborts the tick; a program that fails to
        trace or compile raises out of it.

        ``adversary`` (a ``core.adversary.Adversary``, default ``None`` =
        the bit-identical pre-attack path) tampers client views at the same
        fixed point as the serial engine: view → adversary tamper → fault
        corruption → receiver screens, all before any key is consumed — so
        the engines' key streams AND the adversary's replay cache stay in
        lockstep."""
        from repro.core.faults import CorruptEmbeddingError
        from repro.core.federation import FederationEvent, NodeState
        from repro.kge.eval import _metrics, best_threshold_accuracy
        from repro.kernels.dispatch import (
            resolve_interpret,
            resolve_tick_placement,
            resolve_tick_residency,
            resolve_train_impl,
        )

        sched = self.sched
        placement = resolve_tick_placement(
            placement if placement is not None else sched.tick_placement
        )
        residency = resolve_tick_residency(
            residency if residency is not None else sched.tick_residency
        )
        t0 = time.perf_counter()
        impls = {
            e.host: resolve_train_impl(None, sched.trainers[e.host].model.family)
            for e in entries
        }
        if "reference" in impls.values():
            # the host-loop dense path cannot be embedded in a tick program;
            # silently substituting the sparse step would betray the oracle
            # the user asked for — fail loudly before touching any state
            raise ValueError(
                "tick_impl='batched' cannot embed the 'reference' training "
                "step (REPRO_TRAIN_IMPL=reference); run with "
                "tick_impl='reference' instead"
            )
        n = len(entries)
        specs: List[Optional[EntrySpec]] = [None] * n
        protos: List[Optional[Tuple[Dict, List]]] = [None] * n
        owners: List[str] = [e.host for e in entries]
        entry_faults: List = [None] * n
        entry_attacks: List = [None] * n
        #: FederationEvents of entries isolated before dispatch
        pre_events: List[Optional[FederationEvent]] = [None] * n
        for i, e in enumerate(entries):
            tr = sched.trainers[e.host]
            fault = (
                faults.draw(tick, e.host, e.client)
                if faults is not None else None
            )
            entry_faults[i] = fault
            atk = (
                adversary.draw(tick, e.host, e.client)
                if adversary is not None and e.kind == "ppat" else None
            )
            entry_attacks[i] = atk
            pair = cview = None
            if atk is not None:
                # adversary tamper happens BEFORE crash/drop isolation (the
                # serial loop tampers every planned view): a replay attack's
                # stale-view cache must advance identically in both engines
                # even when the entry then dies to an injected fault
                pair = self._pair_info(e.client, e.host)
                cview = e.client_view or dict(sched.trainers[e.client].params)
                cview = adversary.tamper_view(
                    cview, atk, tick, e.host, e.client,
                    rows=pair["screen_idx"],
                )
            if fault is not None and fault.kind in ("crash", "drop"):
                # host dies / offer message lost before any work — isolated
                # BEFORE the PPAT key split and the engine-key consume, so
                # surviving entries draw from the same stream positions the
                # serial path would give them
                sched._entry_failed(
                    e.host, e.client if e.kind == "ppat" else None, fault.kind
                )
                pre_events[i] = sched.events[-1]
                continue
            metric = self._metric_kind()
            score_info = self._score_info(e.host)
            if e.kind == "ppat":
                if pair is None:
                    pair = self._pair_info(e.client, e.host)
                    cview = (
                        e.client_view
                        or dict(sched.trainers[e.client].params)
                    )
                if fault is not None and fault.kind == "corrupt":
                    cview = faults.corrupt_view(cview, fault, tick, e.host)
                if faults is not None:
                    # receiver-side integrity screen over exactly the rows
                    # the serial path's gathers read (aligned + virtual
                    # neighbors), before any key is consumed — the engines
                    # stay in lockstep on every stream
                    try:
                        sched.screen_incoming(
                            e.host, e.client, cview, bound=faults.norm_bound
                        )
                    except CorruptEmbeddingError:
                        sched._entry_failed(e.host, e.client, "corrupt")
                        pre_events[i] = sched.events[-1]
                        continue
            if sched.state[e.host] is not NodeState.QUARANTINED:
                # a mid-tick quarantine (this owner blamed as the client of
                # an earlier entry) survives its already-planned execution
                sched.state[e.host] = NodeState.BUSY
            # per-tick mutable leaves (explicit device_put at placement
            # time); everything else is referenced from the per-device
            # resident caches via (info, {input name: cache key}) entries
            mut: Dict = {
                "params": dict(tr.params),
                "key_train": tr.consume_engine_key(),
            }
            res: List[Tuple[Dict, Dict[str, str]]] = [
                (self._misc_info(e.host), {"lr": "lr"}),
            ]
            kw = dict(
                kind=e.kind,
                model=tr.model,
                epochs=sched.update_epochs,
                train_impl=impls[e.host],
                interpret=resolve_interpret(None),
                cfg=None,
                aggregation=sched.aggregation,
                refine=sched.procrustes_refine,
                score=metric,
                lp_batch=128,
                block_e=512,
            )
            if e.kind == "ppat":
                # streamed passes pre-split keys in plan order at pass
                # start (TickEntry.key_ppat) so per-level execution keeps
                # the barrier key-stream order; barrier ticks split here
                sub = getattr(e, "key_ppat", None)
                if sub is None:
                    sched._key, sub = jax.random.split(sched._key)
                # the client view is the paper's client → host communication
                # — per-tick state, shipped to the host's device explicitly
                mut.update(client_ent=cview["ent"], key_ppat=sub)
                names = {
                    k: k
                    for k in ("idx_c", "idx_h", "n_x", "n_y", "triples",
                              "num_entities")
                }
                if "rel_c" in pair["arrays"]:
                    names.update(rel_c="rel_c", rel_h="rel_h")
                    mut["client_rel"] = cview["rel"]
                if "neigh" in pair["arrays"]:
                    names.update(neigh="neigh", rels="rels")
                    mut["client_rel_full"] = cview["rel"]
                res.append((pair, names))
                kw.update(
                    cfg=sched.ppat_cfg, batch=pair["batch"],
                    renorm=pair["renorm"], robust=sched.robust_agg,
                    cos=sched.cos_screen is not None,
                )
            else:
                own = self._own_info(e.host)
                res.append(
                    (own, {"triples": "triples", "num_entities": "num_entities"})
                )
                kw.update(batch=own["batch"], renorm=own["renorm"])
            if metric == "accuracy":
                res.append((score_info, {"va": "va", "va_neg": "va_neg"}))
            elif metric == "hit10":
                res.append((
                    score_info,
                    {"test": "test", "filt_t": "filt_t", "filt_h": "filt_h"},
                ))
            specs[i] = EntrySpec(**kw)
            protos[i] = (mut, res)

        outs, groups = self._dispatch(
            specs, protos, owners, placement, residency
        )
        # block per entry so one failing program poisons one entry, not the
        # tick; a shard_map group whose failure only surfaces at execution
        # time is re-dispatched per-entry on the members' home devices (the
        # group's healthy owners still land their results)
        errs: List[Optional[Exception]] = [None] * n
        retried: set = set()
        for i in range(n):
            if outs[i] is None or errs[i] is not None:
                continue
            try:
                outs[i] = jax.block_until_ready(outs[i])
            except Exception as ex:  # noqa: BLE001 — isolate, don't abort
                g = groups[i]
                if g is None or g in retried:
                    errs[i] = ex
                    continue
                retried.add(g)
                for j in g:
                    groups[j] = None
                    try:
                        outs[j] = _entry_program(specs[j])(
                            self._materialize(
                                protos[j], self.placement.device(owners[j])
                            )
                        )
                        self.dispatched["single"] += 1
                    except Exception as e2:  # noqa: BLE001
                        outs[j], errs[j] = None, e2
                if errs[i] is None:
                    try:
                        outs[i] = jax.block_until_ready(outs[i])
                    except Exception as e3:  # noqa: BLE001
                        errs[i] = e3
        # honest AND monotonic: outputs are materialized, and perf_counter
        # is immune to wall-clock adjustments (time.time() is not)
        seconds = time.perf_counter() - t0

        events = []
        for i, e in enumerate(entries):
            if pre_events[i] is not None:
                events.append(pre_events[i])
                continue
            spec, out = specs[i], outs[i]
            if out is None or errs[i] is not None:
                # an uninjected execution-time exception the blocking phase
                # isolated: same failure path as a crash, attributed to the
                # host, kind "error"
                sched._entry_failed(
                    e.host, e.client if e.kind == "ppat" else None, "error"
                )
                events.append(sched.events[-1])
                continue
            tr = sched.trainers[e.host]
            fault = entry_faults[i]
            epsilon = float("nan")
            if e.kind == "ppat":
                acct = MomentsAccountant(sched.ppat_cfg.lam, sched.ppat_cfg.delta)
                acct.update(
                    np.asarray(out["n0s"]).ravel(), np.asarray(out["n1s"]).ravel()
                )
                epsilon = acct.epsilon()
                sched.epsilons.append(epsilon)
                sched.accountant.merge(acct)  # federation-lifetime ε
            before = sched.best_score[e.host]
            if spec.score == "accuracy":
                sp, sn = (np.asarray(v) for v in out["score"])
                _, after = best_threshold_accuracy(sp, sn, max_candidates=256)
            elif spec.score == "hit10":
                ntest = self._score_info(e.host)["ntest"]
                ranks = np.empty(2 * ntest, dtype=np.int64)
                for ci, (ct, ch) in zip(
                    range(0, ntest, spec.lp_batch), out["score"]
                ):
                    nc = len(np.asarray(ct))
                    ranks[2 * ci : 2 * (ci + nc) : 2] = np.asarray(ct) + 1
                    ranks[2 * ci + 1 : 2 * (ci + nc) : 2] = np.asarray(ch) + 1
                after = _metrics(ranks)["hit@10"]
            else:  # custom score_fn: score host-side on the candidate params
                tr.params = dict(out["params"])
                after = sched.score_fn(e.host)
            # straggler deadline: the entry's result arrived, but too late
            # to merge — injected straggles contribute their simulated delay
            elapsed = seconds
            if fault is not None and fault.kind == "straggle":
                elapsed += fault.delay
            straggled = deadline is not None and elapsed > deadline
            # cosine-shift accept gate (see federate_once): same statistic,
            # same reputation-sharpened threshold, same decision
            mean_cos = None
            if e.kind == "ppat" and spec.cos:
                mean_cos = float(out["mean_cos"])
            poisoned = (
                mean_cos is not None and not straggled
                and mean_cos < sched._cos_tau(e.client)
            )
            accepted = after > before and not straggled and not poisoned
            if accepted:
                tr.params = dict(out["params"])
                sched.best_score[e.host] = after
                sched.best_snapshot[e.host] = tr.snapshot()
            else:
                tr.restore(sched.best_snapshot[e.host])
            if sched.state[e.host] is NodeState.BUSY:
                # conditional: a mid-tick quarantine (this host blamed as
                # the client of another entry) survives its own completion
                sched.state[e.host] = NodeState.READY
            atk = entry_attacks[i]
            fault_kind = (
                "straggle" if straggled else ("poison" if poisoned else None)
            )
            ev = FederationEvent(
                tick, e.host, e.client,
                "ppat" if e.kind == "ppat" else "self-train",
                before, after, accepted, epsilon=epsilon, seconds=elapsed,
                fault=fault_kind,
                attack=atk.kind if atk is not None else None,
            )
            sched.events.append(ev)
            events.append(ev)
            if accepted:
                sched.broadcast(e.host)
                if e.kind == "ppat":
                    sched._rep_recover(e.host, e.client)
                sched._notify_accept(e.host)
            if straggled:
                sched._entry_failed(e.host, e.client, "straggle", emit=False)
            elif poisoned:
                sched._entry_failed(e.host, e.client, "poison", emit=False)
            else:
                sched._note_entry_ok(e.host, e.client)
        return events
