"""Backend auto-dispatch for the Pallas kernel families.

Every kernel wrapper takes ``interpret=None`` by default and resolves it here:

  1. an explicit ``True``/``False`` from the caller always wins;
  2. else the ``REPRO_PALLAS_INTERPRET`` env var (``1/true/on`` or ``0/false/off``)
     overrides the backend heuristic — useful to force-compile on CPU or debug
     on TPU without touching call sites;
  3. else resolve from ``jax.default_backend()``: compiled Pallas on TPU/GPU,
     interpreter on CPU (the CI container), so the same call sites run fast on
     accelerators and still pass on CPU CI.

The streaming rank engine additionally picks an *implementation*: the Pallas
fused-rank kernel on TPU (its accumulation grid relies on sequential grid
execution), or a jnp ``lax.scan`` streaming equivalent everywhere else — on
GPU the Triton grid runs in parallel (the revisited output block would race),
and on CPU interpret-mode Pallas re-traces the kernel body per grid step,
far slower than one compiled XLA loop. ``REPRO_RANK_IMPL`` overrides
(``pallas`` | ``xla``).

The training engine picks its step implementation the same way:
``REPRO_TRAIN_IMPL`` (``pallas`` | ``xla`` | ``reference``) overrides the
backend heuristic in ``resolve_train_impl``.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

_TRUTHY = ("1", "true", "yes", "on")
_FALSY = ("0", "false", "no", "off")

#: backends with a real Mosaic/Triton Pallas lowering
COMPILED_BACKENDS = ("tpu", "gpu", "cuda", "rocm")


def _env_flag(name: str) -> Optional[bool]:
    raw = os.environ.get(name, "").strip().lower()
    if raw in _TRUTHY:
        return True
    if raw in _FALSY:
        return False
    return None


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve the ``interpret=`` flag for a ``pl.pallas_call``."""
    if interpret is not None:
        return bool(interpret)
    env = _env_flag("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env
    return jax.default_backend() not in COMPILED_BACKENDS


#: families whose margin-SGD step the fused sparse_update kernel covers
SPARSE_KERNEL_FAMILIES = ("transe", "distmult")


def resolve_train_impl(impl: Optional[str] = None, family: str = "transe") -> str:
    """Pick the training-engine step implementation.

    ``xla`` — the autodiff sparse step (every family, every backend) — is
    the default everywhere. ``pallas`` — the fused gather→score→scatter
    sparse_update kernel (TransE/DistMult only) — runs only when asked for,
    in interpret mode: Mosaic cannot lower its in-kernel row scatters
    (``acc.at[i, :].set``) or vector gathers at any table size, so the TPU
    compiler refuses it (pinned by ``tests/test_tpu_compile.py``) until the
    kernel is rewritten with tables in ``pl.ANY``, row DMA and ids in SMEM.
    ``reference`` — the seed dense host-loop path, kept as the parity
    oracle. ``REPRO_TRAIN_IMPL`` overrides."""
    if impl is None:
        impl = os.environ.get("REPRO_TRAIN_IMPL", "").strip().lower() or None
    if impl is None:
        impl = "xla"
    if impl not in ("pallas", "xla", "reference"):
        raise ValueError(f"unknown train impl {impl!r} (pallas|xla|reference)")
    if impl == "pallas" and family not in SPARSE_KERNEL_FAMILIES:
        impl = "xla"  # kernel does not cover this family's score math
    return impl


def resolve_tick_impl(impl: Optional[str] = None, family: str = "transe") -> str:
    """Pick the federation tick execution engine: ``batched`` or ``reference``.

    ``batched`` — the tick engine plans every Ready owner's pending work at
    tick start and executes the whole tick (PPAT, aggregation, retrain,
    backtrack scoring) as ONE compiled program of independent per-owner
    subgraphs; ``reference`` — the serial per-owner loop (the seed protocol
    driver), kept as the parity oracle. ``REPRO_TICK_IMPL`` overrides.

    The batched engine embeds the device-resident training scan per owner,
    so when the training step resolves to the host-loop ``reference`` impl
    (``REPRO_TRAIN_IMPL=reference``) ticks fall back to ``reference`` too.
    """
    if impl is None:
        impl = os.environ.get("REPRO_TICK_IMPL", "").strip().lower() or None
    if impl is None:
        impl = (
            "reference"
            if resolve_train_impl(None, family) == "reference"
            else "batched"
        )
    if impl not in ("batched", "reference"):
        raise ValueError(f"unknown tick impl {impl!r} (batched|reference)")
    return impl


def resolve_tick_placement(placement: Optional[str] = None) -> str:
    """Pick where the batched tick engine places its entry programs:
    ``single`` (every entry on the default device) or ``sharded`` (signature
    buckets shard_map'ed across ``jax.devices()``, singletons placed by a
    stable signature hash).

    ``auto`` (the default) resolves to ``sharded`` exactly when more than one
    device is visible — on CPU CI that means
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` turns sharding on
    without touching call sites. ``REPRO_TICK_PLACEMENT`` overrides.
    """
    if placement is None:
        placement = (
            os.environ.get("REPRO_TICK_PLACEMENT", "").strip().lower() or None
        )
    if placement is None:
        placement = "auto"
    if placement == "auto":
        placement = "sharded" if len(jax.devices()) > 1 else "single"
    if placement not in ("single", "sharded"):
        raise ValueError(
            f"unknown tick placement {placement!r} (auto|single|sharded)"
        )
    return placement


def resolve_tick_sync(sync: Optional[str] = None) -> str:
    """Pick the federation scheduling discipline: ``barrier`` or ``stream``.

    ``barrier`` (the default) — the lockstep scheduler: one plan per tick,
    every owner blocks on the slowest entry, accepts take effect next tick.
    Kept as the parity oracle for the streamed path. ``stream`` — the
    dependency-level streaming scheduler: each pass's plan is cut into
    dependency levels (entries whose host/client sets overlap serialize,
    disjoint entries stream), levels dispatch as they clear, client views
    are versioned, and a bounded-staleness gate (``staleness_bound=``)
    triggers re-offer handshakes instead of blind accepts on too-stale
    views. ``streamed`` is accepted as an alias. ``REPRO_TICK_SYNC``
    overrides.
    """
    if sync is None:
        sync = os.environ.get("REPRO_TICK_SYNC", "").strip().lower() or None
    if sync is None or sync == "auto":
        sync = "barrier"
    if sync == "streamed":
        sync = "stream"
    if sync not in ("barrier", "stream"):
        raise ValueError(
            f"unknown tick sync {sync!r} (auto|barrier|stream)"
        )
    return sync


def resolve_tick_residency(residency: Optional[str] = None) -> str:
    """Pick what happens to tick-entry outputs after a batched tick:
    ``resident`` (the default) leaves every owner's results committed to the
    device that produced them — an owner's embedding tables stay on its
    sticky home device across ticks, and only the scalar decisions/scores
    sync to host; ``normalize`` restores the pre-residency behavior of
    ``jax.device_put``-ing all results back to the default device each tick
    (an escape hatch for consumers that cannot handle committed arrays).
    ``REPRO_TICK_RESIDENCY`` overrides.
    """
    if residency is None:
        residency = (
            os.environ.get("REPRO_TICK_RESIDENCY", "").strip().lower() or None
        )
    if residency is None or residency == "auto":
        residency = "resident"
    if residency not in ("resident", "normalize"):
        raise ValueError(
            f"unknown tick residency {residency!r} (auto|resident|normalize)"
        )
    return residency


def resolve_tick_faults(spec=None):
    """Resolve the federation fault-injection layer: returns ``None`` (off —
    the default, keeping the tick fast path bit-identical to the pre-fault
    engine) or a fault-plan description the scheduler hands to
    ``core.faults.FaultPlan.parse``.

    ``spec`` may be a spec string, an already-built ``FaultPlan`` /
    ``FaultInjector`` (handed through verbatim — the test harness path), or
    ``None`` to consult ``REPRO_TICK_FAULTS``. Off-values (``off``/``0``/
    ``false``/``none``/empty) resolve to ``None``.
    """
    if spec is not None and not isinstance(spec, str):
        return spec  # FaultPlan / FaultInjector passed programmatically
    if spec is None:
        spec = os.environ.get("REPRO_TICK_FAULTS", "").strip() or None
    if spec is None:
        return None
    if spec.strip().lower() in _FALSY + ("", "none"):
        return None
    return spec


def resolve_tick_adversary(spec=None):
    """Resolve the federation adversarial-peer layer: returns ``None`` (off
    — the default, keeping the tick fast path bit-identical to the
    pre-attack engine) or an adversary description the scheduler hands to
    ``core.adversary.AdversaryPlan.parse``.

    ``spec`` may be a spec string, an already-built ``AdversaryPlan`` /
    ``Adversary`` (handed through verbatim — the test harness path), or
    ``None`` to consult ``REPRO_TICK_ADVERSARY``. Off-values (``off``/
    ``0``/``false``/``none``/empty) resolve to ``None``.
    """
    if spec is not None and not isinstance(spec, str):
        return spec  # AdversaryPlan / Adversary passed programmatically
    if spec is None:
        spec = os.environ.get("REPRO_TICK_ADVERSARY", "").strip() or None
    if spec is None:
        return None
    if spec.strip().lower() in _FALSY + ("", "none"):
        return None
    return spec


def resolve_serve_faults(spec=None):
    """Resolve the serving-tier fault-injection layer: returns ``None`` (off
    — the default, keeping the query fast path bit-identical to the
    pre-fault tier) or a fault-plan description the tier hands to
    ``core.faults.ServeFaultPlan.parse``.

    ``spec`` may be a spec string, an already-built ``ServeFaultPlan``
    (handed through verbatim — the test harness path), or ``None`` to
    consult ``REPRO_SERVE_FAULTS``. Off-values (``off``/``0``/``false``/
    ``none``/empty) resolve to ``None``.
    """
    if spec is not None and not isinstance(spec, str):
        return spec  # ServeFaultPlan passed programmatically
    if spec is None:
        spec = os.environ.get("REPRO_SERVE_FAULTS", "").strip() or None
    if spec is None:
        return None
    if spec.strip().lower() in _FALSY + ("", "none"):
        return None
    return spec


def resolve_serve_impl(impl: Optional[str] = None) -> str:
    """Pick the serving-tier dispatch mode: ``batched`` or ``direct``.

    ``batched`` — the default: the tier coalesces queued requests into
    bucket-padded query batches (continuous batching) so steady-state
    traffic hits a fixed set of compiled programs; ``direct`` — one
    dispatch per request, the per-call baseline the serving bench measures
    batching against. ``REPRO_SERVE_IMPL`` overrides."""
    if impl is None:
        impl = os.environ.get("REPRO_SERVE_IMPL", "").strip().lower() or None
    if impl is None:
        impl = "batched"
    if impl not in ("batched", "direct"):
        raise ValueError(f"unknown serve impl {impl!r} (batched|direct)")
    return impl


def resolve_serve_replicas(n: Optional[int] = None) -> int:
    """Pick how many table replicas the serving tier spreads over the mesh.

    Explicit ``n`` wins, else ``REPRO_SERVE_REPLICAS``, else every visible
    device capped at 4 — a single-device CI run degenerates to one replica
    while ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (or a real
    multi-chip mesh) turns replica routing on without touching call sites.
    The tier clamps to the actual device count, so over-asking is safe."""
    if n is None:
        raw = os.environ.get("REPRO_SERVE_REPLICAS", "").strip()
        n = int(raw) if raw else None
    if n is None:
        n = min(4, len(jax.devices()))
    n = int(n)
    if n < 1:
        raise ValueError(f"serve replicas must be >= 1, got {n}")
    return n


#: score modes the fused-rank kernel lowers for on TPU (``cl1``, RotatE, on
#: the lane-aligned [re | im] planes its wrapper lays out)
RANK_KERNEL_MODES = ("l1", "l2", "dot", "cl1")
#: score modes the pairwise (B, E) score kernel lowers for: its ``cl1`` tile
#: slices each row into half-width [re | im] lane slices, which Mosaic
#: refuses ("Shape mismatch in input, indices and output")
PAIRWISE_KERNEL_MODES = ("l1", "l2", "dot")


def resolve_rank_impl(impl: Optional[str] = None, mode: str = "l1") -> str:
    """Pick the fused-rank engine implementation: ``pallas`` or ``xla``.

    The fused-rank kernel revisits its output block across the entity grid
    axis (``index_map`` ignores j), which is only sound where grid steps run
    sequentially — TPU. On GPU the Triton grid is parallel, so auto picks the
    ``xla`` scan there too; ``REPRO_RANK_IMPL=pallas`` can force it for
    experimentation. Modes the kernel does not lower for
    (``RANK_KERNEL_MODES``) always take the ``xla`` scan, the way
    ``resolve_train_impl`` keeps families the training kernel does not cover
    off it."""
    if impl is None:
        impl = os.environ.get("REPRO_RANK_IMPL", "").strip().lower() or None
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown rank-engine impl {impl!r} (pallas|xla)")
    if impl == "pallas" and mode not in RANK_KERNEL_MODES:
        impl = "xla"
    return impl
