"""Batched federation tick engine: batched-vs-reference bit parity, plan
semantics, program-cache reuse, and the sparse entity-norm projection."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.federation import FederationScheduler, NodeState, TickEntry
from repro.core.ppat import PPATConfig
from repro.core.tick_engine import tick_program_cache_size
from repro.kernels.dispatch import (
    resolve_tick_impl,
    resolve_tick_placement,
    resolve_tick_residency,
)
from repro.kge.data import equal_shape_universe, synthesize_universe
from repro.kge.engine import (
    _train_scan,
    pad_tables,
    pad_triples,
    resolve_renorm,
    shape_spec,
)
from repro.kge.models import KGEModel, init_kge


@pytest.fixture(scope="module")
def universe():
    stats = [("A", 12, 90000, 300000), ("B", 10, 70000, 240000),
             ("C", 8, 60000, 200000)]
    aligns = [("A", "B", 30000), ("B", "C", 20000), ("A", "C", 18000)]
    return synthesize_universe(seed=1, scale=1 / 500, kg_stats=stats,
                               alignments=aligns)


def _make(universe, **kw):
    defaults = dict(
        dim=16, ppat_cfg=PPATConfig(steps=6, seed=0),
        local_epochs=4, update_epochs=2, seed=0, score_max_test=40,
    )
    defaults.update(kw)
    return FederationScheduler(universe, **defaults)


def _run_pair(universe, ticks=3, **kw):
    feds = {}
    for impl in ("reference", "batched"):
        fed = _make(universe, **kw)
        fed.initial_training()
        fed.run(max_ticks=ticks, tick_impl=impl)
        feds[impl] = fed
    return feds["reference"], feds["batched"]


def _assert_parity(ref, bat, universe):
    """The tick-engine contract: identical protocol trajectory, identical
    scores/ε (exact floats), bit-identical final embeddings."""
    er = [(e.tick, e.host, e.client, e.kind, e.accepted) for e in ref.events]
    eb = [(e.tick, e.host, e.client, e.kind, e.accepted) for e in bat.events]
    assert er == eb
    for r, b in zip(ref.events, bat.events):
        assert r.score_before == b.score_before, (r, b)
        assert r.score_after == b.score_after, (r, b)
        assert (math.isnan(r.epsilon) and math.isnan(b.epsilon)) or (
            r.epsilon == b.epsilon
        )
    assert ref.best_score == bat.best_score
    assert ref.epsilons == bat.epsilons
    for n in universe:
        for k in ref.trainers[n].params:
            np.testing.assert_array_equal(
                np.asarray(ref.trainers[n].params[k]),
                np.asarray(bat.trainers[n].params[k]),
                err_msg=f"{n}.{k} diverged between tick impls",
            )
    assert ref.state == bat.state
    assert all(
        list(ref.queue[n]) == list(bat.queue[n]) for n in universe
    )


@pytest.mark.parametrize("metric", ["accuracy", "hit10"])
def test_tick_parity(universe, metric):
    """Batched ticks reproduce serial ticks exactly: accept/reject decisions,
    scores, ε history, and bit-identical embeddings (same per-pair keys)."""
    ref, bat = _run_pair(universe, score_metric=metric)
    _assert_parity(ref, bat, universe)


def test_tick_parity_without_virtual_and_refine(universe):
    ref, bat = _run_pair(
        universe, ticks=2, use_virtual=False, procrustes_refine=False
    )
    _assert_parity(ref, bat, universe)


def test_tick_parity_custom_score_fn(universe):
    """A user-supplied score_fn cannot be traced — the batched engine must
    fall back to scoring the candidate params host-side, same trajectory."""
    def run(impl):
        fed = _make(universe)
        fed.score_fn = lambda name: fed._valid_accuracy(name)  # opaque fn
        fed.initial_training()
        fed.run(max_ticks=2, tick_impl=impl)
        return fed

    ref, bat = run("reference"), run("batched")
    _assert_parity(ref, bat, universe)


def test_tick_program_reused_across_ticks(universe):
    """Steady-state federation reuses the compiled tick-entry programs:
    ticks whose entry signatures (spec + bucket-padded shapes) were seen
    before must not recompile."""
    fed = _make(universe)
    fed.initial_training()
    # warm-up: each owner has 2 partners, so 2 ticks rotate through every
    # (client, host) pair signature; a drained-queue tick compiles the
    # self-train signatures
    fed.run(max_ticks=2, tick_impl="batched")
    for name in universe:
        fed.queue[name].clear()
        fed._queued[name].clear()
    fed.run(max_ticks=1, tick_impl="batched")
    n = tick_program_cache_size()
    fed.run(max_ticks=2, tick_impl="batched")
    assert tick_program_cache_size() == n, (
        "batched tick recompiled despite unchanged entry signatures"
    )


def test_plan_tick_snapshot_semantics(universe):
    """The plan is fixed at tick start: offers are popped, client views are
    frozen, and idle owners sleep (when self-training is off)."""
    fed = _make(universe)
    fed.initial_training()
    plan = fed.plan_tick()
    assert all(isinstance(e, TickEntry) for e in plan)
    assert {e.host for e in plan} == set(universe)  # everyone was Ready
    assert all(e.kind == "ppat" and e.client_view is not None for e in plan)
    # popped offers are gone from the queues
    for e in plan:
        assert e.client not in fed._queued[e.host]
    # empty-queue owners go to Sleep when self-training is disabled
    fed2 = _make(universe)
    fed2.initial_training()
    for n in universe:
        fed2.queue[n].clear()
        fed2._queued[n].clear()
    assert fed2.plan_tick(self_train=False) == []
    assert all(s is NodeState.SLEEP for s in fed2.state.values())


def test_score_fn_swap_rebuilds_score_cache(universe):
    """Swapping the backtrack metric between runs must rebuild the cached
    scoring inputs, not serve the previous metric's arrays."""
    fed = _make(universe)
    fed.initial_training()
    fed.run(max_ticks=1, tick_impl="batched")   # caches accuracy inputs
    fed.score_fn = fed._valid_hit10
    fed.best_score = {n: fed._valid_hit10(n) for n in universe}
    fed.run(max_ticks=1, tick_impl="batched")   # must rebuild as hit10
    hit10_events = [e for e in fed.events if e.tick == fed._tick]
    assert hit10_events
    assert all(0.0 <= e.score_after <= 1.0 for e in hit10_events)


def test_batched_tick_rejects_reference_train_impl(universe, monkeypatch):
    """The host-loop 'reference' training step cannot be embedded in a tick
    program — an explicit batched run must fail loudly, with no scheduler
    state consumed."""
    fed = _make(universe)
    fed.initial_training()
    monkeypatch.setenv("REPRO_TRAIN_IMPL", "reference")
    keys_before = {n: np.asarray(fed.trainers[n]._key) for n in universe}
    queues_before = {n: list(fed.queue[n]) for n in universe}
    with pytest.raises(ValueError, match="tick_impl='reference'"):
        fed.run(max_ticks=1, tick_impl="batched")
    for n in universe:
        np.testing.assert_array_equal(
            np.asarray(fed.trainers[n]._key), keys_before[n]
        )
        assert fed.state[n] is not NodeState.BUSY
        # the error fires before the plan pops any offers
        assert list(fed.queue[n]) == queues_before[n]


def test_entry_trace_failure_propagates(universe, monkeypatch):
    """An entry program that cannot be traced or compiled is a defect of
    the program, not an owner's fault: it raises out of ``run`` instead of
    being isolated as a ``fault="error"`` event, and the planned offers go
    back to their queues."""
    import repro.core.tick_engine as tick_engine

    class TraceError(Exception):
        pass

    def broken_graph(inp, spec):
        raise TraceError("entry graph failed to trace")

    fed = _make(universe)
    fed.initial_training()
    queues_before = {n: list(fed.queue[n]) for n in universe}
    monkeypatch.setattr(tick_engine, "_ENTRY_PROGRAMS", {})
    monkeypatch.setattr(tick_engine, "entry_graph", broken_graph)
    with pytest.raises(TraceError):
        fed.run(max_ticks=1, tick_impl="batched", tick_placement="single")
    assert not [e for e in fed.events if e.fault is not None]
    assert all(e.kind == "init" for e in fed.events)
    for n in universe:
        assert fed.state[n] is NodeState.READY
        assert list(fed.queue[n]) == queues_before[n]


def test_equal_shaped_owners_share_one_entry_program():
    """Trace-time program dedup: N equal-shaped owners must compile exactly
    ONE tick-entry program per tick kind (per unique entry signature), not
    one per owner — the multi-device version of this claim (8 owners, 8
    simulated devices, shard_map buckets) is pinned by
    ``tests/test_tick_sharded.py``."""
    kgs = equal_shape_universe(
        4, entities=120, relations=6, triples=800, shared=32, seed=3
    )
    fed = FederationScheduler(
        kgs, dim=16, ppat_cfg=PPATConfig(steps=4, seed=0), local_epochs=2,
        update_epochs=2, seed=0, use_virtual=False, score_max_test=24,
    )
    fed.initial_training()
    before = tick_program_cache_size()
    # tick 1: every owner hosts one handshake — 4 equal-shaped ppat entries.
    # Placement is pinned to "single" so the exact program counts hold under
    # any forced host-device count (sharded would chunk the bucket by device
    # count); the sharded dedup claim is pinned by tests/test_tick_sharded.py.
    fed.run(max_ticks=1, tick_impl="batched", tick_placement="single")
    assert tick_program_cache_size() == before + 1
    # an all-self-train tick adds exactly one more program (new entry kind)
    for n in kgs:
        fed.queue[n].clear()
        fed._queued[n].clear()
    fed.run(max_ticks=1, tick_impl="batched", tick_placement="single")
    assert tick_program_cache_size() == before + 2


def test_score_inputs_invalidated_by_accepted_extension(universe):
    """Regression: the per-owner backtrack-score caches must be rebuilt when
    an accepted virtual extension grows the owner's embedding universe —
    fixed negatives / CSR filters built pre-accept must not be served against
    the post-accept tables."""
    import jax.numpy as jnp

    fed = _make(universe)
    fed.initial_training()
    name = next(iter(universe))
    tr = fed.trainers[name]
    e0 = tr.model.num_entities
    va0, neg0 = fed._accuracy_inputs(name)
    lp0 = fed._hit10_inputs(name)
    info0 = fed._tick_engine._score_info(name)

    # accept a virtual extension into the owner's live tables: the entity /
    # relation universe grows and stays grown across the next scoring call
    dim = tr.model.dim
    extra = np.array([[e0, tr.model.num_relations, 0]], np.int64)
    tr.extend_tables(
        0.01 * jnp.ones((3, dim)), 0.01 * jnp.ones((1, dim)), extra
    )
    assert tr.model.num_entities == e0 + 3

    va1, neg1 = fed._accuracy_inputs(name)
    np.testing.assert_array_equal(va0, va1)  # positives: unchanged split
    # negatives are REdrawn against the extended universe
    assert not np.array_equal(neg0, neg1)
    assert neg1[:, [0, 2]].max() < e0 + 3
    # hit@10 CSR filters are universe-extent independent (appended virtual
    # ids invalidate nothing) — the expensive rebuild must NOT fire
    assert fed._hit10_inputs(name) is lp0
    assert fed._tick_engine._score_info(name) is not info0
    # both metrics score the extended universe without stale-shape failures
    assert 0.0 <= fed._valid_accuracy(name) <= 1.0
    assert 0.0 <= fed._valid_hit10(name) <= 1.0

    # stripping the extension reverts the version: the rebuilt negatives are
    # bit-identical to the originals (fixed sampling seed)
    tr.strip_virtual()
    va2, neg2 = fed._accuracy_inputs(name)
    np.testing.assert_array_equal(neg0, neg2)
    np.testing.assert_array_equal(va0, va2)


def test_resolve_tick_placement(monkeypatch):
    """Placement resolution: explicit wins, then REPRO_TICK_PLACEMENT, then
    auto by visible device count (sharded iff >1 device, so the suite also
    passes under a forced multi-device XLA_FLAGS)."""
    auto = "sharded" if len(jax.devices()) > 1 else "single"
    assert resolve_tick_placement("single") == "single"
    assert resolve_tick_placement("sharded") == "sharded"
    assert resolve_tick_placement("auto") == auto
    assert resolve_tick_placement(None) == auto
    monkeypatch.setenv("REPRO_TICK_PLACEMENT", "sharded")
    assert resolve_tick_placement(None) == "sharded"
    assert resolve_tick_placement("single") == "single"  # explicit beats env
    monkeypatch.setenv("REPRO_TICK_PLACEMENT", "auto")
    assert resolve_tick_placement(None) == auto
    monkeypatch.delenv("REPRO_TICK_PLACEMENT")
    with pytest.raises(ValueError):
        resolve_tick_placement("nope")


def test_resolve_tick_residency(monkeypatch):
    """Residency resolution: explicit wins, then REPRO_TICK_RESIDENCY, then
    auto → resident (owner-sticky is the default everywhere; normalize is
    the legacy stage-back-to-device-0 escape hatch)."""
    assert resolve_tick_residency(None) == "resident"
    assert resolve_tick_residency("auto") == "resident"
    assert resolve_tick_residency("resident") == "resident"
    assert resolve_tick_residency("normalize") == "normalize"
    monkeypatch.setenv("REPRO_TICK_RESIDENCY", "normalize")
    assert resolve_tick_residency(None) == "normalize"
    assert resolve_tick_residency("resident") == "resident"  # explicit wins
    monkeypatch.delenv("REPRO_TICK_RESIDENCY")
    with pytest.raises(ValueError):
        resolve_tick_residency("nope")


def test_single_device_residency_keeps_state_usable(universe):
    """On one device residency is trivially satisfied; the engine's resident
    caches must still serve steady-state ticks without re-staging cached
    immutable inputs (the miss counter stays flat once every pair/score
    cache is warm)."""
    fed = _make(universe)
    fed.initial_training()
    fed.run(max_ticks=2, tick_impl="batched")  # warm every (client, host)
    for name in universe:  # warm the self-train caches too
        fed.queue[name].clear()
        fed._queued[name].clear()
    fed.run(max_ticks=1, tick_impl="batched")
    eng = fed._tick_engine
    misses = eng.resident_transfers
    fed.run(max_ticks=2, tick_impl="batched")
    assert eng.resident_transfers == misses, (
        "steady-state single-device ticks re-staged cached inputs"
    )


def test_resolve_tick_impl(monkeypatch):
    assert resolve_tick_impl("reference") == "reference"
    assert resolve_tick_impl("batched") == "batched"
    assert resolve_tick_impl(None) == "batched"
    monkeypatch.setenv("REPRO_TICK_IMPL", "reference")
    assert resolve_tick_impl(None) == "reference"
    monkeypatch.delenv("REPRO_TICK_IMPL")
    # host-loop training cannot be embedded in a tick program → fall back
    monkeypatch.setenv("REPRO_TRAIN_IMPL", "reference")
    assert resolve_tick_impl(None) == "reference"
    monkeypatch.delenv("REPRO_TRAIN_IMPL")
    with pytest.raises(ValueError):
        resolve_tick_impl("nope")


# ---------------------------------------------------------------------------
# sparse entity-norm projection (kge.engine renorm="sparse")
# ---------------------------------------------------------------------------
def _scan_kwargs(e, renorm, epochs=3):
    m = KGEModel("transe", e, 5, 16)
    return m, dict(
        spec=shape_spec(m), epochs=epochs, batch=50, impl="xla",
        interpret=True, renorm=renorm,
    )


def test_sparse_renorm_bit_parity_all_touched():
    """When every entity appears in the triple store, the sparse projection
    schedule (project the rows an epoch is about to read, full projection
    once at the end) applies exactly the dense per-epoch full projection —
    bit-identical params and losses."""
    e = 60
    rng = np.random.default_rng(0)
    # every entity occurs as a head → touched every epoch
    tri = np.stack(
        [np.arange(e), rng.integers(0, 5, e), rng.integers(0, e, e)], axis=1
    ).astype(np.int32)
    tri = np.concatenate([tri, tri[rng.integers(0, e, 140)]])
    m, kw_d = _scan_kwargs(e, "dense")
    _, kw_s = _scan_kwargs(e, "sparse")
    p = init_kge(jax.random.PRNGKey(0), m)
    padded, _, _ = pad_tables(p, m)
    args = (pad_triples(jnp.asarray(tri), 50), jax.random.PRNGKey(1),
            jnp.float32(0.5), jnp.int32(e))
    dense, ld = _train_scan(padded, *args, **kw_d)
    sparse, ls = _train_scan(padded, *args, **kw_s)
    np.testing.assert_array_equal(np.asarray(ld), np.asarray(ls))
    for k in dense:
        np.testing.assert_array_equal(np.asarray(dense[k]), np.asarray(sparse[k]))


def test_sparse_renorm_close_and_projected_general():
    """General stores: the dense schedule re-projects untouched rows every
    epoch (1-ulp drift on a few rows — x/‖x‖ is not a bit fixpoint), so the
    contract is: trajectories agree to fp tolerance AND the sparse result is
    fully projected (no entity norm above 1)."""
    e = 400
    rng = np.random.default_rng(1)
    tri = np.stack(
        [rng.integers(0, e, 150), rng.integers(0, 5, 150),
         rng.integers(0, e, 150)], axis=1,
    ).astype(np.int32)
    m, kw_d = _scan_kwargs(e, "dense", epochs=4)
    _, kw_s = _scan_kwargs(e, "sparse", epochs=4)
    p = init_kge(jax.random.PRNGKey(2), m)
    padded, _, _ = pad_tables(p, m)
    args = (pad_triples(jnp.asarray(tri), 50), jax.random.PRNGKey(3),
            jnp.float32(0.5), jnp.int32(e))
    dense, ld = _train_scan(padded, *args, **kw_d)
    sparse, ls = _train_scan(padded, *args, **kw_s)
    np.testing.assert_allclose(np.asarray(ld), np.asarray(ls), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(dense["ent"]), np.asarray(sparse["ent"]), atol=1e-6
    )
    norms = np.linalg.norm(np.asarray(sparse["ent"]), axis=-1)
    assert (norms <= 1.0 + 1e-5).all()


def test_sparse_renorm_padding_invariance():
    """Sparse renorm keeps the bucket-padding invariant: growing the physical
    table leaves the logical rows bit-identical and padding rows zero."""
    e = 70
    rng = np.random.default_rng(4)
    tri = np.stack(
        [rng.integers(0, e, 120), rng.integers(0, 5, 120),
         rng.integers(0, e, 120)], axis=1,
    ).astype(np.int32)
    m, kw = _scan_kwargs(e, "sparse")
    kw["batch"] = 40
    p = init_kge(jax.random.PRNGKey(5), m)
    args = (pad_triples(jnp.asarray(tri), 40), jax.random.PRNGKey(6),
            jnp.float32(0.5), jnp.int32(e))
    small, l_small = _train_scan(p, *args, **kw)
    grown = {k: jnp.pad(v, ((0, 64), (0, 0))) for k, v in p.items()}
    big, l_big = _train_scan(grown, *args, **kw)
    np.testing.assert_array_equal(np.asarray(l_small), np.asarray(l_big))
    for k in p:
        n = p[k].shape[0]
        np.testing.assert_array_equal(np.asarray(small[k]), np.asarray(big[k][:n]))
    np.testing.assert_array_equal(np.asarray(big["ent"][e:]), 0.0)


def test_resolve_renorm_threshold():
    assert resolve_renorm(100, 100_000) == "sparse"  # 400 rows vs 100k
    assert resolve_renorm(10_000, 10_240) == "dense"  # 40k rows vs 10k
