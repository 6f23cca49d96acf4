"""Serving: token-engine continuous batching + the KGE serving tier.

KGE coverage: ranker rank parity vs the seed reference math, top-k filter
exclusion, request validation (range + non-finite bitmask), version swap,
tier batching bit-parity vs per-call, program-cache pinning across traffic
mixes, replica routing, and the version hot-swap boundary (manual publish
and a federation-tick flip) — zero failed requests, ranks bit-equal per
version."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.kernels.dispatch import resolve_serve_impl, resolve_serve_replicas
from repro.kge.models import KGEModel, score_all_tails
from repro.kge.trainer import init_kge
from repro.models.model import decode_step, init_cache, init_params, prefill
from repro.serving import (
    FilterPack,
    KGECandidateRanker,
    KGEServingTier,
    serving_program_cache_size,
)
from repro.serving.engine import ServingEngine, _streaming_topk_jit


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(get_config("qwen3-0.6b")).replace(dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _reference(cfg, params, prompt, n):
    cache = init_cache(cfg, 1, 64, jnp.float32)
    logits, cache = prefill(params, cfg, jnp.asarray(prompt[None]), cache)
    out = [int(jnp.argmax(logits[0, -1]))]
    pos = len(prompt)
    for _ in range(n - 1):
        lg, cache = decode_step(
            params, cfg, jnp.asarray([[out[-1]]], jnp.int32), cache, jnp.int32(pos)
        )
        out.append(int(jnp.argmax(lg[0, 0])))
        pos += 1
    return out


def test_continuous_batching_matches_sequential(setup):
    cfg, params = setup
    eng = ServingEngine(params, cfg, max_batch=2, max_len=64)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 6 + i).astype(np.int32) for i in range(4)]
    rids = [eng.submit(p, max_new_tokens=5) for p in prompts]
    done = eng.run_until_drained()
    assert len(done) == 4
    for rid, prompt in zip(rids, prompts):
        got = next(r for r in done if r.rid == rid).generated
        assert got == _reference(cfg, params, prompt, 5), rid


def test_slots_recycled(setup):
    cfg, params = setup
    eng = ServingEngine(params, cfg, max_batch=1, max_len=64)  # forces queueing
    rng = np.random.default_rng(1)
    for i in range(3):
        eng.submit(rng.integers(0, cfg.vocab_size, 5).astype(np.int32), max_new_tokens=3)
    done = eng.run_until_drained()
    assert len(done) == 3
    assert all(len(r.generated) == 3 for r in done)


# ---------------------------------------------------------------------------
# KGE candidate ranker + serving tier
# ---------------------------------------------------------------------------
E, R, D = 300, 6, 16


def _tri(n, seed):
    rng = np.random.default_rng(seed)
    return np.stack(
        [rng.integers(0, E, n), rng.integers(0, R, n), rng.integers(0, E, n)],
        axis=1,
    ).astype(np.int64)


@pytest.fixture(scope="module")
def kge_world():
    m = KGEModel("transe", E, R, D)
    params = init_kge(jax.random.PRNGKey(1), m)
    known = _tri(400, seed=100)
    return m, params, known


def _ref_tail_ranks(params, m, known, h, r, t):
    """Seed-style oracle: dense (B, E) scores + per-row Python filtering."""
    from repro.kge.eval import _filter_mask

    hr_t, _ = _filter_mask(known, m.num_entities)
    dense = np.asarray(
        score_all_tails(params, m, jnp.asarray(h), jnp.asarray(r), via_kernel=False)
    )
    ranks = []
    for j in range(len(h)):
        row = dense[j].copy()
        for other in hr_t.get((int(h[j]), int(r[j])), ()):
            if other != int(t[j]):
                row[other] = -np.inf
        ranks.append(1 + int((row > row[int(t[j])]).sum()))
    return np.asarray(ranks)


def test_ranker_rank_parity_vs_reference(kge_world):
    m, params, known = kge_world
    q = _tri(24, seed=2)
    ranker = KGECandidateRanker(params, m, known, block_e=64)
    got = ranker.rank_tails(q[:, 0], q[:, 1], q[:, 2])
    np.testing.assert_array_equal(
        got, _ref_tail_ranks(params, m, known, q[:, 0], q[:, 1], q[:, 2])
    )


def test_ranker_topk_excludes_known_and_matches_bruteforce(kge_world):
    m, params, known = kge_world
    # query keys that certainly have known tails
    q = known[:10]
    ranker = KGECandidateRanker(params, m, known, block_e=64)
    ids, scores = ranker.topk_tails(q[:, 0], q[:, 1], k=7)
    dense = np.asarray(
        score_all_tails(params, m, jnp.asarray(q[:, 0]), jnp.asarray(q[:, 1]),
                        via_kernel=False)
    )
    for j in range(len(q)):
        row = dense[j].copy()
        key = (int(q[j, 0]), int(q[j, 1]))
        for t in ranker._hr_t.get(key, ()):
            row[t] = -np.inf
        expect = np.argsort(-row, kind="stable")[:7]
        assert not (set(ids[j].tolist()) & ranker._hr_t.get(key, set()))
        np.testing.assert_allclose(row[expect], scores[j], rtol=1e-6, atol=1e-6)


def test_filter_pack_pow2_width_and_sentinel(kge_world):
    m, _, known = kge_world
    pack = FilterPack(known, m.num_entities)
    assert pack.width & (pack.width - 1) == 0  # power of two
    assert pack.rows.shape[1] == pack.width
    # unknown key → sentinel row, all −1 (no exclusions)
    rows = pack.rows_for(np.array([0]), np.array([R - 1]))
    if (0, R - 1) not in pack.hr_t:
        assert (rows == -1).all()
    # pinned width refuses to silently truncate
    from repro.kge.eval import pack_padded_filters

    with pytest.raises(ValueError, match="exceeds width"):
        pack_padded_filters([[1, 2, 3]], width=2)


def test_ranker_swap_matches_fresh_ranker(kge_world):
    m, params, known = kge_world
    p2 = init_kge(jax.random.PRNGKey(9), m)
    q = _tri(8, seed=3)
    ranker = KGECandidateRanker(params, m, known, block_e=64)
    before = ranker.rank_tails(q[:, 0], q[:, 1], q[:, 2])
    ranker.swap(p2)
    assert ranker.version == 1
    after = ranker.rank_tails(q[:, 0], q[:, 1], q[:, 2])
    fresh = KGECandidateRanker(p2, m, known, block_e=64)
    np.testing.assert_array_equal(after, fresh.rank_tails(q[:, 0], q[:, 1], q[:, 2]))
    # swap back restores the original ranks bit-exactly
    ranker.swap(params)
    np.testing.assert_array_equal(
        before, ranker.rank_tails(q[:, 0], q[:, 1], q[:, 2])
    )


def test_tier_validation_and_nonfinite_bitmask(kge_world):
    m, params, _ = kge_world
    bad = {k: np.asarray(v).copy() for k, v in params.items()}
    bad["ent"][3, 0] = np.nan
    bad["rel"][1, 2] = np.inf
    tier = KGEServingTier(bad, m, None, block_e=64)
    with pytest.raises(ValueError, match=r"head entity ids .*\[-1\]"):
        tier.submit_rank([-1], [0], [1])
    with pytest.raises(ValueError, match=rf"tail entity ids .*\[{E}\]"):
        tier.submit_rank([0], [0], [E])
    with pytest.raises(ValueError, match=r"non-finite query embedding: entity ids \[3\]"):
        tier.submit_rank([3], [0], [1])
    with pytest.raises(ValueError, match=r"relation ids \[1\]"):
        tier.submit_topk([0], [1], k=3)
    with pytest.raises(ValueError, match="k must be in"):
        tier.submit_topk([0], [0], k=0)
    # publishing a repaired version clears the refusal — masks are per-version
    tier.publish(params)
    req = tier.submit_rank([3], [0], [1])
    tier.run_until_drained()
    assert req.done and req.error is None


def test_tier_batched_parity_mixed_traffic(kge_world):
    m, params, known = kge_world
    ranker = KGECandidateRanker(params, m, known, block_e=64)
    tier = KGEServingTier(params, m, known, block_e=64, max_batch=16)
    rank_reqs, topk_reqs = [], []
    for i, n in enumerate((3, 5, 2, 7, 1, 4)):
        q = _tri(n, seed=10 + i)
        rank_reqs.append((q, tier.submit_rank(q[:, 0], q[:, 1], q[:, 2])))
    for i, n in enumerate((2, 3)):
        q = _tri(n, seed=20 + i)
        topk_reqs.append((q, tier.submit_topk(q[:, 0], q[:, 1], k=5)))
    tier.run_until_drained()
    assert tier.stats["failed"] == 0
    # coalescing actually happened: fewer batches than requests
    assert tier.stats["batches"] < len(rank_reqs) + len(topk_reqs)
    for q, req in rank_reqs:
        np.testing.assert_array_equal(
            req.result, ranker.rank_tails(q[:, 0], q[:, 1], q[:, 2])
        )
    for q, req in topk_reqs:
        ids, vals = ranker.topk_tails(q[:, 0], q[:, 1], k=5)
        np.testing.assert_array_equal(req.result[0], ids)
        np.testing.assert_allclose(req.result[1], vals, rtol=0, atol=0)


def test_tier_direct_impl_is_per_request(kge_world):
    m, params, known = kge_world
    tier = KGEServingTier(params, m, known, block_e=64, serve_impl="direct")
    ranker = KGECandidateRanker(params, m, known, block_e=64)
    qs = [_tri(n, seed=30 + n) for n in (2, 3, 4)]
    reqs = [tier.submit_rank(q[:, 0], q[:, 1], q[:, 2]) for q in qs]
    tier.run_until_drained()
    assert tier.stats["batches"] == len(reqs)  # no coalescing
    for q, req in zip(qs, reqs):
        np.testing.assert_array_equal(
            req.result, ranker.rank_tails(q[:, 0], q[:, 1], q[:, 2])
        )


def test_tier_program_cache_pinned_across_traffic_mixes(kge_world, monkeypatch):
    m, params, known = kge_world
    # a block size no other test uses, so every bucket compiles here
    tier = KGEServingTier(params, m, known, block_e=72, max_batch=16)
    launched = set()
    launch = tier._launch

    def record(kind, host_in, segs, nq, tv, kb, **kw):
        launched.add((kind, host_in[0].shape[0], kb))
        return launch(kind, host_in, segs, nq, tv, kb, **kw)

    monkeypatch.setattr(tier, "_launch", record)
    cold, cold_topk = serving_program_cache_size(), _streaming_topk_jit._cache_size()
    # warm every bucket the tier can emit for this traffic envelope
    for i, n in enumerate((1, 3, 8, 16, 11)):
        q = _tri(n, seed=40 + i)
        tier.submit_rank(q[:, 0], q[:, 1], q[:, 2])
    for i, (n, k) in enumerate(((2, 5), (5, 5), (9, 5), (3, 9))):
        q = _tri(n, seed=50 + i)
        tier.submit_topk(q[:, 0], q[:, 1], k=k)
        tier.run_until_drained()  # one batch each
    warm = serving_program_cache_size()
    # each (kind, rows bucket, k bucket) added exactly one program, and the
    # top-k buckets each one top-k program
    topk = {s for s in launched if s[0] == "topk"}
    assert {s[1:] for s in topk} == {(8, 8), (16, 8), (8, 16)}
    assert warm - cold == len(launched)
    assert _streaming_topk_jit._cache_size() - cold_topk == len(topk)
    # a different mix of sizes within the same bucket envelope (rank batches
    # pad to 16, topk batches to 8) must not retrace
    for i, n in enumerate((2, 7, 13, 1, 16, 4, 9)):
        q = _tri(n, seed=60 + i)
        tier.submit_rank(q[:, 0], q[:, 1], q[:, 2])
    for i, (n, k) in enumerate(((1, 5), (4, 5), (2, 5), (12, 6), (2, 16))):
        q = _tri(n, seed=70 + i)
        tier.submit_topk(q[:, 0], q[:, 1], k=k)
    tier.run_until_drained()
    assert serving_program_cache_size() == warm
    assert tier.stats["failed"] == 0


def test_tier_warm_buckets_swap_pays_no_compile(kge_world):
    """Per-replica warm-up on publish: a tier constructed with
    ``warm_buckets`` pre-traces those buckets against the staged tables, so
    first traffic — and the first post-swap batch — never compiles."""
    m, params, known = kge_world
    p2 = init_kge(jax.random.PRNGKey(12), m)
    tier = KGEServingTier(
        params, m, known, block_e=64, max_batch=16,
        warm_buckets=[("rank", 8), ("rank", 16), ("topk", 8, 5)],
    )
    # constructor publish warmed each spec once per replica
    assert tier.stats["warmed"] == 3 * len(tier.replicas)
    warm = serving_program_cache_size()
    # first real traffic landing in the warmed buckets: zero retraces
    for i, n in enumerate((3, 16, 11)):
        q = _tri(n, seed=140 + i)
        tier.submit_rank(q[:, 0], q[:, 1], q[:, 2])
    q = _tri(5, seed=150)
    tier.submit_topk(q[:, 0], q[:, 1], k=5)
    tier.run_until_drained()
    assert serving_program_cache_size() == warm
    # hot-swap: the publish-time re-warm is a no-op (shapes already traced)
    # and the first post-swap batch still pays no compile
    tier.publish(p2)
    assert tier.stats["warmed"] == 3 * len(tier.replicas)
    assert serving_program_cache_size() == warm
    b = tier.submit_rank(*(_tri(9, seed=160).T))
    q = _tri(4, seed=170)
    tier.submit_topk(q[:, 0], q[:, 1], k=5)
    tier.run_until_drained()
    assert serving_program_cache_size() == warm
    assert tier.stats["failed"] == 0
    assert b.version == 1
    # parity: warmed tier still serves bit-identical ranks
    q = _tri(9, seed=160)
    r2 = KGECandidateRanker(p2, m, known, block_e=64)
    np.testing.assert_array_equal(
        b.result, r2.rank_tails(q[:, 0], q[:, 1], q[:, 2])
    )


def test_tier_warm_buckets_validation(kge_world):
    m, params, known = kge_world
    for bad in ([("rank", 8, 3)], [("topk", 8)], [("scan", 8)], [()]):
        with pytest.raises(ValueError, match="warm bucket"):
            KGEServingTier(params, m, known, block_e=64, warm_buckets=bad)


def test_tier_replica_routing_least_loaded(kge_world, monkeypatch):
    from repro.serving import tier as tier_mod

    m, params, known = kge_world
    dev = jax.devices()[0]
    # two replica slots (same physical device on 1-device CI): the router
    # must still spread consecutive batches by in-flight count
    tier = KGEServingTier(params, m, known, block_e=64, replicas=2,
                          devices=[dev, dev], max_batch=4, max_inflight=4)
    # freeze completion: CPU batches finish between steps, so without this
    # the in-flight gauge drains and the routing decision is timing-luck
    monkeypatch.setattr(tier_mod._InFlight, "ready", lambda self: False)
    for i in range(4):
        q = _tri(4, seed=80 + i)
        tier.submit_rank(q[:, 0], q[:, 1], q[:, 2])
        tier.step()
    assert [rp.inflight for rp in tier.replicas] == [2, 2]
    monkeypatch.undo()
    tier.run_until_drained()
    assert dict(tier.replica_load()) == {0: 2, 1: 2}
    assert tier.stats["failed"] == 0
    # sequential low-traffic batches (each drained before the next, so
    # in-flight is always 0 at pick time) must STILL spread across the
    # ring: lifetime dispatch count tie-breaks before slot
    for i in range(2):
        q = _tri(4, seed=86 + i)
        tier.submit_rank(q[:, 0], q[:, 1], q[:, 2])
        tier.run_until_drained()
    assert dict(tier.replica_load()) == {0: 3, 1: 3}


def test_tier_hot_swap_boundary_bit_equal(kge_world):
    m, params, known = kge_world
    p2 = init_kge(jax.random.PRNGKey(11), m)
    tier = KGEServingTier(params, m, known, block_e=64, max_batch=8)
    q = _tri(6, seed=90)
    # dispatch A before the flip (in-flight on v0), then publish, then B
    a = tier.submit_rank(q[:, 0], q[:, 1], q[:, 2])
    tier.step()
    tier.publish(p2)
    b = tier.submit_rank(q[:, 0], q[:, 1], q[:, 2])
    tier.run_until_drained()
    assert tier.stats["failed"] == 0
    assert (a.version, b.version) == (0, 1)
    r1 = KGECandidateRanker(params, m, known, block_e=64)
    r2 = KGECandidateRanker(p2, m, known, block_e=64)
    np.testing.assert_array_equal(a.result, r1.rank_tails(q[:, 0], q[:, 1], q[:, 2]))
    np.testing.assert_array_equal(b.result, r2.rank_tails(q[:, 0], q[:, 1], q[:, 2]))


def test_federation_tick_version_flip_serves_bit_equal():
    """The acceptance bar: a tier attached to a federating owner hot-swaps
    on every accepted tick update with ZERO failed requests, and ranks
    served after the flip are bit-equal to a per-call ranker on the
    owner's accepted params."""
    from repro.core.federation import FederationScheduler
    from repro.core.ppat import PPATConfig
    from repro.kge.data import synthesize_universe

    kgs = synthesize_universe(
        seed=1, scale=1 / 500,
        kg_stats=[("A", 12, 90000, 300000), ("B", 10, 70000, 250000)],
        alignments=[("A", "B", 30000)],
    )
    ctr = itertools.count()
    # monotone score ⇒ every handshake/self-train is accepted: the flip is
    # deterministic, not at the mercy of tiny-universe training dynamics
    sched = FederationScheduler(
        kgs, dim=16, ppat_cfg=PPATConfig(steps=5, seed=0),
        local_epochs=2, update_epochs=2, seed=0,
        score_fn=lambda name: float(next(ctr)),
    )
    sched.initial_training()
    tier = KGEServingTier.for_owner(sched, "A", block_e=64, max_batch=16)
    v0 = tier.version
    q = np.asarray(kgs["A"].test)[:6]
    pre = tier.submit_rank(q[:, 0], q[:, 1], q[:, 2])
    tier.step()  # dispatched before any tick → pinned to v0
    sched.run(max_ticks=2)
    post = tier.submit_rank(q[:, 0], q[:, 1], q[:, 2])
    tier.run_until_drained()
    accepts = sum(
        1 for e in sched.events
        if e.accepted and e.host == "A" and e.kind != "init"
    )
    assert accepts >= 1
    assert tier.version == v0 + accepts  # one publish per accepted update
    assert tier.stats["failed"] == 0 and tier.stats["publish_errors"] == 0
    assert (pre.version, post.version) == (v0, tier.version)
    known = np.concatenate([kgs["A"].train, kgs["A"].valid, kgs["A"].test])
    tr = sched.trainers["A"]
    now = KGECandidateRanker(dict(tr.params), tr.model, known, block_e=64)
    np.testing.assert_array_equal(
        post.result, now.rank_tails(q[:, 0], q[:, 1], q[:, 2])
    )
    assert pre.result is not None and pre.error is None


def test_resolve_serve_knobs(monkeypatch):
    assert resolve_serve_impl() == "batched"
    assert resolve_serve_impl("direct") == "direct"
    monkeypatch.setenv("REPRO_SERVE_IMPL", "direct")
    assert resolve_serve_impl() == "direct"
    assert resolve_serve_impl("batched") == "batched"  # explicit wins
    with pytest.raises(ValueError, match="unknown serve impl"):
        resolve_serve_impl("turbo")
    monkeypatch.setenv("REPRO_SERVE_REPLICAS", "3")
    assert resolve_serve_replicas() == 3
    assert resolve_serve_replicas(1) == 1  # explicit wins
    with pytest.raises(ValueError, match="replicas must be >= 1"):
        resolve_serve_replicas(0)


@pytest.mark.parametrize(
    "impl, fifo, expect",
    [
        # rank, top-k, rank: each batch stops at the other kind, the last
        # at the empty queue
        ("batched", [("rank", 2), ("topk", 2), ("rank", 2)],
         {"cut_kind": 2, "cut_full": 0, "cut_empty": 1}),
        # four 3-row ranks under max_batch 8: 3 + 3, then 3 would pass it
        ("batched", [("rank", 3)] * 4,
         {"cut_kind": 0, "cut_full": 1, "cut_empty": 1}),
        ("batched", [("topk", 1)], {"cut_kind": 0, "cut_full": 0, "cut_empty": 1}),
        ("direct", [("rank", 2), ("topk", 2)],
         {"cut_kind": 0, "cut_full": 0, "cut_empty": 0}),
    ],
    ids=["kind", "full", "empty", "direct"],
)
def test_tier_coalesce_cut_counters(kge_world, impl, fifo, expect):
    m, params, known = kge_world
    tier = KGEServingTier(params, m, known, block_e=64, max_batch=8,
                          serve_impl=impl)
    for i, (kind, n) in enumerate(fifo):
        q = _tri(n, seed=200 + i)
        if kind == "rank":
            tier.submit_rank(q[:, 0], q[:, 1], q[:, 2])
        else:
            tier.submit_topk(q[:, 0], q[:, 1], k=5)
    tier.run_until_drained()
    cuts = {k: tier.stats[k] for k in expect}
    assert cuts == expect
    # every batched coalesce counts exactly once, a direct one never
    assert sum(cuts.values()) == (tier.stats["batches"] if impl == "batched" else 0)


@pytest.mark.parametrize("fault", [None, "crash", "straggle"])
def test_tier_request_dispatch_time_and_batch(kge_world, monkeypatch, fault):
    from repro.core.faults import ServeFault, ServeFaultPlan

    m, params, known = kge_world
    dev = jax.devices()[0]
    # launch seq 0 on slot 0 crashes (retried) or straggles 30 s (hedged)
    plan = fault and ServeFaultPlan(table={(0, 0): ServeFault(fault, delay=30.0)})
    tier = KGEServingTier(params, m, known, block_e=64, max_batch=8,
                          replicas=2, devices=[dev, dev], serve_faults=plan,
                          hedge_after=0.01 if fault == "straggle" else None)
    launches = []
    real = tier._launch

    def spy(*a, **kw):
        fl = real(*a, **kw)
        launches.append(fl)
        return fl

    monkeypatch.setattr(tier, "_launch", spy)
    reqs = []
    for i, n in enumerate((2, 3, 1, 4)):
        q = _tri(n, seed=210 + i)
        reqs.append(tier.submit_rank(q[:, 0], q[:, 1], q[:, 2]))
    q = _tri(2, seed=220)
    reqs.append(tier.submit_topk(q[:, 0], q[:, 1], k=5))
    tier.run_until_drained()
    assert tier.stats["retried"] == (fault == "crash")
    assert tier.stats["hedged"] == (fault == "straggle")
    assert tier.stats["failed"] == 0
    for req in reqs:
        assert req.state == "served"
        assert req.submitted_at <= req.dispatched_at <= req.finished_at
        mine = [fl for fl in launches if any(q is req for q, _, _ in fl.segs)]
        # the first launch set dispatched_at; the last one served it
        assert req.dispatched_at == mine[0].dispatched_at
        assert req.batch == mine[-1].seq
    tried = [fl.seq for fl in launches if any(q is reqs[0] for q, _, _ in fl.segs)]
    assert tried[0] == 0 and len(tried) == 1 + bool(fault)


def test_tier_spans_in_order(kge_world, monkeypatch):
    from repro.serving import tier as tier_mod

    m, params, known = kge_world
    tier = KGEServingTier(params, m, known, block_e=64, max_batch=8)
    events = []

    class Recorder:
        def __init__(self, name, **meta):
            self.name, self.meta = name, dict(meta)

        def __enter__(self):
            events.append(("enter", self.name, self))
            return self

        def __exit__(self, *exc):
            events.append(("exit", self.name, self))
            return False

        def set_metadata(self, **meta):
            self.meta.update(meta)

    monkeypatch.setattr(tier_mod, "TraceAnnotation", Recorder)
    # the batch is not ready at the first poll, so the reap waits once
    real_ready, polls = tier_mod._InFlight.ready, []

    def ready(self):
        polls.append(1)
        return len(polls) > 1 and real_ready(self)

    monkeypatch.setattr(tier_mod._InFlight, "ready", ready)
    q = _tri(3, seed=230)
    req = tier.submit_rank(q[:, 0], q[:, 1], q[:, 2])
    tier.run_until_drained()
    assert req.state == "served"
    names = [n for kind, n, _ in events if kind == "enter"]
    assert names == ["repro.serve." + s for s in (
        "submit", "coalesce", "assemble", "put", "launch", "wait", "collect")]
    # leaf spans: each one closes before the next opens
    assert [k for k, _, _ in events] == ["enter", "exit"] * len(names)
    meta = {n[len("repro.serve."):]: sp.meta for k, n, sp in events if k == "enter"}
    assert meta["submit"] == {"rid": req.rid}
    assert meta["coalesce"] == {"rows": 3}
    assert meta["assemble"] == {"kind": "rank", "rows": 3}
    assert meta["put"] == meta["collect"] == {"seq": req.batch}
    assert meta["launch"] == {"seq": req.batch, "replica": 0}
