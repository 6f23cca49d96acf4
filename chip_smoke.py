"""Drive federation, retraining and serving once on TPU and check the results.

    python chip_smoke.py            # one chip: Dbpedia + Geonames at full scale
    python chip_smoke.py --chips 4  # four chips: owner-sharded ticks vs single

One chip: the paper's largest pair (Tab. 2/3: Dbpedia 491,078 entities,
14,085 relations; Geonames 300,000 / 6; 118,939 aligned), TransE at d=100,
backtracking on filtered Hit@10. It runs ``FederationScheduler`` initial
training and two batched ticks, checks the fused-rank kernel against its
XLA twin, then serves rank and top-k traffic through ``KGEServingTier``
across a hot-swapping third tick.

Four chips: four equal owners at FB15k-237's per-client size when split by
relation over four clients (FedE, arXiv 2010.12882). Two ticks with owners
sharded one per chip are compared with the same ticks on one chip, then a
four-replica serving tier takes traffic.

Needs a TPU: with any other platform it exits non-zero before doing any work.
Every ``REPRO_*`` dispatch override must be unset. Timings are printed for
information only. The last line of output is the JSON contract
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
from pathlib import Path


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def require_tpu(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"needs a TPU; JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        fail(f"--chips {chips} needs {chips} TPU chips; JAX found {len(devs)}")
    overrides = sorted(k for k in os.environ if k.startswith("REPRO_"))
    if overrides:
        fail(f"refusing to run with dispatch overrides set: {overrides}")
    return devs


class Clock:
    """Wall and XLA compile seconds per phase (compile seconds include
    persistent-cache reads, which is how a cache hit shows)."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.cache_hits = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def phase(self, name: str, fn, *a, **kw):
        t0, c0 = time.perf_counter(), self.compile_s
        out = fn(*a, **kw)
        wall = time.perf_counter() - t0
        comp = self.compile_s - c0
        print(f"phase {name}: {wall:.1f} s wall, {comp:.1f} s compile, "
              f"{wall - comp:.1f} s other (information only)")
        return out


def check_events(events, what: str) -> None:
    import math

    faults = [e for e in events if e.fault is not None]
    print(f"{what}: {len(events)} events, {len(faults)} with a fault")
    if faults:
        fail(f"{what}: fault events {[(e.host, e.client, e.fault) for e in faults]}")
    for e in events:
        if not (math.isfinite(e.score_before) and math.isfinite(e.score_after)):
            fail(f"{what}: non-finite score in {e}")
        if e.kind == "ppat" and not math.isfinite(e.epsilon):
            fail(f"{what}: non-finite epsilon in {e}")


def print_events(events) -> None:
    for e in events:
        print(f"  tick {e.tick} {e.kind} {e.host}<-{e.client}: "
              f"score {e.score_before:.4f} -> {e.score_after:.4f} "
              f"accepted={e.accepted} eps={e.epsilon:.3f} {e.seconds:.1f} s")


def rank_kernel_checks(tr, kg, n_queries: int = 1024) -> None:
    """On the trained table: the fused-rank call compiles to the Pallas
    kernel (``tpu_custom_call`` in the compiled text, not the interpreter),
    and its counts match the XLA scan twin up to fp near-ties."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.triple_score import fused_ranks
    from repro.kge.eval import build_score_inputs
    from repro.kge.models import lp_gold_scores, lp_query_tails

    test, filt_t, _ = build_score_inputs(kg, split="valid", max_test=n_queries)
    h, r, t = (jnp.asarray(test[:, i]) for i in range(3))
    q, table, mode = lp_query_tails(tr.params, tr.model, h, r)
    gold = lp_gold_scores(q, table, t, mode)
    filt = jnp.asarray(filt_t)
    rank = jax.jit(functools.partial(fused_ranks, mode=mode))
    text = rank.lower(q, table, gold, filt).compile().as_text()
    if "tpu_custom_call" not in text:
        fail("fused-rank call did not compile to a Pallas TPU kernel")
    print("fused-rank kernel: tpu_custom_call found in the compiled program")
    pallas = np.asarray(rank(q, table, gold, filt))
    xla = np.asarray(fused_ranks(q, table, gold, filt, mode=mode, impl="xla"))
    # Both compute the same f32 tile scores and differ only in summation
    # order: a d-term sum of non-negative |q - e| terms has rounding error
    # at most d * 2^-24 * |score| in each, so a count can only move by the
    # entities whose XLA score lies within twice that of the gold score.
    d = q.shape[1]
    eps = 2 * d * 2.0**-24 * (jnp.abs(gold) + 1.0)
    band = np.asarray(
        fused_ranks(q, table, gold - eps, filt, mode=mode, impl="xla")
    ) - np.asarray(
        fused_ranks(q, table, gold + eps, filt, mode=mode, impl="xla")
    )
    diff = np.abs(pallas.astype(np.int64) - xla)
    print(f"fused-rank pallas vs xla on {len(test)} queries: "
          f"{int((diff > 0).sum())} counts differ, max |diff| {int(diff.max())}, "
          f"near-tie band max {int(band.max())}")
    if (diff > band).any():
        fail("fused-rank pallas and xla counts differ beyond fp near-ties")


def topk_agrees(ids, scores, ref_ids, ref_scores, d: int) -> bool:
    """Top-k ``(ids, scores)`` against the reference's, up to f32 rounding.

    The tier scores a pow-2 bucket of queries with a pow-2 k, the reference
    all queries at once: XLA may sum a score's d terms in another order for
    each shape, which moves it by at most d * 2^-24 * |score| in each. So
    an id both return must carry scores that close, and an id only one
    returns must sit that close to the other's k-th score (a near-tie at
    the cut). Ranks need no such bound: they count against the gold score
    computed in the same tile."""
    import numpy as np

    tol = 2 * d * 2.0**-24 * (float(np.max(np.abs(ref_scores))) + 1.0)
    ref = dict(zip(ref_ids.tolist(), ref_scores.tolist()))
    for e, s in zip(ids.tolist(), scores.tolist()):
        if abs(s - ref[e]) > tol if e in ref else s > ref_scores[-1] + tol:
            return False
    missing = np.isin(ref_ids, ids, invert=True)
    return bool(np.all(ref_scores[missing] <= scores[-1] + tol))


def serve_and_check(sched, owner: str, n: int, swap_ticks: int, **tier_kw):
    """Serve ``n`` rank and ``n`` top-k requests. With ``swap_ticks`` > 0
    they straddle a hot-swap: the first half is dispatched on the tables the
    tier starts with, then federation ticks run until one accepts an update
    for ``owner`` (at most ``swap_ticks``), then the second half is served.
    Every result is checked against a per-call ranker on the version that
    served it. Returns the tier."""
    import numpy as np

    from repro.serving import KGEServingTier
    from repro.serving.engine import KGECandidateRanker

    tier = KGEServingTier.for_owner(sched, owner, **tier_kw)
    tr = sched.trainers[owner]
    tables = {tier.version: dict(tr.params)}
    q = np.asarray(sched.kgs[owner].test)[:n]
    reqs = []

    def submit(rows):
        for i in rows:
            reqs.append((i, tier.submit_rank(
                q[i:i + 1, 0], q[i:i + 1, 1], q[i:i + 1, 2])))
        for i in rows:
            reqs.append((i, tier.submit_topk(q[i:i + 1, 0], q[i:i + 1, 1], k=10)))

    submit(range(n // 2))
    while tier.queue:
        tier.step()  # dispatched now: pinned to the version before the swap
    v0 = tier.version
    for _ in range(swap_ticks):
        n_events = len(sched.events)
        sched.run(max_ticks=1)
        check_events(sched.events[n_events:], "serving tick")
        print_events(sched.events[n_events:])
        if tier.version != v0:
            break
    if swap_ticks:
        print(f"serving: hot-swap from version {v0} to {tier.version}")
        if tier.version == v0:
            fail(f"{swap_ticks} ticks accepted no update for {owner}: "
                 f"no hot-swap")
        tables[tier.version] = dict(tr.params)
    submit(range(n // 2, n))
    tier.run_until_drained()
    s = tier.stats
    print("serving stats: " + ", ".join(
        f"{k}={s[k]}" for k in ("submitted", "served", "failed", "retried",
                                "shed", "publish_errors", "batches")))
    if s["failed"] or s["retried"] or s["publish_errors"]:
        fail("serving failed, retried or unpublished requests")
    if s["served"] != s["submitted"]:
        fail("serving did not serve every submitted request")
    want = {}
    for v, params in tables.items():
        ref = KGECandidateRanker(params, tr.model, filters=tier.filters,
                                 block_e=tier.block_e)
        ids, scores = ref.topk_tails(q[:, 0], q[:, 1], k=10)
        want[v] = (ref.rank_tails(q[:, 0], q[:, 1], q[:, 2]),
                   np.asarray(ids), np.asarray(scores))
    exact, max_diff = 0, 0.0
    for i, req in reqs:
        if req.version not in want:
            fail(f"request {req.rid} served on unknown version {req.version}")
        rank, ids, scores = want[req.version]
        if req.kind == "rank":
            ok = req.result[0] == rank[i]
        else:
            got_ids, got = req.result[0][0], req.result[1][0]
            ok = topk_agrees(got_ids, got, ids[i], scores[i], tr.model.dim)
            exact += bool(np.array_equal(got_ids, ids[i])
                          and np.array_equal(got, scores[i]))
            max_diff = max(max_diff, float(np.max(np.abs(got - scores[i]))))
        if not ok:
            fail(f"request {req.rid}: tier result differs from the per-call "
                 f"ranker on version {req.version}")
    served_on = sorted({req.version for _, req in reqs})
    print(f"serving: {len(reqs)} results equal a per-call "
          f"KGECandidateRanker on their versions {served_on} (top-k: {exact} "
          f"of {n} bit-equal, max score diff {max_diff:.3e})")
    if served_on != sorted(tables):
        fail(f"requests were not served on both versions {sorted(tables)}")
    return tier


def one_chip(args, clock: Clock) -> None:
    from repro.core.federation import FederationScheduler
    from repro.core.ppat import PPATConfig
    from repro.kernels.dispatch import (
        resolve_interpret,
        resolve_rank_impl,
        resolve_tick_impl,
        resolve_tick_placement,
        resolve_train_impl,
    )
    from repro.kge.data import PAPER_ALIGNMENTS, PAPER_KG_STATS, synthesize_universe

    pair = {"Dbpedia", "Geonames"}
    stats = [row for row in PAPER_KG_STATS if row[0] in pair]
    aligns = [row for row in PAPER_ALIGNMENTS if {row[0], row[1]} == pair]
    kgs = clock.phase(
        "synthesize", synthesize_universe, seed=args.seed, scale=1,
        density_boost=1, kg_stats=stats, alignments=aligns, exact=True,
    )
    for name, kg in kgs.items():
        print(f"owner {name}: {kg.num_entities} entities, "
              f"{kg.num_relations} relations, {len(kg.triples)} triples")
    print(f"aligned Dbpedia-Geonames: "
          f"{len(kgs['Dbpedia'].aligned_with(kgs['Geonames'])[0])}")
    print(f"resolved: interpret={resolve_interpret(None)} "
          f"rank={resolve_rank_impl(None, 'l1')} "
          f"train={resolve_train_impl(None, 'transe')} "
          f"tick={resolve_tick_impl(None, 'transe')} "
          f"placement={resolve_tick_placement(None)}")
    print(f"cuts: local epochs 50 -> {args.epochs}, update epochs 25 -> "
          f"{args.epochs}, PPAT steps 200 -> {args.ppat_steps}; widths and "
          f"table sizes as published")
    sched = FederationScheduler(
        kgs, dim=100, score_metric="hit10", local_epochs=args.epochs,
        update_epochs=args.epochs, seed=args.seed,
        ppat_cfg=PPATConfig(steps=args.ppat_steps, seed=args.seed),
    )
    clock.phase("initial_training", sched.initial_training)
    clock.phase("run(max_ticks=2)", sched.run, max_ticks=2)
    ticks = [e for e in sched.events if e.kind != "init"]
    check_events(ticks, "federation ticks")
    ways = {(e.host, e.client) for e in ticks if e.kind == "ppat"}
    if not {("Dbpedia", "Geonames"), ("Geonames", "Dbpedia")} <= ways:
        fail(f"a PPAT handshake did not run each way: {sorted(ways)}")
    print_events(sched.events)
    clock.phase("rank kernel checks", rank_kernel_checks,
                sched.trainers["Dbpedia"], kgs["Dbpedia"])
    # Synthetic tails are nearest neighbours among 491,078 (300,000) latents,
    # so filtered Hit@10 on 200 valid triples stays at 0 for both owners
    # and no Hit@10 tick can accept an update. The ticks under serving
    # backtrack on triple-classification accuracy instead, re-baselined on
    # the tables as they are, so that a tick can accept and hot-swap.
    sched.score_fn = sched._valid_accuracy
    for o in kgs:
        sched.best_score[o] = sched.score_fn(o)
    print("serving ticks backtrack on accuracy, baseline "
          + ", ".join(f"{o} {s:.4f}" for o, s in sched.best_score.items()))
    clock.phase("serving", serve_and_check, sched, "Dbpedia", 64,
                swap_ticks=6)
    print(f"federation epsilon spent: {sched.accountant.epsilon():.3f} "
          f"over {len(sched.epsilons)} handshakes")


def four_chips(args, clock: Clock) -> None:
    import jax
    import numpy as np

    from repro.core.distributed import committed_device
    from repro.core.federation import FederationScheduler
    from repro.core.ppat import PPATConfig
    from repro.kge.data import equal_shape_universe

    # FB15k-237 (14,541 entities, 237 relations, 310,116 triples) split by
    # relation over 4 clients: every client sees the whole entity set;
    # 7,271 of them (just over half) are aligned across every pair
    kgs = clock.phase(
        "synthesize", equal_shape_universe, 4, entities=14541, relations=59,
        triples=77529, shared=7271, seed=args.seed,
    )
    for name, kg in kgs.items():
        print(f"owner {name}: {kg.num_entities} entities, "
              f"{kg.num_relations} relations, {len(kg.triples)} triples")
    print(f"aligned per owner pair: "
          f"{len(kgs['K0'].aligned_with(kgs['K1'])[0])} entities")
    print(f"cuts: local epochs 50 -> {args.epochs}, update epochs 25 -> "
          f"{args.epochs}, PPAT steps 200 -> {args.ppat_steps}")

    def federation(placement):
        # backtracking on triple-classification accuracy: hit@10's known-true
        # filter width differs per owner, which would split equal owners
        # into separate entry signatures and so keep them out of one group
        sched = FederationScheduler(
            kgs, dim=100, local_epochs=args.epochs,
            update_epochs=args.epochs, seed=args.seed,
            ppat_cfg=PPATConfig(steps=args.ppat_steps, seed=args.seed),
        )
        clock.phase(f"{placement}: initial_training", sched.initial_training)
        clock.phase(f"{placement}: run(max_ticks=2)", sched.run,
                    max_ticks=2, tick_placement=placement)
        check_events(sched.events, placement)
        return sched

    sharded = federation("sharded")
    dispatched = dict(sharded._tick_engine.dispatched)
    single = federation("single")
    n_entries = sum(1 for e in sharded.events if e.kind != "init")
    print(f"sharded dispatch: {dispatched} for {n_entries} entries")
    if dispatched["group"] != n_entries or dispatched["single"]:
        fail("not every sharded entry ran inside a shard_map group program")

    # The shard_map body and the single-device entry program trace the same
    # per-entry graph, but XLA fuses the two programs differently on TPU
    # (340 vs 332 fusions when compiled for a described v5e:2x2), so bit
    # equality is not promised, and PPAT voting, the procrustes SVD and the
    # retrain scan would carry any drift on. The decisions must agree. A score may move by 0.01 (one scored triple in a hundred
    # crossing the threshold), epsilon by 1% and an embedding by 5% of its
    # table's largest entry: a shard read from the wrong chip or a stale
    # table moves them far more.
    for a, b in zip(sharded.events, single.events):
        print(f"  tick {a.tick} {a.kind} {a.host}<-{a.client}: accepted "
              f"{a.accepted}/{b.accepted}, score {a.score_after:.4f}/"
              f"{b.score_after:.4f}, eps {a.epsilon:.4f}/{b.epsilon:.4f} "
              f"(sharded/single)")

    def decisions(s):
        return [(e.tick, e.host, e.client, e.kind, e.accepted)
                for e in s.events]

    if decisions(sharded) != decisions(single):
        fail("sharded and single placement took different decisions")
    d_score = max(abs(a.score_after - b.score_after)
                  for a, b in zip(sharded.events, single.events))
    d_eps = max((abs(a.epsilon - b.epsilon) / b.epsilon
                 for a, b in zip(sharded.events, single.events)
                 if a.kind == "ppat"), default=0.0)
    d_emb = max(
        float(np.max(np.abs(x - y)) / np.max(np.abs(y)))
        for o in kgs for k in sharded.trainers[o].params
        for x, y in [(np.asarray(sharded.trainers[o].params[k]),
                      np.asarray(single.trainers[o].params[k]))]
    )
    print(f"sharded vs single: {len(sharded.events)} events, identical "
          f"decisions; max score diff {d_score:.4f} (bound 0.01), max "
          f"relative epsilon diff {d_eps:.2e} (bound 1e-2), max embedding "
          f"diff {d_emb:.2e} of the table's largest entry (bound 5e-2)")
    if d_score > 0.01 or d_eps > 1e-2 or d_emb > 5e-2:
        fail("sharded and single placement differ beyond compile drift")
    # every owner has its own home chip, and an owner that accepted a tick
    # update keeps the accepted tables committed there (one that never
    # accepted still holds its initial-training tables, which are not)
    placement = sharded._tick_engine.placement
    homes = {o: placement.device(o) for o in kgs}
    print("owner homes: " + ", ".join(f"{o}->{d}" for o, d in homes.items()))
    if len(set(homes.values())) != 4:
        fail("owners do not have four distinct home chips")
    accepted = sorted({e.host for e in sharded.events
                       if e.accepted and e.kind != "init"})
    for o in accepted:
        dev = committed_device(sharded.trainers[o].params)
        if dev != homes[o]:
            fail(f"{o}'s accepted tables are on {dev}, not its home {homes[o]}")
    print(f"accepted tables committed to their home chips: {accepted}")
    if not accepted:
        fail("no owner accepted a tick update: residency not shown")
    tier = clock.phase("serving", serve_and_check, sharded, "K0", 64,
                       swap_ticks=0, replicas=4, max_batch=8)
    load = tier.replica_load()
    print(f"replica load (slot, batches): {load}")
    if len(load) != 4 or any(n == 0 for _, n in load):
        fail("the serving tier did not use all four replicas")
    if {rp.device for rp in tier.replicas} != set(jax.devices()[:4]):
        fail("serving replicas are not on four distinct chips")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--epochs", type=int, default=2,
                    help="local and update epochs (the paper's 50 and 25, cut)")
    ap.add_argument("--ppat-steps", type=int, default=200)
    args = ap.parse_args()
    devs = require_tpu(args.chips)
    import jax

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.utils.compile_cache import use_compile_cache

    print(f"jax {jax.__version__}, device_kind {devs[0].device_kind}, "
          f"{len(devs)} device(s)")
    print(f"compile cache: {use_compile_cache()}")
    clock = Clock()
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(args, clock)
    else:
        four_chips(args, clock)
    total = time.perf_counter() - t0
    print(f"total: {total:.1f} s wall, {clock.compile_s:.1f} s compile, "
          f"{clock.cache_hits} persistent-cache hits (information only)")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }}))


if __name__ == "__main__":
    main()
