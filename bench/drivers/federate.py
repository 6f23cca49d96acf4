"""Driver ``federate``: federation ticks through ``FederationScheduler``.

Set-up: the owners' graphs from ``bench/gen`` (on-disk cache), a scheduler
with the configuration's sizes (its tables drawn on the device from the
seed; no local training), and the first ticks. Every tick the benchmark
re-baselines each owner's best score below any reachable score and renews
every owner's handshake offers with ``broadcast``, then calls
``run(max_ticks=1)`` on the batched tick engine: every owner hosts one PPAT
handshake (with its only partner, or with the next partner in name order
when it has several), retrains, and backtracks, and every update is
accepted, as in the steady state of Alg. 1 while updates keep coming. The
synthetic graphs do not generalise (Hit@10 stays near 0), so without the
re-baseline no tick would accept and the tables would never move.

The first ``compared_ticks`` ticks run in set-up, through the same call as
the window (the first compiles). The window runs ticks until ``--seconds``
have passed and the tick in flight has completed. A traced run traces the
window's first tick only: a window's retrain steps are more device events
than the profiler keeps (a 12 s trace held device events for its first
6 s).

Correctness: ``bench/reference/tick.py`` follows the first ticks from the
seed. Compared, per owner: epsilon and Hit@10 of each tick, and for each
table (entities, relations) the norm of its change over the first tick and
over all compared ticks.
"""
from __future__ import annotations

import gc

import numpy as np

from bench import harness, trace, work
from bench.gen import universe
from bench.reference import tick as ref


def _kgs(owners):
    from repro.kge.data import KG

    out = {}
    for name, g in owners.items():
        kg = KG(
            name=name, num_entities=int(g["num_entities"]),
            num_relations=int(g["num_relations"]),
            triples=np.concatenate([g["train"], g["valid"], g["test"]]),
            universe_ids=g["universe_ids"],
        )
        kg.train, kg.valid, kg.test = g["train"], g["valid"], g["test"]
        out[name] = kg
    return out


def change_norms(now, before):
    """{owner.table: ||now - before||} on the device, as host floats."""
    import jax.numpy as jnp

    return {
        f"{o}.{k}": float(jnp.linalg.norm((now[o][k] - before[o][k]).astype(jnp.float32)))
        for o in now for k in ("ent", "rel")
    }


def tick_work(owners, pairs, cfg) -> work.Work:
    """The logical work of one tick: every owner's handshake."""
    w = work.Work()
    p = cfg["ppat"]
    for (client, host), pr in pairs.items():
        g = owners[host]
        e, d = int(g["num_entities"]), cfg["dim"]
        w = w + work.ppat(p["steps"], p["batch"], d, p["hidden"], p["teachers"])
        w = w + work.align(len(pr.idx_c), d, len(pr.neigh), len(pr.rels))
        w = w + work.transe_epoch(pr.real, cfg["batch"], e + len(pr.neigh), d)
        if cfg.get("score_metric", "hit10") == "hit10":
            q = min(cfg["score_queries"], len(g["valid"]))
            w = w + work.hit10(q, e, d, 2 * q)
        else:
            w = w + work.triple_scores(2 * len(g["valid"]), d)
    return w


def partners(owners, host):
    """The owners that share aligned entities with ``host``, in name order."""
    return [m for m in owners if m != host and len(
        ref.aligned(owners[host]["universe_ids"], owners[m]["universe_ids"])[0])]


def run(ctx) -> harness.Run:
    import jax

    from repro.core.federation import FederationScheduler
    from repro.core.ppat import PPATConfig

    cfg, tr = ctx.config, ctx.traffic
    t = harness.now()
    owners, built_s = universe.load(cfg["universe"], ctx.seed)
    harness.log(f"federate: graphs generated and written in {built_s:.3f} s "
                f"(left out of set-up), read from disk in "
                f"{harness.now() - t - built_s:.3f} s")
    t = harness.now()
    p = cfg["ppat"]
    sched = FederationScheduler(
        _kgs(owners), dim=cfg["dim"], score_metric=cfg.get("score_metric", "hit10"),
        score_max_test=cfg["score_queries"], local_epochs=cfg["local_epochs"],
        update_epochs=cfg["update_epochs"], seed=ctx.seed, margin=cfg["margin"],
        batch_size=cfg["batch"], tick_placement=cfg["placement"],
        ppat_cfg=PPATConfig(
            batch=p["batch"], num_teachers=p["teachers"], lr=p["lr"],
            momentum=p["momentum"], hidden=p["hidden"], steps=p["steps"],
            lam=p["lam"], delta=p["delta"], ortho_beta=p["ortho_beta"],
            seed=ctx.seed,
        ),
    )
    names = list(sched.trainers)
    for o in names:
        sched.best_snapshot[o] = sched.trainers[o].snapshot()
    traced = ctx.trace_dir is not None

    def tick():
        for o in names:
            sched.best_score[o] = -1.0
        with trace.span("broadcast", traced):
            for o in names:
                sched.broadcast(o)
        n_ev = len(sched.events)
        with trace.span("run", traced):
            sched.run(max_ticks=1)
        return sched.events[n_ev:]

    def tables():
        return {o: dict(sched.trainers[o].params) for o in names}

    start = tables()
    prog = {"epsilon": [], "score": [], "first": None, "change": None, "faults": 0}
    for i in range(tr["compared_ticks"]):
        evs = tick()
        prog["epsilon"].append({e.host: e.epsilon for e in evs})
        prog["score"].append({e.host: e.score_after for e in evs})
        prog["faults"] += sum(1 for e in evs if e.fault is not None or not e.accepted)
        if i == 0:
            prog["first"] = change_norms(tables(), start)
    prog["change"] = change_norms(tables(), start)
    del start
    harness.log(f"federate: scheduler and {tr['compared_ticks']} set-up ticks "
                f"in {harness.now() - t:.3f} s")
    # the work of a tick with each owner's first partner (all of a cell's
    # handshakes are alike in size: one partner, or equal owners)
    pairs = {}
    for h in names:
        c = partners(owners, h)[0]
        pairs[(c, h)] = ref.pair(owners[c], owners[h], cfg["batch"])
    engine = sched._tick_engine
    x0 = engine.resident_transfers
    mark = ctx.clock.mark()
    gc.collect()  # set-up's garbage is not collected inside the window
    setup_s = harness.now() - ctx.t0 - built_s
    harness.log(f"federate: set-up {setup_s:.3f} s")
    ticks = entries = failed = 0
    t_start = harness.now()
    while True:
        # a traced run traces the window's first tick: a whole window of
        # retrain steps is more device events than the profiler keeps
        with trace.capture(ctx.trace_dir if ticks == 0 else None):
            evs = tick()
        ticks += 1
        entries += len(evs)
        failed += sum(1 for e in evs if e.fault is not None or not e.accepted)
        if harness.now() - t_start >= ctx.seconds:
            break
    jax.block_until_ready([t.params for t in sched.trainers.values()])
    t_end = harness.now()
    records = {
        "window_s": t_end - t_start,
        **ctx.clock.since(mark),
        "ticks": ticks,
        "transfers": engine.resident_transfers - x0,
        "device_kind": ctx.devices[0].device_kind,
        "tick_work": tick_work(owners, pairs, cfg),
    }

    def compare():
        nonlocal sched
        sched = None
        gc.collect()
        return _compare(ctx, cfg, tr, owners, prog)

    return harness.Run(setup_s=setup_s, attempted=entries, failed=failed,
                       records=records, compare=compare)


def reference_run(owners, cfg, seed, n_ticks, dtype):
    """The reference's readings over the first ``n_ticks`` ticks."""
    import jax

    with jax.default_matmul_precision("highest"):
        fed = ref.Federation(owners, cfg, seed, dtype)
        start = {o: dict(t) for o, t in fed.tables.items()}
        out = {"epsilon": [], "score": [], "first": None, "change": None}
        for t in range(n_ticks):
            r = fed.tick(t)
            out["epsilon"].append(r.epsilon)
            out["score"].append(r.score)
            if t == 0:
                out["first"] = change_norms(fed.tables, start)
        out["change"] = change_norms(fed.tables, start)
    return out


def gaps(prog, want):
    """The numbers compared: the widest relative epsilon gap, the widest
    Hit@10 gap, and for the first tick's change and the whole change the
    widest gap between norms, by table, relative to the reference's norm of
    that table or of the median table, whichever is larger. Tables the
    reference leaves all but unmoved (a change under a thousandth of the
    median table's) are not compared."""
    eps = max(abs(a[o] - b[o]) / abs(b[o])
              for a, b in zip(prog["epsilon"], want["epsilon"]) for o in b)
    score = max(abs(a[o] - b[o]) for a, b in zip(prog["score"], want["score"]) for o in b)

    def norm_gap(k):
        ref_n = want[k]
        med = float(np.median(list(ref_n.values())))
        moved = [t for t, v in ref_n.items() if v >= 1e-3 * med]
        return max(abs(prog[k][t] - ref_n[t]) / max(ref_n[t], med) for t in moved)

    return {"eps_gap": eps, "score_gap": score,
            "first_change_gap": norm_gap("first"), "change_gap": norm_gap("change")}


def _compare(ctx, cfg, tr, owners, prog):
    import jax.numpy as jnp

    want = reference_run(owners, cfg, ctx.seed, tr["compared_ticks"], jnp.float32)
    return [harness.Check("failed_entries", float(prog["faults"]), 0.0)] + checks_of(
        gaps(prog, want), tr["limits"])


def checks_of(g, limits):
    """The gaps that have a limit are compared; the others (a number that
    neither the control nor a fault moves, such as epsilon here) are only
    reported."""
    for k, v in g.items():
        if k not in limits:
            harness.log(f"reported, not compared: {k} {v!r}")
    return [harness.Check(k, v, limits[k]) for k, v in g.items() if k in limits]
