"""Driver ``serve``: open-loop rank and top-k traffic through one
``KGEServingTier``.

Set-up: the owner's graph from ``bench/gen`` (on-disk cache), its TransE
tables made on the device from the seed in one jitted call, the tier with
its filters over every known triple, and every query bucket the traffic
can fill warmed (rank and top-k at 8, 16, 32 and 64 rows). Window: requests
are submitted at their scheduled times from one thread that also drives
``step()``; after the last arrival the tier drains. A request's latency runs
from its scheduled send time until its result is on the host; one that
fails or is shed counts as infinitely late. A traced run traces the last
``TRACE_S`` seconds of the window and the drain.

Correctness: a sample of served rank and top-k answers, drawn from the
seed, is compared with ``bench/reference/serve.py`` on the table that
served it (the seed-made table: the window publishes no other version).
"""
from __future__ import annotations

import contextlib
import functools
import gc
import math
import time

import numpy as np

from bench import harness, trace, work
from bench.gen import traffic as traffic_gen
from bench.gen import universe
from bench.reference import serve as ref

#: how long the window's loop sleeps when a step dispatched nothing
IDLE_S = 2e-4
#: a traced run traces the window's last seconds and the drain: over a
#: 30 s window the profiler dropped device events (a 6.4 s "idle" gap)
TRACE_S = 10.0


def make_tables(seed: int, entities: int, relations: int, dim: int):
    """TransE tables on the device, from the seed, in one jitted call:
    uniform in +-6/sqrt(d) (the program's own init), entity rows projected
    into the unit ball as TransE training keeps them."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def make(key, e, r, d):
        k1, k2 = jax.random.split(key)
        b = 6.0 / math.sqrt(d)
        ent = jax.random.uniform(k1, (e, d), jnp.float32, -b, b)
        ent = ent / jnp.maximum(jnp.linalg.norm(ent, axis=-1, keepdims=True), 1.0)
        rel = jax.random.uniform(k2, (r, d), jnp.float32, -b, b)
        return {"ent": ent, "rel": rel}

    return make(jax.random.PRNGKey(seed), entities, relations, dim)


class Cell:
    """The cell after set-up: the graph, the seed-made table, the tier."""

    def __init__(self, ctx):
        from repro.kge.models import KGEModel
        from repro.serving import KGEServingTier

        cfg, tr = ctx.config, ctx.traffic
        t = harness.now()
        owners, self.built_s = universe.load(cfg["universe"], ctx.seed)
        g = owners[cfg["owner"]]
        self.known = np.concatenate([g["train"], g["valid"], g["test"]])
        self.test = g["test"]
        e, r, self.dim = int(g["num_entities"]), int(g["num_relations"]), cfg["dim"]
        self.entities = e
        harness.log(f"serve: graph generated and written in {self.built_s:.3f} s "
                    f"(left out of set-up), read from disk in "
                    f"{harness.now() - t - self.built_s:.3f} s")
        t = harness.now()
        self.params = make_tables(ctx.seed, e, r, self.dim)
        model = KGEModel("transe", e, r, self.dim, margin=cfg["margin"])
        kw = cfg["tier"]
        buckets = []
        b = kw["min_bucket"]
        while b <= kw["max_batch"]:
            buckets += [("rank", b), ("topk", b, tr["k"])]
            b *= 2
        self.tier = KGEServingTier(
            self.params, model, self.known, max_batch=kw["max_batch"],
            min_bucket=kw["min_bucket"], block_e=kw["block_e"],
            replicas=kw["replicas"], warm_buckets=buckets,
        )
        # one real batch per bucket and kind, so every program the window
        # drives (and the host paths around it) has run once
        for spec in buckets:
            for q in self.test[:spec[1]]:
                if spec[0] == "rank":
                    self.tier.submit_rank(q[:1], q[1:2], q[2:3])
                else:
                    self.tier.submit_topk(q[:1], q[1:2], k=tr["k"])
            self.tier.run_until_drained()
        harness.log(f"serve: tables, filters and tier warmed in "
                    f"{harness.now() - t:.3f} s")

    def window(self, gen, k: int, seconds: float, trace_dir, clock):
        """Offer ``gen``'s requests at their times, then drain. Returns the
        requests, the window's records and the queue lengths a fifth of the
        way in and when the last request was offered."""
        tier = self.tier
        n = len(gen["at"])
        traced = False
        trace_from = max(0.0, seconds - TRACE_S) if trace_dir is not None else math.inf
        reqs = [None] * n
        sent = np.empty(n)
        s0 = dict(tier.stats)
        mark = clock.mark()
        q20 = None
        # step() calls, and (seconds into the window, ms) of those over 50 ms
        steps, long_steps = 0, []
        with contextlib.ExitStack() as tracing:
            t_start, t_wall = harness.now(), time.time()
            i = 0
            while i < n:
                el = harness.now() - t_start
                if q20 is None and el >= 0.2 * seconds:
                    q20 = len(tier.queue)
                if not traced and el >= trace_from:
                    tracing.enter_context(trace.capture(trace_dir))
                    traced = True
                while i < n and gen["at"][i] <= el:
                    hh, rr, tt = gen["h"][i:i + 1], gen["r"][i:i + 1], gen["t"][i:i + 1]
                    with trace.span("submit", traced):
                        if gen["kind"][i] == 0:
                            reqs[i] = tier.submit_rank(hh, rr, tt)
                        else:
                            reqs[i] = tier.submit_topk(hh, rr, k=k)
                    sent[i] = harness.now() - t_start
                    i += 1
                t_step = harness.now()
                with trace.span("step", traced):
                    rows = tier.step()
                steps += 1
                d = harness.now() - t_step
                if d > 0.05:
                    long_steps.append((round(t_step - t_start, 3), round(d * 1e3, 3)))
                if not rows and i < n:
                    # nothing dispatched: wait a little for the next arrival
                    # or result instead of spinning on the host's cores
                    time.sleep(min(IDLE_S, max(0.0, gen["at"][i] - (harness.now() - t_start))))
            q_end = len(tier.queue)
            with trace.span("drain", traced):
                tier.run_until_drained()
            t_end = harness.now()
        lat = np.full(n, np.inf)
        for i, q in enumerate(reqs):
            if q.state == "served":
                lat[i] = (q.finished_at - (t_start + gen["at"][i])) * 1e3
        late = (sent - gen["at"]) * 1e3
        harness.log(f"serve: generator ran late by p95 {np.percentile(late, 95):.3f} ms, "
                    f"max {late.max():.3f} ms; queue {q20} at a fifth of the "
                    f"window, {q_end} at its end")
        harness.log(f"serve: window from unix time {t_wall:.3f}; {steps} calls of step(), "
                    f"{len(long_steps)} over 50 ms (s in, ms): {long_steps[:16]}")
        s1 = tier.stats
        rank_idx = np.flatnonzero(gen["kind"] == 0)
        filt_rank = ref.known_tails(self.known, gen["h"][rank_idx], gen["r"][rank_idx])
        records = {
            "window_s": t_end - t_start,
            **clock.since(mark),
            "latency_ms": lat,
            "served": s1["served"] - s0["served"],
            "batches": s1["batches"] - s0["batches"],
            "queue_fifth": q20, "queue_end": q_end,
            # logical work of the rank requests: queries, their filters (the
            # gold id is one of each query's known tails)
            "rank_queries": len(rank_idx),
            "rank_filter_ids": int(sum(len(f) for f in filt_rank)),
            "entities": self.entities, "dim": self.dim,
            "request_work": work.l1_rank(1, self.entities, self.dim, 0),
        }
        return reqs, records


def run(ctx) -> harness.Run:
    tr = ctx.traffic
    cell = Cell(ctx)
    gen = traffic_gen.open_loop(tr, cell.test, ctx.seed, ctx.seconds)
    gc.collect()  # set-up's garbage is not collected inside the window
    setup_s = harness.now() - ctx.t0 - cell.built_s
    harness.log(f"serve: {len(gen['at'])} requests at {tr['rate']}/s, "
                f"set-up {setup_s:.3f} s")
    reqs, records = cell.window(gen, tr["k"], ctx.seconds, ctx.trace_dir, ctx.clock)
    records["device_kind"] = ctx.devices[0].device_kind
    failed = int(np.sum(~np.isfinite(records["latency_ms"])))
    params, known, dim = cell.params, cell.known, cell.dim

    def compare():
        nonlocal cell
        cell = None
        gc.collect()
        return _compare(ctx, tr, params, known, gen, reqs, dim)

    return harness.Run(setup_s=setup_s, attempted=len(reqs), failed=failed,
                       records=records, compare=compare)


def sample(gen, reqs, seed: int, per_kind: int):
    """Indices of served requests to check, drawn from the seed: up to
    ``per_kind`` of each kind."""
    rng = np.random.default_rng([seed, 0xC0C])
    out = []
    for kind in (0, 1):
        idx = [i for i in np.flatnonzero(gen["kind"] == kind)
               if reqs[i].state == "served"]
        take = min(per_kind, len(idx))
        out.append(np.sort(rng.choice(idx, size=take, replace=False))
                   if take else np.zeros(0, np.int64))
    return out


def answers(gen, reqs, rank_i, topk_i):
    counts = np.array([int(reqs[i].result[0]) - 1 for i in rank_i], np.int64)
    ids = np.array([np.asarray(reqs[i].result[0][0]) for i in topk_i])
    vals = np.array([np.asarray(reqs[i].result[1][0]) for i in topk_i])
    return counts, ids, vals


def checks_of(s_rank, s_topk, gen, rank_i, topk_i, filt_r, filt_k,
              counts, ids, vals, dim, limits, unanswered):
    return [
        harness.Check("unanswered", float(unanswered), 0.0),
        harness.Check("rank_miss", ref.rank_miss(
            s_rank, gen["t"][rank_i], filt_r, counts, dim), limits["rank_miss"]),
        harness.Check("topk_gap", ref.topk_gap(
            s_topk, filt_k, ids, vals, dim), limits["topk_gap"]),
    ]


def _compare(ctx, tr, params, known, gen, reqs, dim):
    rank_i, topk_i = sample(gen, reqs, ctx.seed, tr["check_per_kind"])
    filt_r = ref.known_tails(known, gen["h"][rank_i], gen["r"][rank_i])
    filt_k = ref.known_tails(known, gen["h"][topk_i], gen["r"][topk_i])
    s_rank = ref.scores(params, gen["h"][rank_i], gen["r"][rank_i])
    s_topk = ref.scores(params, gen["h"][topk_i], gen["r"][topk_i])
    counts, ids, vals = answers(gen, reqs, rank_i, topk_i)
    unanswered = sum(1 for q in reqs if q.state != "served")
    return checks_of(s_rank, s_topk, gen, rank_i, topk_i, filt_r, filt_k,
                     counts, ids, vals, dim, tr["limits"], unanswered)
