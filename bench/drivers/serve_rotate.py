"""Driver ``serve_rotate``: the ``serve`` driver's open-loop window over a
RotatE owner.

Set-up: the owner's graph from ``bench/gen`` (on-disk cache), its RotatE
tables made on the device from the seed in one jitted call (entities uniform
in +-6/sqrt(d), phases uniform in +-pi, as the program initialises a RotatE
owner), ``KGEModel("rotate", ...)``, the tier with its filters over every
known triple, and every query bucket of both kinds warmed. The window,
latencies and the sample of answers checked are ``serve.py``'s; the answers
are compared with ``bench/reference/serve_rotate.py``.

The cell measures the fused-rank kernel's ``cl1`` path. A program without
it ranks RotatE in the XLA scan, predicted to cost about what the top-k scan
does, too slow for the cell's rate: its queue would grow through the window
and drain long after. Such a program fails at set-up instead.

Records beyond ``serve.py``'s: ``batch_rows_by_kind`` (the kind and real
rows of every batch that served a request of the window, for
``serve_mfu.rotate``); ``request_work`` counts RotatE's work.
"""
from __future__ import annotations

import functools
import gc
import math

import numpy as np

from bench import harness, work_rotate
from bench.drivers import serve
from bench.gen import traffic as traffic_gen
from bench.gen import universe
from bench.reference import serve_rotate as ref


def make_tables(seed: int, entities: int, relations: int, dim: int):
    """RotatE tables on the device, from the seed, in one jitted call:
    entity rows of d reals ([re | im]) uniform in +-6/sqrt(d), relation rows
    of d/2 phases uniform in +-pi (the program's own init)."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def make(key, e, r, d):
        k1, k2 = jax.random.split(key)
        b = 6.0 / math.sqrt(d)
        ent = jax.random.uniform(k1, (e, d), jnp.float32, -b, b)
        rel = jax.random.uniform(k2, (r, d // 2), jnp.float32, -math.pi, math.pi)
        return {"ent": ent, "rel": rel}

    return make(jax.random.PRNGKey(seed), entities, relations, dim)


def require_cl1_kernel() -> None:
    from repro.kernels.dispatch import RANK_KERNEL_MODES

    if "cl1" not in RANK_KERNEL_MODES:
        raise harness.BenchError(
            "the fused-rank kernel has no cl1 path, which this cell measures: "
            "RotatE ranks would take the XLA scan"
        )


class Cell(serve.Cell):
    """The cell after set-up: the graph, the seed-made RotatE table, the tier."""

    def __init__(self, ctx):  # noqa: D107 - serve.Cell's set-up, RotatE tables
        from repro.kge.models import KGEModel
        from repro.serving import KGEServingTier

        require_cl1_kernel()
        cfg, tr = ctx.config, ctx.traffic
        t = harness.now()
        owners, self.built_s = universe.load(cfg["universe"], ctx.seed)
        g = owners[cfg["owner"]]
        self.known = np.concatenate([g["train"], g["valid"], g["test"]])
        self.test = g["test"]
        e, r, self.dim = int(g["num_entities"]), int(g["num_relations"]), cfg["dim"]
        self.entities = e
        harness.log(f"serve_rotate: graph generated and written in "
                    f"{self.built_s:.3f} s (left out of set-up), read from disk "
                    f"in {harness.now() - t - self.built_s:.3f} s")
        t = harness.now()
        self.params = make_tables(ctx.seed, e, r, self.dim)
        model = KGEModel(cfg["family"], e, r, self.dim, margin=cfg["margin"])
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
        # one real batch per bucket and kind, as serve.Cell does
        for spec in buckets:
            for q in self.test[:spec[1]]:
                if spec[0] == "rank":
                    self.tier.submit_rank(q[:1], q[1:2], q[2:3])
                else:
                    self.tier.submit_topk(q[:1], q[1:2], k=tr["k"])
            self.tier.run_until_drained()
        harness.log(f"serve_rotate: tables, filters and tier warmed in "
                    f"{harness.now() - t:.3f} s")


def batch_rows_by_kind(reqs):
    """[[kind, rows], ...] of every batch that served a request."""
    rows = {}
    for q in reqs:
        if q.state == "served":
            kind, n = rows.get(q.batch, (q.kind, 0))
            rows[q.batch] = (kind, n + 1)
    return [[k, n] for k, n in rows.values()]


def run(ctx) -> harness.Run:
    tr = ctx.traffic
    cell = Cell(ctx)
    gen = traffic_gen.open_loop(tr, cell.test, ctx.seed, ctx.seconds)
    gc.collect()  # set-up's garbage is not collected inside the window
    setup_s = harness.now() - ctx.t0 - cell.built_s
    harness.log(f"serve_rotate: {len(gen['at'])} requests at {tr['rate']}/s, "
                f"set-up {setup_s:.3f} s")
    reqs, records = cell.window(gen, tr["k"], ctx.seconds, ctx.trace_dir, ctx.clock)
    records["device_kind"] = ctx.devices[0].device_kind
    records["request_work"] = work_rotate.cl1_rank(1, cell.entities, cell.dim, 0)
    records["batch_rows_by_kind"] = batch_rows_by_kind(reqs)
    failed = int(np.sum(~np.isfinite(records["latency_ms"])))
    params, known, dim = cell.params, cell.known, cell.dim

    def compare():
        nonlocal cell
        cell = None
        gc.collect()
        return _compare(ctx, tr, params, known, gen, reqs, dim)

    return harness.Run(setup_s=setup_s, attempted=len(reqs), failed=failed,
                       records=records, compare=compare)


def checks_of(s_rank, s_topk, gen, rank_i, topk_i, filt_r, filt_k,
              counts, ids, vals, dim, bound, limits, unanswered):
    return [
        harness.Check("unanswered", float(unanswered), 0.0),
        harness.Check("rank_miss", ref.rank_miss(
            s_rank, gen["t"][rank_i], filt_r, counts, dim, bound),
            limits["rank_miss"]),
        harness.Check("topk_gap", ref.topk_gap(
            s_topk, filt_k, ids, vals, dim), limits["topk_gap"]),
    ]


def reference_inputs(params, known, gen, rank_i, topk_i, dtype=None):
    """Filters and reference scores of the sampled rank and top-k queries
    (float32, or ``dtype`` for the control)."""
    kw = {} if dtype is None else {"dtype": dtype}
    filt_r = ref.known_tails(known, gen["h"][rank_i], gen["r"][rank_i])
    filt_k = ref.known_tails(known, gen["h"][topk_i], gen["r"][topk_i])
    s_rank = ref.scores(params, gen["h"][rank_i], gen["r"][rank_i], **kw)
    s_topk = ref.scores(params, gen["h"][topk_i], gen["r"][topk_i], **kw)
    return filt_r, filt_k, s_rank, s_topk


def _compare(ctx, tr, params, known, gen, reqs, dim):
    rank_i, topk_i = serve.sample(gen, reqs, ctx.seed, tr["check_per_kind"])
    filt_r, filt_k, s_rank, s_topk = reference_inputs(params, known, gen, rank_i, topk_i)
    counts, ids, vals = serve.answers(gen, reqs, rank_i, topk_i)
    unanswered = sum(1 for q in reqs if q.state != "served")
    return checks_of(s_rank, s_topk, gen, rank_i, topk_i, filt_r, filt_k,
                     counts, ids, vals, dim, ref.bound_of(params), tr["limits"],
                     unanswered)


def control_checks(cfg, tr, seed: int, seconds: float):
    """The bfloat16 control (``bench/control.py``'s, for this driver): the
    sampled queries of a ``seconds`` window answered by the reference in
    bfloat16 and held to the float32 reference's comparison."""
    import jax.numpy as jnp

    owners, _ = universe.load(cfg["universe"], seed)
    g = owners[cfg["owner"]]
    known = np.concatenate([g["train"], g["valid"], g["test"]])
    params = make_tables(seed, int(g["num_entities"]), int(g["num_relations"]), cfg["dim"])
    gen = traffic_gen.open_loop(tr, g["test"], seed, seconds)

    class Served:
        state = "served"

    rank_i, topk_i = serve.sample(gen, [Served()] * len(gen["at"]), seed, tr["check_per_kind"])
    filt_r, filt_k, s_rank, s_topk = reference_inputs(params, known, gen, rank_i, topk_i)
    _, _, c_rank, c_topk = reference_inputs(params, known, gen, rank_i, topk_i,
                                            jnp.bfloat16)
    counts = ref.rank_answers(c_rank, gen["t"][rank_i], filt_r)
    ids, vals = ref.topk_answers(c_topk, filt_k, tr["k"])
    return checks_of(s_rank, s_topk, gen, rank_i, topk_i, filt_r, filt_k,
                     counts, ids, vals, cfg["dim"], ref.bound_of(params),
                     tr["limits"], 0)
