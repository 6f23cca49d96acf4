"""Find a serving cell's knee: set the cell up once, then offer its traffic
at each of several fixed rates in turn and print one line per rate.

    python3 bench/sweep.py --workload <cell> --seed <n> --rates 40,80,120 [--seconds 10]

The cell's driver (``bench/drivers/<driver>.py``) must have a ``Cell`` with
``window``, as ``serve`` and ``serve_rotate`` do. Each rate gets a window of
``--seconds`` (then the drain) with the traffic file's mix and that rate;
its line gives p50 and p95 latency (ms, from the scheduled send), rows a
batch, the queue at the end of the offered window, and the seconds the
drain took. The knee is the highest rate whose queue stays short and whose
p50 has not begun to climb. Rates are offered in the order given, and the
sweep stops after the first whose drain outlasts its window: that rate is
well past the knee, and a higher one would only queue longer. A cell's
``rate`` is then fixed in its traffic file; the benchmark's own runs never
search for it.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from bench.gen import traffic as traffic_gen

    cell = harness.cell_of(args.workload)
    devices = harness.require_chips(cell["chips"])
    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    traffic = harness.traffic_of(cell)
    ctx = SimpleNamespace(
        cell=cell, config=harness.config_of(cell), traffic=traffic,
        seed=args.seed, seconds=args.seconds, clock=harness.Clock(),
        t0=harness.now(), devices=devices, trace_dir=None,
    )
    driver = harness.load_module(harness.BENCH / "drivers" / f"{traffic['driver']}.py")
    c = driver.Cell(ctx)
    for rate in (float(x) for x in args.rates.split(",")):
        gen = traffic_gen.open_loop(dict(traffic, rate=rate), c.test, args.seed,
                                    args.seconds)
        gc.collect()
        reqs, rec = c.window(gen, traffic["k"], args.seconds, None, ctx.clock)
        lat = rec["latency_ms"]
        print(json.dumps({
            "workload": args.workload, "rate": rate, "requests": len(reqs),
            "p50_ms": harness.nearest_rank(lat, 0.50),
            "p95_ms": harness.nearest_rank(lat, 0.95),
            "rows_per_batch": rec["served"] / max(rec["batches"], 1),
            "queue_end": rec["queue_end"],
            "drain_s": rec["window_s"] - args.seconds,
            "window_compiles": rec["window_compiles"],
        }), flush=True)
        if rec["window_s"] - args.seconds > args.seconds:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
