"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic come from ``BENCHMARK.json``,
``bench/configs/<config>.json`` and ``bench/traffic/<traffic>.json``; the
traffic file names the driver (``bench/drivers/<driver>.py``) that sets
the cell up, warms it and runs the measured window. Untraced runs report
the cell's end-to-end metrics, traced runs (``--trace 1``) its per-layer
metrics, each read by ``bench/metrics/<metric>.py``.

Needs the chips the cell asks for: on any other platform, with too few
chips, or with a ``REPRO_*`` dispatch override set, it exits non-zero and
prints no result. The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and in a
traced run ``breakdown``), then ``checks``: each number compared with the
plain reference beside its limit, which also close standard error.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, trace  # noqa: E402

TRACE_DIR = ROOT / "bench" / ".cache" / "trace"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def execute(args, *, t0: float = T0, cache: bool = True) -> str:
    """One run of one cell; returns the result line."""
    bench = harness.manifest()
    cell = harness.cell_of(args.workload, bench)
    devices = harness.require_chips(cell["chips"])
    if cache:
        from repro.utils.compile_cache import use_compile_cache

        use_compile_cache()
    traffic = harness.traffic_of(cell)
    ctx = SimpleNamespace(
        cell=cell, config=harness.config_of(cell), traffic=traffic,
        seed=args.seed, seconds=args.seconds, clock=harness.Clock(), t0=t0,
        devices=devices,
        trace_dir=(TRACE_DIR / args.workload) if args.trace else None,
    )
    driver = harness.load_module(harness.BENCH / "drivers" / f"{traffic['driver']}.py")
    run = driver.run(ctx)
    device = harness.device_block(devices)
    rec = run.records
    rec["setup_s"] = run.setup_s
    inside = (f"compiles inside the window: {rec['window_compiles']} "
              f"(cache reads {rec['window_cache_reads']}, traces {rec['window_traces']})")
    print(inside, flush=True)
    harness.log(inside)
    breakdown = None
    if args.trace:
        summary = trace.reduce(ctx.trace_dir, chips=len(devices))
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        rec["trace"] = summary
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        breakdown = {"device_ops": summary["device_ops"],
                     "idle_gaps": summary["idle_gaps"]}
    t = harness.now()
    checks = run.compare()
    harness.log(f"reference comparison: {harness.now() - t:.3f} s")
    correct = all(c.ok for c in checks)
    metrics = harness.read_metrics(
        harness.metrics_for(cell["name"], bool(args.trace), bench), rec
    )
    for c in checks:
        harness.log(f"check {c.name}: {c.value!r} (limit {c.limit!r})"
                    f"{'' if c.ok else '  FAILED'}")
    return harness.result_line(correct, run, metrics, device, checks, breakdown)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        line = execute(args)
    except harness.BenchError as e:
        harness.log(f"bench: {e}")
        return 1
    except Exception:  # noqa: BLE001 - any failure of the program: no result
        traceback.print_exc()
        return 1
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
