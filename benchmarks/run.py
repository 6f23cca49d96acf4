"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. ``--only <prefix>`` filters.
``--json [dir]`` additionally writes one machine-readable
``BENCH_<suite>.json`` file per suite (name → µs/call, plus numeric
``_env.*`` rows recording the measurement environment — device count — so
baselines regenerated under different settings diff loudly), so the perf
trajectory can be tracked across PRs by diffing committed artifacts.

``--smoke`` (the ``make bench-smoke`` tier-1 gate) runs EVERY suite at tiny
extents (N=2 owners, E ≤ 1k, single-digit epochs) — the bench code paths,
including their in-bench parity asserts, execute in CI time. Smoke numbers
are not measurements: combining ``--smoke`` with ``--json`` is refused so
they can never overwrite the committed baselines.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

from benchmarks import (
    attack_eval,
    bench_aggregation,
    bench_alignment_scale,
    bench_eval_engine,
    bench_federation_tick,
    bench_kernels,
    bench_link_prediction,
    bench_noise_ablation,
    bench_privacy,
    bench_roofline,
    bench_serving,
    bench_time_cost,
    bench_train_engine,
    bench_triple_classification,
    serve_chaos_smoke,
)
from benchmarks.common import drain_recorded, write_bench_json

SUITES = [
    ("privacy", bench_privacy.main),             # §4.1.2 (ε̂ = 2.73)
    ("kernels", bench_kernels.main),             # kernel parity + timing
    ("roofline", bench_roofline.main),           # §Roofline from dry-run
    ("time_cost", bench_time_cost.main),         # Fig. 7
    ("triple_classification", bench_triple_classification.main),  # Fig. 4/5
    ("link_prediction", bench_link_prediction.main),              # Tab. 4
    ("eval_engine", lambda: bench_eval_engine.main([])),          # fused ranks
    ("train_engine", lambda: bench_train_engine.main([])),        # sparse scan
    ("federation_tick", lambda: bench_federation_tick.main([])),  # tick engine
    ("serving", lambda: bench_serving.main([])),                  # serving tier
    # pass/fail resilience gate (emits no rows → never lands in BENCH json);
    # registered so the tier-1 bench-smoke run exercises the chaos scenario
    ("serve_chaos", lambda: serve_chaos_smoke.gate()),
    ("noise_ablation", bench_noise_ablation.main),                # Tab. 5
    ("alignment_scale", bench_alignment_scale.main),              # Tab. 6
    ("aggregation", bench_aggregation.main),                      # Tab. 7
    ("attack_eval", attack_eval.main),           # measured leakage vs ε
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    # default to the REPO ROOT, not benchmarks/: the committed BENCH_*.json
    # perf-trajectory artifacts live at the root, and defaulting elsewhere
    # quietly left that trajectory empty
    repo_root = os.path.abspath(
        os.path.join(os.path.dirname(__file__) or ".", "..")
    )
    ap.add_argument(
        "--json", nargs="?", const=repo_root,
        default=None, metavar="DIR",
        help="write BENCH_<suite>.json per suite (default: the repo root)",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny-extent tier-1 gate: run every suite at N=2 / E≤1k",
    )
    args = ap.parse_args()
    if args.smoke:
        if args.json is not None:
            ap.error("--smoke numbers must never overwrite BENCH_*.json "
                     "baselines; drop --json")
        os.environ["REPRO_BENCH_SMOKE"] = "1"

    import jax

    from repro.utils.compile_cache import use_compile_cache

    use_compile_cache()
    print(
        f"# devices={len(jax.devices())} backend={jax.default_backend()}"
        f"{' SMOKE (numbers are not measurements)' if args.smoke else ''}",
        file=sys.stderr,
    )
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in SUITES:
        if args.only and not name.startswith(args.only):
            continue
        t0 = time.perf_counter()
        drain_recorded()
        suite_ok = True
        try:
            fn()
        except Exception:
            suite_ok = False
            failures += 1
            traceback.print_exc()
            print(f"{name}.FAILED,0.0,exception")
        if args.json is not None:
            rows = drain_recorded()
            if not suite_ok:
                # partial rows must not read as a clean (regressed) run when
                # artifacts are diffed across PRs — mark the failure
                rows[f"{name}.FAILED"] = 0.0
            if rows:
                path = write_bench_json(name, rows, args.json)
                print(f"# wrote {path}", file=sys.stderr)
        print(
            f"# suite {name} done in {time.perf_counter()-t0:.0f}s",
            file=sys.stderr,
        )
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
