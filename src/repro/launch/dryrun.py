import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: lower + compile every (arch × input shape × mesh).

Proves the distribution config is coherent without hardware: the production
mesh is built from 512 host-platform placeholder devices, every step function
is lowered from ShapeDtypeStructs (no allocation), compiled, and its
memory_analysis / cost_analysis / collective schedule are captured for the
roofline (§Roofline in EXPERIMENTS.md).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--json out.json]
"""

import argparse
import json
import sys
import time
import traceback
from typing import Any, Dict

import jax

from repro.configs.base import INPUT_SHAPES
from repro.configs.registry import ARCHS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.workloads import make_workload, supported
from repro.utils.hlo import collective_bytes, loop_aware_collective_bytes
from repro.utils.roofline import roofline_terms


def dryrun_one(
    arch: str, shape_name: str, *, multi_pod: bool = False, verbose: bool = True
) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = next(s for s in INPUT_SHAPES if s.name == shape_name)
    ok, why = supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skipped", "why": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.devices.size
    t0 = time.perf_counter()
    wl = make_workload(cfg, shape_name, mesh, multi_pod=multi_pod)
    with mesh:
        lowered = jax.jit(
            wl["fn"],
            in_shardings=wl["in_shardings"],
            out_shardings=wl["out_shardings"],
        ).lower(*wl["args"])
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    coll = collective_bytes(hlo_text)
    coll_corrected = loop_aware_collective_bytes(hlo_text)
    res = {
        "arch": arch,
        "shape": shape_name,
        "kind": wl["kind"],
        "status": "ok",
        "chips": int(n_chips),
        "multi_pod": multi_pod,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes_per_device": int(mem.argument_size_in_bytes),
            "output_bytes_per_device": int(mem.output_size_in_bytes),
            "temp_bytes_per_device": int(mem.temp_size_in_bytes),
            "peak_bytes_per_device": mem.peak_memory_in_bytes,
        },
        "cost": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        "collectives": coll,
        "collectives_corrected": coll_corrected,
    }
    res["roofline"] = roofline_terms(cfg, shape, res, chips=n_chips)
    if verbose:
        m = res["memory"]
        r = res["roofline"]
        print(
            f"[ok] {arch} × {shape_name} ({'2-pod' if multi_pod else '1-pod'}, "
            f"{n_chips} chips) compile={t_compile:.0f}s "
            f"peak/dev={m['peak_bytes_per_device']/2**30:.2f}GiB "
            f"args/dev={m['argument_bytes_per_device']/2**30:.2f}GiB "
            f"compute={r['compute_s']:.2e}s memory={r['memory_s']:.2e}s "
            f"collective={r['collective_s']:.2e}s → {r['bottleneck']}"
        )
        sys.stdout.flush()
    return res


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    combos = []
    archs = sorted(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = [s.name for s in INPUT_SHAPES] if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for mp in meshes:
        for a in archs:
            for s in shapes:
                combos.append((a, s, mp))

    results = []
    for a, s, mp in combos:
        try:
            results.append(dryrun_one(a, s, multi_pod=mp))
        except Exception as e:  # a failure here is a bug in the system
            traceback.print_exc()
            results.append(
                {"arch": a, "shape": s, "multi_pod": mp, "status": "error",
                 "error": f"{type(e).__name__}: {e}"}
            )
        if results[-1]["status"] == "skipped":
            print(f"[skip] {a} × {s}: {results[-1]['why']}")

    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\ndry-run: {n_ok} ok, {n_skip} skipped (documented), {n_err} errors")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.json}")
    if n_err:
        sys.exit(1)


if __name__ == "__main__":
    main()
