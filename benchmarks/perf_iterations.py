"""§Perf hillclimb driver: lower named variants of the three chosen pairs and
record the corrected roofline terms per iteration.

  PYTHONPATH=src python benchmarks/perf_iterations.py [--pair qwen3|jamba|kimi]

Writes benchmarks/perf_results.json. Each entry is one
hypothesis → change → measure cycle; the narrative lives in EXPERIMENTS.md.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)

import argparse
import json
import time

import jax

from repro.configs import TrainConfig, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.workloads import default_train_config, make_workload
from repro.utils.hlo import collective_bytes, loop_aware_collective_bytes
from repro.utils.roofline import roofline_terms
from repro.configs.base import INPUT_SHAPE_BY_NAME

HERE = os.path.dirname(__file__)


def measure(cfg, shape_name, tcfg=None, label="", layout="tp"):
    mesh = make_production_mesh()
    shape = INPUT_SHAPE_BY_NAME[shape_name]
    wl = make_workload(cfg, shape_name, mesh, tcfg=tcfg, layout=layout)
    t0 = time.perf_counter()
    with mesh:
        compiled = (
            jax.jit(wl["fn"], in_shardings=wl["in_shardings"],
                    out_shardings=wl["out_shardings"])
            .lower(*wl["args"]).compile()
        )
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    txt = compiled.as_text()
    res = {
        "arch": cfg.name, "shape": shape_name, "variant": label,
        "compile_s": round(time.perf_counter() - t0, 1),
        "memory": {"peak_bytes_per_device": mem.peak_memory_in_bytes,
                   "argument_bytes_per_device": int(mem.argument_size_in_bytes)},
        "cost": {"flops": float(cost.get("flops", 0.0)),
                 "bytes_accessed": float(cost.get("bytes accessed", 0.0))},
        "collectives": collective_bytes(txt),
        "collectives_corrected": loop_aware_collective_bytes(txt),
    }
    res["roofline"] = roofline_terms(cfg, shape, res, chips=mesh.devices.size)
    rf = res["roofline"]
    print(f"[{cfg.name} × {shape_name} | {label}] "
          f"compute={rf['compute_s']:.3e} memory={rf['memory_s']:.3e} "
          f"collective={rf['collective_s']:.3e} → {rf['bottleneck']} "
          f"| coll/dev={res['collectives_corrected']['total']/2**30:.1f}GiB "
          f"peak={res['memory']['peak_bytes_per_device']/2**30:.1f}GiB")
    return res


def pair_qwen3(results):
    cfg = get_config("qwen3-0.6b")
    # v1: shard-preserving microbatch split + seq-chunked CE (code default now)
    results.append(measure(cfg, "train_4k", label="v1_shard_friendly_accum"))
    # v2: remat policy saves dot outputs → bwd recompute skips TP collectives
    results.append(measure(cfg.replace(remat_policy="dots"), "train_4k",
                           label="v2_remat_dots"))
    # v3: fewer, larger microbatches (4): param-sized collectives ×4 less
    tcfg = default_train_config(cfg, INPUT_SHAPE_BY_NAME["train_4k"])
    tcfg4 = TrainConfig(**{**tcfg.__dict__, "microbatches": 4})
    results.append(measure(cfg.replace(remat_policy="dots"), "train_4k",
                           tcfg=tcfg4, label="v3_mb4"))
    # v4: drop tensor parallelism entirely — 0.6B params replicate; batch over
    # all 256 devices, single microbatch → ONE gradient all-reduce per step.
    tcfg_dp = TrainConfig(**{**tcfg.__dict__, "microbatches": 1, "ce_chunk": 512})
    results.append(measure(cfg, "train_4k", tcfg=tcfg_dp,
                           label="v4_pure_dp", layout="dp"))


import dataclasses as _dc


def pair_jamba(results):
    cfg = get_config("jamba-1.5-large-398b")
    cfg_gather = cfg.replace(moe=_dc.replace(cfg.moe, impl="gather"))
    results.append(measure(cfg_gather, "prefill_32k", label="v1_gather_moe"))
    results.append(measure(cfg_gather.replace(remat_policy="dots"), "prefill_32k",
                           label="v2_remat_dots"))
    # v3/v4 combined in the production config: EP all-to-all + late psum
    results.append(measure(cfg, "prefill_32k", label="v4_a2a_latepsum"))


def pair_kimi(results):
    cfg = get_config("kimi-k2-1t-a32b")
    cfg_gather = cfg.replace(
        moe=_dc.replace(cfg.moe, impl="gather", route_groups=0)
    )
    shape = INPUT_SHAPE_BY_NAME["train_4k"]
    tcfg = default_train_config(cfg, shape)
    results.append(measure(cfg_gather, "train_4k", label="v1_shard_friendly_accum"))
    tcfg_bf16 = TrainConfig(**{**tcfg.__dict__, "moment_dtype": "bfloat16"})
    results.append(measure(cfg_gather, "train_4k", tcfg=tcfg_bf16,
                           label="v2_bf16_moments"))
    cfg_a2a = cfg.replace(moe=_dc.replace(cfg.moe, impl="alltoall", route_groups=0))
    results.append(measure(cfg_a2a, "train_4k", tcfg=tcfg_bf16,
                           label="v4_moe_alltoall"))
    # v6 = production config: + node-limited routing (G=4) + late psum
    results.append(measure(cfg, "train_4k", tcfg=tcfg_bf16,
                           label="v6_a2a_grp4_latepsum"))
    cfg_g2 = cfg.replace(moe=_dc.replace(cfg.moe, route_groups=2))
    results.append(measure(cfg_g2, "train_4k", tcfg=tcfg_bf16,
                           label="v7_grp2_refuted"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", default="all")
    ap.add_argument("--out", default=os.path.join(HERE, "perf_results.json"))
    args = ap.parse_args()
    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    if args.pair in ("all", "qwen3"):
        pair_qwen3(results)
    if args.pair in ("all", "jamba"):
        pair_jamba(results)
    if args.pair in ("all", "kimi"):
        pair_kimi(results)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    print("wrote", args.out)


if __name__ == "__main__":
    main()
