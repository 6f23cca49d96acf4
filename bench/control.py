"""The control: the plain reference put in the program's place and computed
in bfloat16, the precision below the configurations' float32, and held to
the same comparison as the program. It has to come out not correct.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 [--seconds 5]

Serving cells: the traffic of a ``--seconds`` window is generated as in a
run, the same seeded sample of rank and top-k queries is answered by the
bfloat16 reference and compared with the float32 reference. Federation
cells: the bfloat16 reference's first ticks are compared with the float32
reference's. Prints one JSON line per seed with every number compared and
its limit. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def serve_checks(cfg, tr, seed: int, seconds: float):
    import jax.numpy as jnp
    import numpy as np

    from bench.drivers import serve
    from bench.gen import traffic as traffic_gen
    from bench.gen import universe
    from bench.reference import serve as ref

    owners, _ = universe.load(cfg["universe"], seed)
    g = owners[cfg["owner"]]
    known = np.concatenate([g["train"], g["valid"], g["test"]])
    params = serve.make_tables(seed, int(g["num_entities"]), int(g["num_relations"]), cfg["dim"])
    gen = traffic_gen.open_loop(tr, g["test"], seed, seconds)

    class Served:
        state = "served"

    rank_i, topk_i = serve.sample(gen, [Served()] * len(gen["at"]), seed, tr["check_per_kind"])
    filt_r = ref.known_tails(known, gen["h"][rank_i], gen["r"][rank_i])
    filt_k = ref.known_tails(known, gen["h"][topk_i], gen["r"][topk_i])
    s_rank = ref.scores(params, gen["h"][rank_i], gen["r"][rank_i])
    s_topk = ref.scores(params, gen["h"][topk_i], gen["r"][topk_i])
    c_rank = ref.scores(params, gen["h"][rank_i], gen["r"][rank_i], dtype=jnp.bfloat16)
    c_topk = ref.scores(params, gen["h"][topk_i], gen["r"][topk_i], dtype=jnp.bfloat16)
    counts = ref.rank_answers(c_rank, gen["t"][rank_i], filt_r)
    ids, vals = ref.topk_answers(c_topk, filt_k, tr["k"])
    return serve.checks_of(s_rank, s_topk, gen, rank_i, topk_i, filt_r, filt_k,
                           counts, ids, vals, cfg["dim"], tr["limits"], 0)


def federate_checks(cfg, tr, seed: int):
    import jax.numpy as jnp

    from bench.drivers import federate
    from bench.gen import universe

    owners, _ = universe.load(cfg["universe"], seed)
    n = tr["compared_ticks"]
    want = federate.reference_run(owners, cfg, seed, n, jnp.float32)
    got = federate.reference_run(owners, cfg, seed, n, jnp.bfloat16)
    return federate.checks_of(federate.gaps(got, want), tr["limits"])


def checks(cell_name: str, seed: int, seconds: float):
    cell = harness.cell_of(cell_name)
    cfg, tr = harness.config_of(cell), harness.traffic_of(cell)
    if tr["driver"] == "serve":
        return serve_checks(cfg, tr, seed, seconds)
    return federate_checks(cfg, tr, seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        cs = checks(args.workload, seed, args.seconds)
        print(json.dumps({"seed": seed, "correct": all(c.ok for c in cs),
                          "checks": {c.name: [c.value, c.limit] for c in cs}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
