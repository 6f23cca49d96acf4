"""The bfloat16 control of a ``serve_rotate`` cell: ``bench/control.py``'s,
for the cells that file does not dispatch. The sampled queries of a
``--seconds`` window are answered by ``bench/reference/serve_rotate.py`` in
bfloat16 and held to the float32 reference's comparison. It has to come out
not correct.

    python3 bench/control_rotate.py --workload dbpedia.rotate.serve --seeds 11,12,13

Prints one JSON line per seed with every number compared and its limit.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    from bench.drivers import serve_rotate

    cell = harness.cell_of(args.workload)
    cfg, tr = harness.config_of(cell), harness.traffic_of(cell)
    if tr["driver"] != "serve_rotate":
        raise SystemExit(f"{args.workload} is not a serve_rotate cell; use bench/control.py")
    for seed in (int(s) for s in args.seeds.split(",")):
        cs = serve_rotate.control_checks(cfg, tr, seed, args.seconds)
        print(json.dumps({"seed": seed, "correct": all(c.ok for c in cs),
                          "checks": {c.name: [c.value, c.limit] for c in cs}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
