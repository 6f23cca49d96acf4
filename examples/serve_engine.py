"""Serving example: continuous batching engine over a reduced model.

Submits a burst of ragged-length requests into a small slot pool and drains
them, printing per-request latency — demonstrates the serving substrate the
decode dry-run shapes model.

  PYTHONPATH=src python examples/serve_engine.py [--arch mamba2-2.7b]
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.models.model import init_params
from repro.serving.engine import ServingEngine
from repro.utils.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    args = ap.parse_args()

    cfg = reduced(get_config(args.arch)).replace(dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, max_batch=args.slots, max_len=128)

    rng = np.random.default_rng(0)
    t0 = time.time()
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size, 8 + 4 * (i % 3)).astype(np.int32)
        eng.submit(prompt, max_new_tokens=8 + (i % 2) * 4)
    done = eng.run_until_drained()
    wall = time.time() - t0

    print(f"arch={cfg.name} slots={args.slots} requests={args.requests}")
    for r in sorted(done, key=lambda r: r.rid):
        lat = (r.finished_at - r.submitted_at) * 1e3
        print(f"  req{r.rid}: prompt={len(r.prompt):3d} gen={len(r.generated):3d} "
              f"latency={lat:7.1f} ms  tokens={r.generated[:6]}…")
    total_tokens = sum(len(r.generated) for r in done)
    print(f"drained {total_tokens} tokens in {wall:.2f}s "
          f"({total_tokens/wall:.1f} tok/s aggregate)")


if __name__ == "__main__":
    main()
