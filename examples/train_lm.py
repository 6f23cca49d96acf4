"""End-to-end driver: train a ~100M-param dense LM for a few hundred steps.

Uses the real qwen3-0.6b layer stack at a width that lands near 100M params
(the full 0.6B card at vocab 152k would be embedding-dominated on CPU), the
synthetic corpus, AdamW + cosine, checkpointing — the whole substrate.

  PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.checkpoint import save_checkpoint
from repro.configs import TrainConfig, get_config
from repro.data.pipeline import SyntheticTextDataset, make_batches
from repro.train.step import init_train_state, make_train_step
from repro.utils.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--checkpoint", default="/tmp/train_lm_100m.npz")
    args = ap.parse_args()

    # qwen3 family, sized to ~100M params
    cfg = get_config("qwen3-0.6b").replace(
        num_layers=8, d_model=512, num_heads=8, num_kv_heads=4, head_dim=64,
        d_ff=2048, vocab_size=32768, dtype="float32",
    )
    n = cfg.param_count()
    print(f"model: {cfg.num_layers}L d={cfg.d_model} vocab={cfg.vocab_size} "
          f"→ {n/1e6:.1f}M params")

    tcfg = TrainConfig(
        global_batch=args.batch, seq_len=args.seq_len, microbatches=1,
        ce_chunk=1024, learning_rate=1e-3,
        warmup_steps=20, total_steps=args.steps,
    )
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    step = jax.jit(make_train_step(cfg, tcfg))
    ds = SyntheticTextDataset(vocab_size=cfg.vocab_size, seed=0)

    t0 = time.time()
    first = None
    for i, batch in enumerate(
        make_batches(ds, batch=args.batch, seq_len=args.seq_len, steps=args.steps)
    ):
        state, m = step(state, {k: jnp.asarray(v) for k, v in batch.items()})
        loss = float(m["loss"])
        first = first if first is not None else loss
        if (i + 1) % 20 == 0:
            tps = args.batch * args.seq_len * 20 / (time.time() - t0)
            t0 = time.time()
            print(f"step {i+1:4d} loss={loss:.4f} lr={float(m['lr']):.2e} tok/s={tps:,.0f}")
    print(f"\nloss: {first:.3f} → {loss:.3f} over {args.steps} steps")
    save_checkpoint(args.checkpoint, state.params,
                    metadata={"arch": "qwen3-100m", "steps": args.steps})
    print(f"checkpoint: {args.checkpoint}")


if __name__ == "__main__":
    main()
