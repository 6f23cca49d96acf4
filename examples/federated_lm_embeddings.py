"""FKGE applied to the assigned LLM architectures (DESIGN.md §4).

Two parties train reduced LMs from different corpora with an overlapping
vocabulary (aligned token ids = the paper's aligned entities). The parties
run PPAT over the shared rows of their token-embedding tables; the host
aggregates the DP-synthesized embeddings and continues training. This is the
technique transplanted verbatim onto the transformer substrate — only the
"KG embedding table" becomes the "token embedding table".

  PYTHONPATH=src python examples/federated_lm_embeddings.py [--arch qwen3-0.6b]
"""
import argparse
import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import TrainConfig, get_config, reduced
from repro.core.alignment import procrustes
from repro.core.ppat import PPATConfig, train_ppat
from repro.data.pipeline import SyntheticTextDataset, make_batches
from repro.train.step import init_train_state, make_train_step
from repro.utils.compile_cache import use_compile_cache


def train_party(cfg, seed, steps, batch=8, seq=64):
    tcfg = TrainConfig(global_batch=batch, seq_len=seq, learning_rate=3e-3,
                       warmup_steps=5, total_steps=steps)
    state = init_train_state(jax.random.PRNGKey(seed), cfg)
    step = jax.jit(make_train_step(cfg, tcfg))
    ds = SyntheticTextDataset(vocab_size=cfg.vocab_size, seed=seed)
    loss = None
    for b in make_batches(ds, batch=batch, seq_len=seq, steps=steps, seed=seed):
        state, m = step(state, {k: jnp.asarray(v) for k, v in b.items()})
        loss = float(m["loss"])
    return state, step, ds, loss


def eval_loss(cfg, state, ds, seed=99, batches=5, batch=8, seq=64):
    from repro.train.loss import lm_loss

    total = 0.0
    for i, b in enumerate(make_batches(ds, batch=batch, seq_len=seq,
                                       steps=batches, seed=seed)):
        l, _ = lm_loss(state.params, cfg, jnp.asarray(b["tokens"]),
                       jnp.asarray(b["labels"]))
        total += float(l)
    return total / batches


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--steps", type=int, default=60)
    args = ap.parse_args()

    cfg = reduced(get_config(args.arch)).replace(dtype="float32")
    print(f"arch family: {cfg.name} (reduced: {cfg.num_layers}L d={cfg.d_model})")

    # party A and party B: same vocab (fully aligned token ids), different data
    state_a, _, ds_a, loss_a = train_party(cfg, seed=0, steps=args.steps)
    state_b, step_b, ds_b, loss_b = train_party(cfg, seed=1, steps=args.steps)
    print(f"local training: A loss={loss_a:.3f}  B loss={loss_b:.3f}")

    # aligned rows: the shared head of the vocab (most frequent tokens)
    n_aligned = min(256, cfg.vocab_size)
    idx = jnp.arange(n_aligned)
    x = state_a.params["embed"]["table"][idx].astype(jnp.float32)  # client: A
    y = state_b.params["embed"]["table"][idx].astype(jnp.float32)  # host:   B

    client, host, hist = train_ppat(x, y, PPATConfig(steps=150, seed=0))
    synth = client.generate(x)
    r = procrustes(synth, y)  # host-local MUSE refinement (DP post-processing)
    refined = synth @ r
    print(f"PPAT done: ε̂={hist['epsilon']:.2f} "
          f"(λ={0.05}, δ=1e-5; only G(X) and ∂L/∂G(X) crossed the boundary)")

    before = eval_loss(cfg, state_b, ds_b)
    new_table = state_b.params["embed"]["table"].at[idx].set(
        (0.5 * (y + refined)).astype(state_b.params["embed"]["table"].dtype)
    )
    params_new = dict(state_b.params, embed={"table": new_table})
    state_new = state_b._replace(params=params_new)
    # KGEmb-Update: brief local retraining after aggregation
    for b in make_batches(ds_b, batch=8, seq_len=64, steps=10, seed=42):
        state_new, _ = step_b(state_new, {k: jnp.asarray(v) for k, v in b.items()})
    after = eval_loss(cfg, state_new, ds_b)
    verdict = "kept" if after <= before else "backtracked (paper's rule)"
    print(f"host eval loss: {before:.3f} → {after:.3f} → {verdict}")


if __name__ == "__main__":
    main()
