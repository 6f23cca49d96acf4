"""Mesh-mapped FKGE: the paper's peer-to-peer topology on one (simulated) pod.

Two KG owners live on two mesh slices; the PPAT exchange runs as an SPMD
program where the ONLY cross-slice tensors are the generated embeddings and
their gradients (collective-permute = the paper's pipes). The entity tables
are sharded over the 'model' axis via the sharded KGE train step.

  PYTHONPATH=src python examples/distributed_fkge.py
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 " + os.environ.get("XLA_FLAGS", "")
)

import sys
import time

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributed import (
    init_distributed_ppat,
    make_party_mesh,
    make_sharded_kge_step,
    ppat_exchange_step,
)
from repro.core.ppat import PPATConfig
from repro.core.alignment import csls_retrieval_acc, procrustes
from repro.kge.data import corrupt_triples, synthesize_universe
from repro.kge.models import KGEModel, init_kge
from repro.utils.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    print(f"devices: {len(jax.devices())}")
    kgs = synthesize_universe(
        seed=0, scale=1 / 400,
        kg_stats=[("A", 10, 90000, 300000), ("B", 8, 70000, 240000)],
        alignments=[("A", "B", 30000)],
    )
    a, b = kgs["A"], kgs["B"]
    ia, ib = a.aligned_with(b)
    print(f"A: {a.num_entities} ents; B: {b.num_entities} ents; aligned: {len(ia)}")

    # ---- sharded local KGE training (entity tables over 'model') ----------
    mesh_kge = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
    dim = 32
    rng = np.random.default_rng(0)
    params = {}
    for name, kg in (("A", a), ("B", b)):
        # pad the entity table to a mesh-divisible row count (vocab-padding
        # pattern; padded rows never appear in triples)
        e_pad = -(-kg.num_entities // 8) * 8
        model = KGEModel("transe", e_pad, kg.num_relations, dim, margin=2.0)
        p = init_kge(jax.random.PRNGKey(hash(name) % 2**31), model)
        step = make_sharded_kge_step(mesh_kge, model, lr=0.3)
        t0 = time.time()
        for _ in range(300):
            batch = kg.train[rng.integers(0, len(kg.train), 128)]
            neg = corrupt_triples(rng, batch, kg.num_entities)
            p, loss = step(p, jnp.asarray(batch), jnp.asarray(neg))
        print(f"{name}: sharded KGE 300 steps, loss={float(loss):.3f} "
              f"({time.time()-t0:.1f}s)")
        params[name] = p

    # ---- PPAT over the party mesh (client slice ↔ host slice) -------------
    # pull aligned rows off the KGE mesh (the "export" the paper's owners do)
    x = jnp.asarray(np.asarray(params["A"]["ent"])[ia])
    y = jnp.asarray(np.asarray(params["B"]["ent"])[ib])
    cfg = PPATConfig(steps=120, seed=0)
    mesh = make_party_mesh(2)
    state = init_distributed_ppat(jax.random.PRNGKey(0), dim, cfg)
    step = ppat_exchange_step(mesh, cfg)
    key = jax.random.PRNGKey(1)
    t0 = time.time()
    for i in range(cfg.steps):
        xi = rng.integers(0, len(x), cfg.batch)
        yi = rng.integers(0, len(y), cfg.batch)
        xb = jnp.stack([x[xi], jnp.zeros((cfg.batch, dim))])  # party0 = client
        yb = jnp.stack([jnp.zeros((cfg.batch, dim)), y[yi]])  # party1 = host
        keys = jax.random.split(jax.random.fold_in(key, i), 2)
        state, metrics, (n0, n1) = step(state, xb, yb, keys)
    print(f"PPAT (SPMD, collective-permute exchange): {cfg.steps} rounds "
          f"in {time.time()-t0:.1f}s; host gen_loss={float(metrics['gen_loss'][1]):.3f}")

    synth = x @ state["w"]
    r = procrustes(synth, y)  # host-local refinement
    acc = csls_retrieval_acc(synth @ r, y)
    print(f"CSLS retrieval of refined DP embeddings vs host: {acc*100:.1f}%")
    txt = step.lower(state, xb, yb, keys).compile().as_text()
    n_cp = txt.count("collective-permute(") + txt.count("collective-permute-start(")
    print(f"collective-permutes in the lowered exchange program: {n_cp} "
          f"(the paper's pipe sends, §4.4: ≤0.845 Mb per batch)")


if __name__ == "__main__":
    main()
