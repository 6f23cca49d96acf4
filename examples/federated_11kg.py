"""The paper's full experiment, scaled: 11 LOD-statistics-matched KGs,
mixed base models (TransE/H/R/D as in Fig. 5), asynchronous federation with
handshake + backtrack + broadcast.

  PYTHONPATH=src python examples/federated_11kg.py [--ticks 4] [--scale 400]
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

from repro.core.federation import FederationScheduler
from repro.core.ppat import PPATConfig
from repro.kge.data import synthesize_universe
from repro.utils.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--ticks", type=int, default=3)
    ap.add_argument("--scale", type=float, default=400.0, help="1/scale of Tab. 2")
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--ppat-steps", type=int, default=100)
    args = ap.parse_args()

    t0 = time.time()
    kgs = synthesize_universe(seed=0, scale=1 / args.scale)
    print(f"generated {len(kgs)} KGs in {time.time()-t0:.1f}s "
          f"({sum(len(k.triples) for k in kgs.values())} triples total)")

    # Fig. 5: each KG randomly picks a translation-family base model
    families = {}
    fams = ["transe", "transh", "transr", "transd"]
    for i, name in enumerate(kgs):
        families[name] = fams[i % len(fams)]
    print("base models:", families)

    fed = FederationScheduler(
        kgs,
        families=families,
        dim=args.dim,
        ppat_cfg=PPATConfig(steps=args.ppat_steps, seed=0),
        local_epochs=100,
        update_epochs=30,
        seed=0,
    )
    init = fed.initial_training()
    print("\ninitial  :", {k: round(v, 3) for k, v in sorted(init.items())})
    final = fed.run(max_ticks=args.ticks)
    print("federated:", {k: round(v, 3) for k, v in sorted(final.items())})

    gains = {k: final[k] - init[k] for k in final}
    print("gains    :", {k: f"{v*100:+.1f}%" for k, v in sorted(gains.items())})
    n_acc = sum(1 for e in fed.events if e.kind == "ppat" and e.accepted)
    n_all = sum(1 for e in fed.events if e.kind == "ppat")
    print(f"\n{n_all} handshakes, {n_acc} accepted, "
          f"{len([e for e in fed.events if e.kind=='self-train'])} self-train rounds, "
          f"max ε̂ = {max(fed.epsilons):.2f}, total {time.time()-t0:.0f}s")


if __name__ == "__main__":
    main()
