"""Quickstart: federate two knowledge graphs with FKGE in ~a minute on CPU.

Builds two synthetic KGs sharing aligned entities, trains each locally
(TransE), runs one PPAT federation round in each direction, and prints the
triple-classification scores before/after plus the DP budget ε̂.

  PYTHONPATH=src python examples/quickstart.py
"""
import sys

sys.path.insert(0, "src")

from repro.core.federation import FederationScheduler
from repro.core.ppat import PPATConfig
from repro.kge.data import synthesize_universe
from repro.utils.compile_cache import use_compile_cache


def main():
    use_compile_cache()
    kgs = synthesize_universe(
        seed=0,
        scale=1 / 400,
        kg_stats=[("Books", 12, 100000, 340000), ("Movies", 10, 80000, 270000)],
        alignments=[("Books", "Movies", 30000)],
    )
    for name, kg in kgs.items():
        print(f"{name}: {kg.num_entities} entities, {len(kg.triples)} triples")

    fed = FederationScheduler(
        kgs,
        dim=32,
        ppat_cfg=PPATConfig(steps=150, seed=0),
        local_epochs=150,
        update_epochs=40,
        seed=0,
    )
    init = fed.initial_training()
    print("\nafter local training :", {k: round(v, 3) for k, v in init.items()})

    final = fed.run(max_ticks=3)
    print("after federation     :", {k: round(v, 3) for k, v in final.items()})

    for ev in fed.events:
        if ev.kind == "ppat":
            arrow = "✓ kept" if ev.accepted else "✗ backtracked"
            print(
                f"  PPAT({ev.client}→{ev.host}): {ev.score_before:.3f} → "
                f"{ev.score_after:.3f} {arrow}  (ε̂={ev.epsilon:.1f})"
            )
    print(f"\nprivacy: per-handshake ε̂ from the moments accountant above; "
          f"paper setting λ={fed.ppat_cfg.lam}, δ={fed.ppat_cfg.delta}")


if __name__ == "__main__":
    main()
