"""A benchmark tree with small cells beside the real ones, for CPU tests.

``make(tmp)`` lays out ``tmp/BENCHMARK.json`` and ``tmp/bench/`` as the
harness expects them: the real configurations, traffic files, drivers and
metric readers, plus the small configurations and traffic files of
``bench/tests/data`` as cells of their own, added by files and manifest
entries only. The federation metrics, which no cell of the manifest
reports yet, come from ``data/federation-metrics.json``. ``steer(root, cache)`` points the harness at that tree and
replaces its chip check with the devices JAX has.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

from bench import harness

DATA = Path(__file__).resolve().parent / "data"

TINY = [
    # (cell, config, traffic, chips, end-to-end metrics it reports)
    ("tiny.handshake", "tiny-pair", "tiny-handshake", 1, ("tick_s",)),
    ("tiny.serve", "tiny-serve", "tiny-open-loop", 1, ("serve_p50_ms",)),
    ("fede4.tiny", "fede4-tiny", "tiny-handshake", 4, ("tick_s",)),
]


def make(tmp: Path) -> Path:
    root = Path(tmp) / "root"
    bench = root / "bench"
    for sub in ("configs", "traffic", "drivers", "metrics"):
        shutil.copytree(harness.BENCH / sub, bench / sub)
    manifest = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    federation = json.loads((DATA / "federation-metrics.json").read_text())
    for part in ("end_to_end", "per_layer"):
        have = {m["name"] for m in manifest[part]}
        manifest[part] += [m for m in federation[part] if m["name"] not in have]
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for cell, config, traffic, chips, metrics in TINY:
        shutil.copy(DATA / f"{config}.json", bench / "configs" / f"{config}.json")
        shutil.copy(DATA / f"{traffic}.json", bench / "traffic" / f"{traffic}.json")
        manifest["workloads"].append({
            "name": cell, "config": config, "traffic": traffic, "chips": chips,
            "why": "test cell",
        })
        for metric in metrics:
            e2e[metric]["workloads"].append(cell)
        for m in manifest["per_layer"]:
            if m["moves"] in metrics:
                m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest, indent=1))
    return root


def steer(monkeypatch, root: Path, cache: Path) -> None:
    import jax

    from bench.gen import universe

    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "BENCH", root / "bench")
    monkeypatch.setattr(universe, "CACHE_DIR", cache)
    monkeypatch.setattr(harness, "require_chips", lambda n: jax.devices()[:n])


def args(cell: str, seed: int = 7, seconds: float = 0.5, trace: int = 0):
    from types import SimpleNamespace

    return SimpleNamespace(workload=cell, seed=seed, seconds=seconds, trace=trace)
