"""What every cell shares: the chip check, the compile clock, device facts,
the metric readers found by name, and the result line.

A driver (``bench/drivers/<name>.py``) builds its cell, warms it up, runs
the measured window and returns a ``Run``. The harness then reads peak
memory, lets the driver free the program's state and run its reference
comparison, reads each metric with its reader (``bench/metrics/<name>.py``),
and prints the result as the last line of standard output.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, bad cell, program failure)."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_of(name: str, bench: Optional[dict] = None) -> dict:
    bench = bench or manifest()
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload named {name!r} in BENCHMARK.json")


def config_of(cell: dict) -> dict:
    return load_json(BENCH / "configs" / f"{cell['config']}.json")


def traffic_of(cell: dict) -> dict:
    return load_json(BENCH / "traffic" / f"{cell['traffic']}.json")


def load_module(path: Path):
    """Import a benchmark file by path (metric names hold dots, so they are
    not importable as module names)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def require_chips(chips: int):
    """The devices of a cell that asks for ``chips`` TPU chips. Refuses any
    other platform, too few chips, and any ``REPRO_*`` dispatch override:
    the benchmark measures the program as it resolves itself on the chip."""
    overrides = sorted(k for k in os.environ if k.startswith("REPRO_"))
    if overrides:
        raise BenchError(f"refusing to run with dispatch overrides set: {overrides}")
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} TPU chips; JAX found {len(devs)}")
    return devs[:chips]


class Clock:
    """Counts XLA compiles and their seconds, persistent-cache reads (a
    program found in the cache is read, not compiled) and traces."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.traces = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += secs
            elif event == "/jax/core/compile/jaxpr_trace_duration":
                self.traces += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self):
        return self.compiles, self.cache_hits, self.traces

    def since(self, mark) -> dict:
        """What happened after ``mark``, under the window's record names."""
        c, h, t = mark
        return {"window_compiles": self.compiles - c,
                "window_cache_reads": self.cache_hits - h,
                "window_traces": self.traces - t}


@dataclass
class Check:
    """One number compared with its reference, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Run:
    """What a driver hands back after its window."""

    setup_s: float
    attempted: int
    failed: int
    #: everything the metric readers may read: window seconds, ticks,
    #: latencies, counters, logical work
    records: Dict = field(default_factory=dict)
    #: frees the program's state and returns the reference comparison
    compare: Callable[[], List[Check]] = lambda: []


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def device_block(devices) -> dict:
    d = devices[0]
    return {
        "platform": d.platform,
        "kind": d.device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak_bytes(devices),
    }


def metrics_for(cell: str, trace: bool, bench: dict) -> List[dict]:
    """The metric entries this cell reports: its end-to-end metrics when
    untraced, its per-layer metrics when traced. A per-layer metric without
    ``workloads`` is reported wherever the metric it moves is."""
    def applies(m):
        return "workloads" not in m or cell in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [
        m for m in bench["per_layer"]
        if (cell in m["workloads"] if "workloads" in m else m["moves"] in moved)
    ]


def read_metrics(entries: List[dict], records: dict) -> Dict[str, dict]:
    out = {}
    for m in entries:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        v = reader.read(records)
        if v is None:
            continue  # nothing to read in this run: the metric is left out
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def result_line(correct: bool, run: Run, metrics: dict, device: dict,
                checks: List[Check], breakdown: Optional[dict] = None) -> str:
    line = {
        "correct": correct,
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit} for c in checks}
    return json.dumps(line)


def nearest_rank(values, q: float) -> float | None:
    """The q-quantile of ``values`` by nearest rank (infinite values count,
    as the latency of a request that never came); None when it is one."""
    import math

    import numpy as np

    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return None
    x = float(v[max(0, math.ceil(q * v.size) - 1)])
    return x if math.isfinite(x) else None


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def now() -> float:
    return time.perf_counter()
