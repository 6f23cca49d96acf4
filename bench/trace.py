"""Reduce a profiler trace of the measured window to device numbers.

The window is traced with ``jax.profiler.trace`` (``capture``). The
benchmark marks the window and its own host calls with
``jax.profiler.TraceAnnotation`` spans named ``bench.*``. ``reduce`` reads
the ``.xplane.pb`` file with ``jax.profiler.ProfileData``:

* device planes are the planes named ``/device:TPU:<n>``; on each, the
  operations are the events of its ``XLA Ops`` line;
* busy time is the union of those events' intervals inside the window,
  averaged over the chips used; idle share is 1 - busy / window;
* per-operation time is the sum of an operation's event durations, keyed by
  the event's name, which is the operation's HLO text (a Pallas kernel is a
  ``custom-call`` with ``custom_call_target="tpu_custom_call"``, named after
  the jitted function that holds it); the breakdown shortens each to its
  name and opcode (``%fusion.13 fusion``);
* each idle gap is labelled by the ``bench.*`` host span that overlaps it
  most (``host`` when none does).
"""
from __future__ import annotations

import contextlib
import glob
import re
import shutil
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


@contextlib.contextmanager
def capture(trace_dir: Optional[Path]):
    """Trace the enclosed window into ``trace_dir`` (no-op when None)."""
    if trace_dir is None:
        yield
        return
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    with jax.profiler.trace(str(trace_dir)):
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield


def span(name: str, on: bool):
    """A host span ``bench.<name>`` in traced runs; nothing otherwise."""
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation("bench." + name)


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return Path(found[-1])


_OPCODE = re.compile(r"(%[\w.\-]+) = .*? ([a-z][a-z0-9\-]*)\(")


def short(name: str) -> str:
    """``%fusion.13 fusion`` for an HLO text; other names unchanged."""
    m = _OPCODE.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name.split(" = ")[0]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def reduce_planes(planes, *, chips: int = 1, top: int = 10) -> Dict:
    """``planes``: objects with ``name`` and ``lines`` (each with ``name``
    and ``events`` carrying ``name``, ``start_ns``, ``duration_ns``), as
    ``ProfileData`` gives them. Returns window_s, busy_s (mean over the
    first ``chips`` device planes), idle_share, ops {name: seconds summed
    over chips}, calls {name: events}, and the ``top`` device ops and idle gaps."""
    host_spans: List[Tuple[str, float, float]] = []
    devices = []
    for p in planes:
        if p.name.startswith(DEVICE_PREFIX) and p.name[len(DEVICE_PREFIX):].isdigit():
            devices.append(p)
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    host_spans.append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    )
    devices.sort(key=lambda p: int(p.name[len(DEVICE_PREFIX):]))
    devices = devices[:chips]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    windows = [(s, e) for n, s, e in host_spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    lo, hi = windows[0]
    ops: Dict[str, float] = {}
    calls: Dict[str, int] = {}
    busy_ns = 0.0
    gaps: List[Tuple[float, float]] = []
    for p in devices:
        lines = [ln for ln in p.lines if ln.name == OPS_LINE]
        iv = []
        for ln in lines:
            for ev in ln.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= lo or s >= hi:
                    continue
                ops[ev.name] = ops.get(ev.name, 0.0) + (min(e, hi) - max(s, lo)) * 1e-9
                calls[ev.name] = calls.get(ev.name, 0) + 1
                iv.append((s, e))
        busy = _clip(_union(iv), lo, hi)
        busy_ns += sum(e - s for s, e in busy)
        edges = [lo] + [x for b in busy for x in b] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    window_s = (hi - lo) * 1e-9
    busy_s = busy_ns * 1e-9 / len(devices)

    def label(g):
        best, best_ov = "host", 0.0
        for n, s, e in host_spans:
            if n == WINDOW_SPAN:
                continue
            ov = min(e, g[1]) - max(s, g[0])
            if ov > best_ov:
                best, best_ov = n[len("bench."):], ov
        return best

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    by_short: Dict[str, float] = {}
    for n, sec in ops.items():
        by_short[short(n)] = by_short.get(short(n), 0.0) + sec
    top_ops = sorted(by_short.items(), key=lambda kv: kv[1], reverse=True)[:top]
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "ops": ops,
        "calls": calls,
        "device_ops": [[n, s] for n, s in top_ops],
        "idle_gaps": [[label(g), (g[1] - g[0]) * 1e-9] for g in gaps[:top]],
    }


def reduce(trace_dir: Path, *, chips: int = 1) -> Dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(find_xplane(trace_dir)))
    return reduce_planes(pd.planes, chips=chips)


def kernel_seconds(summary: Dict, match: Callable[[str], bool]) -> Optional[float]:
    """Summed device seconds of the operations whose HLO text ``match``es
    (None when the window ran none)."""
    hits = [s for n, s in summary["ops"].items() if match(n)]
    return sum(hits) if hits else None


def kernel_calls(summary: Dict, match: Callable[[str], bool]) -> int:
    """Events, over the chips used, of the operations that ``match``."""
    return sum(c for n, c in summary["calls"].items() if match(n))
