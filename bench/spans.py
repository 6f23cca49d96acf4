"""The program's own host spans on the device trace's clock.

``trace.reduce_planes`` reads device time against the host's
``bench.window`` span as the timestamps stand. The device's clock is not the
host's: on a v5e each program's events start and end up to a couple of
milliseconds before the host events that issue and complete it, by an
amount that differs from process to process. ``reduce_planes`` here keeps
every key of ``trace.reduce_planes`` as it computes it, and adds
the program's ``repro.*`` spans (``KGEServingTier``'s, for one) on the
device's clock:

* ``clock_offset_ms``: ``[low, high]`` bounds of the offset that puts a
  device timestamp on the host's clock (host = device + offset). A device
  program (an ``XLA Modules`` event) cannot start before the host's
  ``DoEnqueueProgram`` that enqueued it started, nor end after the host's
  ``CompleteCallbacks`` for it started; the trace links the three by
  ``run_id`` on one device ordinal. Each linked program bounds the offset,
  and the interval satisfies all of them. The bounds of programs a few
  hundred nanoseconds long can cross by some tens of microseconds, the
  timestamps' own noise: ``low`` then exceeds ``high``, and the reduction
  raises only where it does by more than ``CLOCK_SLACK``. A trace that
  links none reads ``[0, 0]``.
* device operations shifted by the interval's midpoint give the idle
  intervals inside the window, from which come
* ``spans``: ``{name: [count, seconds]}`` of each program span inside the
  window, its name stripped of the ``#key=value#`` metadata;
* ``idle_s``: device-idle seconds in the window on the device's clock,
  and ``idle_under``: ``{name: seconds}`` of them each span name overlaps
  (both the mean over the chips used);
* ``idle_gaps``: the longest idle gaps, each labelled by the ``repro.*``
  span that overlaps it most (``serve.launch``), else by the ``bench.*``
  span that does (``step``), else ``host``.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Tuple

from bench import trace

PROGRAM_PREFIX = "repro."
BENCH_PREFIX = "bench."
MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"
COMPLETE = "CompleteCallbacks"
#: how far the linked programs' bounds may cross (ns): on a v5e they crossed
#: by 0.038 ms in one trace of six, and were 0.03-0.08 ms apart in the rest
CLOCK_SLACK = 0.25e6


def _stats(ev) -> dict:
    return dict(getattr(ev, "stats", ()) or ())


def _span_name(name: str) -> str:
    """``repro.serve.put`` for ``repro.serve.put#seq=3#``."""
    return name.split("#", 1)[0]


def _device_planes(planes, chips: int):
    devs = [p for p in planes if p.name.startswith(trace.DEVICE_PREFIX)
            and p.name[len(trace.DEVICE_PREFIX):].isdigit()]
    devs.sort(key=lambda p: int(p.name[len(trace.DEVICE_PREFIX):]))
    return devs[:chips]


def links(planes, *, chips: int = 1) -> List[Tuple[float, float, float, float]]:
    """``(start, end, enqueued, completed)`` nanoseconds of each device
    program the trace links by ``run_id`` to its host ``DoEnqueueProgram``
    and ``CompleteCallbacks`` (the first on the device's clock, the other
    two on the host's)."""
    planes = list(planes)
    devs = _device_planes(planes, chips)
    ordinals = {int(p.name[len(trace.DEVICE_PREFIX):]) for p in devs}
    enqueued: Dict[Tuple[int, int], float] = {}
    completed: Dict[Tuple[int, int], float] = {}
    for p in planes:
        if p.name.startswith(trace.DEVICE_PREFIX):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name not in (ENQUEUE, COMPLETE):
                    continue
                st = _stats(ev)
                if "run_id" not in st:
                    continue
                key = (int(st.get("device_ordinal", 0)), int(st["run_id"]))
                if key[0] not in ordinals:
                    continue
                if ev.name == ENQUEUE:
                    enqueued[key] = min(enqueued.get(key, ev.start_ns), ev.start_ns)
                else:
                    completed[key] = max(completed.get(key, ev.start_ns), ev.start_ns)
    out = []
    for p in devs:
        ordinal = int(p.name[len(trace.DEVICE_PREFIX):])
        runs: Dict[int, List[float]] = {}
        for line in p.lines:
            if line.name != MODULES_LINE:
                continue
            for ev in line.events:
                st = _stats(ev)
                if "run_id" not in st:
                    continue
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                run = runs.setdefault(int(st["run_id"]), [s, e])
                run[0], run[1] = min(run[0], s), max(run[1], e)
        for run_id, (s, e) in runs.items():
            key = (ordinal, run_id)
            if key in enqueued and key in completed:
                out.append((s, e, enqueued[key], completed[key]))
    return out


def clock_offset(planes, *, chips: int = 1) -> Tuple[float, float]:
    """``(low, high)`` nanoseconds that bound host - device time over the
    linked programs; ``(0, 0)`` when the trace links none. ``low`` may pass
    ``high`` by up to ``CLOCK_SLACK``: the timestamps' own disagreement."""
    ls = links(planes, chips=chips)
    if not ls:
        return 0.0, 0.0
    low = max(q - s for s, _, q, _ in ls)
    high = min(c - e for _, e, _, c in ls)
    if low - high > CLOCK_SLACK:
        raise ValueError(
            f"no clock offset fits all {len(ls)} linked programs: "
            f"{low * 1e-6:.4f} ms > {high * 1e-6:.4f} ms"
        )
    return low, high


def _overlap(iv: List[Tuple[float, float]], starts: List[float],
             s: float, e: float) -> float:
    """Length of ``[s, e]`` inside the sorted, disjoint intervals ``iv``."""
    i = max(0, bisect.bisect_right(starts, s) - 1)
    got = 0.0
    while i < len(iv) and iv[i][0] < e:
        got += max(0.0, min(e, iv[i][1]) - max(s, iv[i][0]))
        i += 1
    return got


def reduce_planes(planes, *, chips: int = 1, top: int = 10) -> Dict:
    """``trace.reduce_planes``'s keys, with ``idle_gaps`` relabelled on the
    device's clock, and ``clock_offset_ms``, ``spans`` and ``idle_under``."""
    planes = list(planes)
    out = trace.reduce_planes(planes, chips=chips, top=top)
    low, high = clock_offset(planes, chips=chips)
    shift = (low + high) / 2
    devs = _device_planes(planes, chips)
    named: List[Tuple[str, float, float]] = []
    for p in planes:
        if p.name.startswith(trace.DEVICE_PREFIX):
            continue
        for line in p.lines:
            for ev in line.events:
                if ev.name.startswith((PROGRAM_PREFIX, BENCH_PREFIX)):
                    named.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    lo, hi = next((s, e) for n, s, e in named if n == trace.WINDOW_SPAN)
    program = [(_span_name(n), max(s, lo), min(e, hi)) for n, s, e in named
               if n.startswith(PROGRAM_PREFIX) and e > lo and s < hi]
    spans: Dict[str, List] = {}
    for n, s, e in program:
        c = spans.setdefault(n, [0, 0.0])
        c[0] += 1
        c[1] += (e - s) * 1e-9
    idle_under: Dict[str, float] = {n: 0.0 for n in spans}
    idle_ns = 0.0
    gaps: List[Tuple[float, float]] = []
    for p in devs:
        busy = trace._clip(trace._union([
            (ev.start_ns + shift, ev.start_ns + ev.duration_ns + shift)
            for ln in p.lines if ln.name == trace.OPS_LINE for ev in ln.events
        ]), lo, hi)
        edges = [lo] + [x for b in busy for x in b] + [hi]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        starts = [s for s, _ in idle]
        idle_ns += sum(e - s for s, e in idle)
        for n, s, e in program:
            idle_under[n] += _overlap(idle, starts, s, e) * 1e-9 / len(devs)
        gaps += idle

    def label(g):
        best, best_ov = None, 0.0
        for prefix in (PROGRAM_PREFIX, BENCH_PREFIX):
            for n, s, e in named:
                if n == trace.WINDOW_SPAN or not n.startswith(prefix):
                    continue
                ov = min(e, g[1]) - max(s, g[0])
                if ov > best_ov:
                    best, best_ov = _span_name(n)[len(prefix):], ov
            if best is not None:
                return best
        return "host"

    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    out["idle_gaps"] = [[label(g), (g[1] - g[0]) * 1e-9] for g in gaps[:top]]
    out["clock_offset_ms"] = [low * 1e-6, high * 1e-6]
    out["spans"] = spans
    out["idle_s"] = idle_ns * 1e-9 / len(devs)
    out["idle_under"] = idle_under
    return out

