"""The trace reduction, on a trace recorded on a TPU v5e, and the table of
peaks."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import trace, work

PROBE = Path(__file__).resolve().parents[1] / "testdata" / "probe.xplane.pb"


def test_recorded_trace_busy_idle_and_kernel_time():
    # bench/testdata/probe.xplane.pb: three rounds of one fused-rank call
    # (8 queries, 4096 x 16 table) and one tanh-matmul fusion, recorded on
    # a v5e inside a bench.window span of 12,912,270 ns. Device clocks run
    # about 1 ms ahead of the host's in this trace, so the first round's
    # two programs fall before the window and the last two rounds' ops lie
    # inside it: 10 ops each round, none overlapping, 82,713 ns and
    # 82,664 ns of them.
    from jax.profiler import ProfileData

    s = trace.reduce_planes(ProfileData.from_file(str(PROBE)).planes)
    assert s["window_s"] == pytest.approx(12_912_270e-9, abs=1e-12)
    assert s["busy_s"] == pytest.approx((82_713 + 82_664) * 1e-9, abs=1e-12)
    assert s["idle_share"] == pytest.approx(1 - 165_377 / 12_912_270, abs=1e-12)

    def rank(n):
        return 'custom_call_target="tpu_custom_call"' in n and " = s32[" in n

    assert trace.kernel_seconds(s, rank) == pytest.approx((36_670 + 36_668) * 1e-9)
    assert trace.kernel_calls(s, rank) == 2
    assert trace.kernel_seconds(s, lambda n: "nothing" in n) is None
    top = dict(s["device_ops"])
    assert top["%fused_ranks_pallas_graph.1 custom-call"] == pytest.approx(73_338e-9)
    assert top["%fusion fusion"] == pytest.approx((42_032 + 42_033) * 1e-9)
    # the longest idle gaps fall inside the host's bench.other spans
    assert s["idle_gaps"][0][0] == "other"


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def test_union_of_overlapping_ops_and_gap_labels():
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.window", 100, 1000),
        _ev("bench.step", 100, 300),
        _ev("bench.drain", 600, 500),
    ])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[
            _ev("%a = f32[] fusion(x)", 50, 150),    # clipped to 100..200
            _ev("%b = f32[] fusion(x)", 150, 100),   # overlaps a: 100..250
            _ev("%c = s32[8,1] custom-call(x)", 700, 100),
        ]),
        NS(name="XLA Modules", events=[_ev("module", 0, 2000)]),
    ])
    other = NS(name="/device:TPU:0 SparseCore", lines=[])
    s = trace.reduce_planes([host, dev, other])
    assert s["window_s"] == pytest.approx(1000e-9)
    assert s["busy_s"] == pytest.approx(250e-9)   # 100..250 and 700..800
    assert s["idle_share"] == pytest.approx(0.75)
    assert s["ops"]["%a = f32[] fusion(x)"] == pytest.approx(100e-9)
    gaps = {round(d * 1e9): lab for lab, d in s["idle_gaps"]}
    # 250..700 overlaps step by 150 ns and drain by 100; 800..1100 is drain
    assert gaps == {450: "step", 300: "drain"}
    assert trace.short("%c = s32[8,1] custom-call(x)") == "%c custom-call"


def test_peaks_are_sourced_and_an_unknown_device_raises():
    import json

    table = json.loads(work.PEAKS.read_text())
    assert "TPU v5e" in table["source"]
    p = work.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks("TPU v99 imaginary")
