"""The program's spans on the device clock (bench/spans.py), on the recorded
v5e probe, on a v5e trace of KGEServingTier batches and on synthetic planes."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from bench import spans, trace

DATA = Path(__file__).resolve().parents[1] / "testdata"
SERVE = ["repro.serve." + n for n in (
    "submit", "coalesce", "assemble", "put", "launch", "collect", "wait")]


def _planes(name):
    from jax.profiler import ProfileData

    return list(ProfileData.from_file(str(DATA / name)).planes)


@pytest.mark.parametrize("name", ["probe.xplane.pb", "serve_spans.xplane.pb"])
def test_recorded_trace_keeps_every_key_and_bounds_the_clock(name):
    planes = _planes(name)
    old = trace.reduce_planes(planes)
    new = spans.reduce_planes(planes)
    for k in old:
        if k != "idle_gaps":
            assert new[k] == old[k], k
    low, high = new["clock_offset_ms"]
    # the device's timestamps run behind the host's: here by 1.383-1.726 ms
    # (probe) and 1.332-1.437 ms (serve); other processes read 0.37-0.45 ms,
    # which is why the offset is bounded anew from each trace
    assert 1.2 <= low <= high <= 1.95
    assert high - low <= 0.6


def test_probe_has_no_program_spans():
    s = spans.reduce_planes(_planes("probe.xplane.pb"))
    assert s["spans"] == {} and s["idle_under"] == {}
    assert s["idle_gaps"][0][0] == "other"   # the bench.* rule still labels


def test_serve_trace_spans_and_gap_labels():
    # bench/testdata/serve_spans.xplane.pb: a KGEServingTier on a v5e
    # (TransE, 131,072 x 100 table, max_batch 8, one batch in flight) taking
    # six requests (rank 2, rank 3, top-k 1, rank 1, top-k 2, rank 2 rows)
    # inside a bench.window span, five batches, stepped in bench.step spans
    s = spans.reduce_planes(_planes("serve_spans.xplane.pb"))
    assert sorted(s["spans"]) == sorted(SERVE)
    assert s["spans"]["repro.serve.submit"][0] == 6
    for n in ("coalesce", "assemble", "put", "launch", "collect"):
        assert s["spans"]["repro.serve." + n][0] == 5
    assert all(sec > 0 for _, sec in s["spans"].values())
    labels = [lab for lab, _ in s["idle_gaps"]]
    assert any(lab.startswith("serve.") for lab in labels)
    assert set(labels) <= {n[len("repro."):] for n in SERVE} | {"step", "submit", "host"}
    # the shift moves busy time across the window's edges only
    assert s["idle_s"] == pytest.approx(s["window_s"] - s["busy_s"], abs=5e-3)
    assert 0 < sum(s["idle_under"].values()) <= s["idle_s"]


def _ev(name, start, dur, **stats):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur), stats=stats)


def _synthetic(*, linked=True, callback_at=4000):
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        _ev("bench.window", 0, 10_000),
        _ev("bench.step", 1000, 2000),
        _ev("repro.serve.put#seq=0#", 1000, 500),
        _ev("repro.serve.launch#seq=0,replica=0#", 1500, 500),
        _ev("bench.drain", 6000, 3000),
    ])])
    if linked:
        host.lines.append(NS(name="runtime", events=[
            _ev("DoEnqueueProgram", 1600, 50, run_id=7, device_ordinal=0),
            _ev("CompleteCallbacks", callback_at, 50, run_id=7, device_ordinal=0),
        ]))
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[_ev("jit_step", 600, 2000, run_id=7)]),
        NS(name="XLA Ops", events=[_ev("%a = f32[] fusion(x)", 600, 2000)]),
    ])
    return [host, dev]


def test_offset_shifts_device_time_under_the_spans():
    s = spans.reduce_planes(_synthetic())
    # enqueued at 1600, ran 600..2600 on the device clock, called back at
    # 4000: host = device + [1000, 1400] ns; shifted by 1200 it runs 1800..3800
    assert s["clock_offset_ms"] == pytest.approx([1000e-6, 1400e-6])
    assert s["idle_share"] == pytest.approx(0.8)      # unshifted, as before
    assert s["idle_s"] == pytest.approx(8000e-9)
    assert s["spans"] == {"repro.serve.put": [1, pytest.approx(500e-9)],
                          "repro.serve.launch": [1, pytest.approx(500e-9)]}
    assert s["idle_under"]["repro.serve.put"] == pytest.approx(500e-9)
    assert s["idle_under"]["repro.serve.launch"] == pytest.approx(300e-9)
    gaps = {round(d * 1e9): lab for lab, d in s["idle_gaps"]}
    # 3800..10000 overlaps only bench.drain; 0..1800 overlaps put most
    assert gaps == {6200: "drain", 1800: "serve.put"}


def test_no_links_reads_zero_offset_and_an_empty_interval_raises():
    s = spans.reduce_planes(_synthetic(linked=False))
    assert s["clock_offset_ms"] == [0.0, 0.0]
    gaps = {round(d * 1e9): lab for lab, d in s["idle_gaps"]}
    assert gaps == {600: "host", 7400: "drain"}
    assert s["idle_under"]["repro.serve.put"] == 0.0
    # called back at 3500: the enqueue bounds the offset below by 1000 ns,
    # the callback above by 900 ns; crossing by 100 ns is within CLOCK_SLACK
    s = spans.reduce_planes(_synthetic(callback_at=3500))
    assert s["clock_offset_ms"] == pytest.approx([1000e-6, 900e-6])
    # by more than CLOCK_SLACK: no offset fits
    with pytest.raises(ValueError, match="no clock offset"):
        spans.reduce_planes(_synthetic(callback_at=2600 + 1000 - spans.CLOCK_SLACK - 1))

