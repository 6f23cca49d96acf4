"""BENCHMARK.json keeps to its format, every name it holds is a file of its
own, and a cell and a per-layer metric are added by adding files."""
import json
import re

from bench import harness
from bench.tests import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _line_ok(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_manifest_format_and_files():
    b = harness.manifest()
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert b["command"] == ["python3", "bench/run.py"] and b["paths"] == ["bench"]
    assert 1 <= b["run_seconds"] <= 51
    cfgs = {c["name"] for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line_ok(c["source"]) and _line_ok(c["why"])
        assert (harness.ROOT / c["file"]).exists() and c["file"].startswith("bench/")
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        cfg = harness.load_json(harness.ROOT / c["file"])
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
    used = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and _line_ok(w["why"])
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        used.add(w["config"])
        traffic = harness.traffic_of(w)
        assert (harness.BENCH / "drivers" / f"{traffic['driver']}.py").exists()
    assert used == cfgs
    cells = {w["name"] for w in b["workloads"]}
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert m["moves"] in {e["name"] for e in b["end_to_end"]} and _line_ok(m["layer"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
        assert (harness.BENCH / "metrics" / f"{m['name']}.py").exists()
    for cell in cells:
        e2e = {m["name"] for m in harness.metrics_for(cell, False, b)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metrics_for(cell, True, b)
    assert len(json.dumps(b)) < 64 * 1024


def test_a_cell_and_a_per_layer_metric_are_added_by_files(tmp_path, monkeypatch):
    root = tiny.make(tmp_path)                 # cells added as files + entries
    (root / "bench" / "metrics" / "ticks_per_window.tick.py").write_text(
        "def read(rec):\n    return rec.get('ticks')\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["per_layer"].append({
        "name": "ticks_per_window.tick", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "whole tick", "moves": "tick_s",
        "workloads": ["tiny.handshake"],
    })
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    monkeypatch.setattr(harness, "ROOT", root)
    monkeypatch.setattr(harness, "BENCH", root / "bench")
    b = harness.manifest()
    assert harness.cell_of("tiny.serve", b)["traffic"] == "tiny-open-loop"
    entries = harness.metrics_for("tiny.handshake", True, b)
    assert "ticks_per_window.tick" in [m["name"] for m in entries]
    got = harness.read_metrics(entries, {"ticks": 3, "window_s": 6.0})
    assert got["ticks_per_window.tick"] == {"value": 3.0, "unit": "count"}
    # a reader with nothing to read leaves its metric out
    assert "idle_share.tick" not in got
