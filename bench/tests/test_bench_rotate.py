"""The ``serve_rotate`` driver end to end on the CPU at a small size, its
bfloat16 control, the RotatE metric readers on records of a traced chip
run, a top-100 cell (no rank requests) through the ``serve`` driver, and
the sweep that sets a serving cell's rate."""
import json
from pathlib import Path

import pytest

from bench import harness, sweep
from bench import run as bench_run
from bench.drivers import serve_rotate
from bench.tests import tiny

DATA = Path(__file__).resolve().parent / "data"

#: (cell, config, traffic, the cell whose metrics it reports): the small
#: counterparts of dbpedia.rotate.serve and dbpedia.serve.topk
CELLS = [
    ("tiny.rotate", "tiny-rotate", "tiny-open-loop-rotate", "dbpedia.rotate.serve"),
    ("tiny.topk", "tiny-serve", "tiny-topk100", "dbpedia.serve.topk"),
]


@pytest.fixture
def tree(tmp_path, monkeypatch):
    root = tiny.make(tmp_path)
    b = json.loads((root / "BENCHMARK.json").read_text())
    for cell, config, traffic, real in CELLS:
        for name, sub in ((config, "configs"), (traffic, "traffic")):
            (root / "bench" / sub / f"{name}.json").write_text(
                (DATA / f"{name}.json").read_text())
        b["workloads"].append({"name": cell, "config": config,
                               "traffic": traffic, "chips": 1, "why": "test cell"})
        for m in b["end_to_end"] + b["per_layer"]:
            if real in m.get("workloads", []):
                m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    tiny.steer(monkeypatch, root, tmp_path / "cache")
    return root


def _run(cell, **kw):
    return json.loads(bench_run.execute(tiny.args(cell, **kw), cache=False))


def test_rotate_cell_result_line_and_correct(tree):
    line = _run("tiny.rotate")
    assert line["correct"] is True
    assert line["attempted"] == 100 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "serve_p50_ms"}
    assert set(line["checks"]) == {"unanswered", "rank_miss", "topk_gap"}
    assert line["checks"]["rank_miss"]["value"] == 0.0


def test_rotate_records_name_every_batch(tree, monkeypatch):
    recs = []
    real = harness.read_metrics
    monkeypatch.setattr(harness, "read_metrics",
                        lambda entries, rec: recs.append(dict(rec)) or real(entries, rec))
    _run("tiny.rotate")
    (rec,) = recs
    kinds = rec["batch_rows_by_kind"]
    assert sum(n for _, n in kinds) == rec["served"] == 100
    assert len(kinds) == rec["batches"] and {k for k, _ in kinds} == {"rank", "topk"}
    assert rec["rank_queries"] == 50


def test_rotate_altered_answer_is_not_correct(tree, monkeypatch):
    from repro.serving import tier

    real = tier.side_counts_dispatch
    monkeypatch.setattr(tier, "side_counts_dispatch", lambda *a, **kw: real(*a, **kw) + 1)
    line = _run("tiny.rotate")
    assert line["correct"] is False and line["checks"]["rank_miss"]["value"] >= 1


def test_rotate_bf16_control_is_not_correct(tree):
    cell = harness.cell_of("tiny.rotate")
    checks = serve_rotate.control_checks(harness.config_of(cell),
                                         harness.traffic_of(cell), 11, 0.5)
    by = {c.name: c for c in checks}
    assert by["rank_miss"].value > 0 and by["topk_gap"].value > 1e-3


def test_rotate_driver_needs_the_cl1_kernel(tree, monkeypatch):
    """A program whose rank kernel has no cl1 path fails at set-up, before
    the graph is read or a table is made."""
    from repro.kernels import dispatch

    monkeypatch.setattr(dispatch, "RANK_KERNEL_MODES", ("l1", "l2", "dot"))
    with pytest.raises(harness.BenchError, match="cl1"):
        bench_run.execute(tiny.args("tiny.rotate"), cache=False)


def test_topk100_cell_through_serve_driver(tree):
    line = _run("tiny.topk")
    assert line["correct"] is True and line["failed"] == 0
    assert line["checks"]["rank_miss"]["value"] == 0.0


def test_rotate_readers_on_recorded_records():
    """The three readers on ``data/rotate-records.json``: a window's records
    with the trace reduced to the RotatE rank path's operations, named as
    the chip's trace names them, and the values each reader must give
    (the file's ``source`` says where both come from)."""
    rec = json.loads((DATA / "rotate-records.json").read_text())
    read = {m: harness.load_module(harness.BENCH / "metrics" / f"{m}.py").read
            for m in ("rank_roofline.rotate", "rank_layout_ms.rotate",
                      "serve_mfu.rotate")}
    got = {m: f(rec) for m, f in read.items()}
    assert got == pytest.approx(rec["expected"], rel=1e-9)
    assert all(0 < v < 100 for k, v in got.items() if k != "rank_layout_ms.rotate")
    # nothing to read: each reader leaves its metric out
    for f in read.values():
        assert f({"window_s": 1.0}) is None
    no_kernel = dict(rec, trace={"ops": {}, "calls": {}})
    assert read["rank_roofline.rotate"](no_kernel) is None
    assert read["rank_layout_ms.rotate"](no_kernel) is None


def test_sweep_offers_each_rate(tree, capsys, monkeypatch):
    from repro.utils import compile_cache

    monkeypatch.setattr(compile_cache, "use_compile_cache", lambda: None)
    assert sweep.main(["--workload", "tiny.rotate", "--seed", "3",
                       "--rates", "40,400", "--seconds", "0.25"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if x.startswith("{")]
    assert [x["rate"] for x in lines] == [40.0, 400.0]
    assert [x["requests"] for x in lines] == [10, 100]
    assert all(x["window_compiles"] == 0 for x in lines)
