"""The serve driver end to end on the CPU at a small size, with the chip
check steered from here: the result line, the reference comparison, the
bfloat16 control and the faults a served answer can have."""
import json

import pytest

from bench import control, harness
from bench import run as bench_run
from bench.tests import tiny

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.fixture
def tree(tmp_path, monkeypatch):
    root = tiny.make(tmp_path)
    tiny.steer(monkeypatch, root, tmp_path / "cache")
    return root


def _run(cell="tiny.serve", **kw):
    return json.loads(bench_run.execute(tiny.args(cell, **kw), cache=False))


def test_result_line_and_correct(tree):
    line = _run()
    assert list(line) == KEYS
    assert line["correct"] is True
    assert line["attempted"] == 100 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "serve_p50_ms"}
    assert all(m["unit"] == "ms" or m["unit"] == "s" for m in line["metrics"].values())
    assert line["device"]["count"] == 1
    assert set(line["checks"]) == {"unanswered", "rank_miss", "topk_gap"}


def test_bf16_control_is_not_correct(tree):
    checks = control.checks("tiny.serve", seed=11, seconds=0.5)
    assert not all(c.ok for c in checks)
    by = {c.name: c for c in checks}
    assert by["rank_miss"].value > 0 and by["topk_gap"].value > 1e-3


def _burst(tree):
    # a burst fills batches, so a fault that drops rows of a batch shows
    path = tree / "bench" / "traffic" / "tiny-open-loop.json"
    t = json.loads(path.read_text())
    path.write_text(json.dumps(dict(t, rate=4000)))


def test_half_of_each_batch_left_out_is_not_correct(tree, monkeypatch):
    from repro.serving import tier

    real = tier.side_counts_dispatch

    def half(*a, **kw):
        c = real(*a, **kw)
        return c.at[1::2].set(0)

    monkeypatch.setattr(tier, "side_counts_dispatch", half)
    _burst(tree)
    line = _run(seconds=0.25)
    assert line["correct"] is False and line["checks"]["rank_miss"]["value"] > 0


def test_altered_answer_is_not_correct(tree, monkeypatch):
    from repro.serving import tier

    real = tier.side_counts_dispatch
    monkeypatch.setattr(tier, "side_counts_dispatch", lambda *a, **kw: real(*a, **kw) + 1)
    line = _run()
    assert line["correct"] is False and line["checks"]["rank_miss"]["value"] >= 1


def test_work_records_count_real_queries_whatever_the_block_size(tree, monkeypatch):
    recs = []
    real = harness.read_metrics

    def keep(entries, rec):
        recs.append(dict(rec))
        return real(entries, rec)

    monkeypatch.setattr(harness, "read_metrics", keep)
    _run()
    path = tree / "bench" / "configs" / "tiny-serve.json"
    cfg = json.loads(path.read_text())
    cfg["tier"]["block_e"] = 256
    path.write_text(json.dumps(cfg))
    _run()
    a, b = recs
    for k in ("rank_queries", "rank_filter_ids", "request_work"):
        assert a[k] == b[k]
    assert a["rank_queries"] == 50           # half of 100 requests, unpadded
