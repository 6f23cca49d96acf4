"""The federate driver end to end on the CPU at a small size, with the chip
check steered from here: the result line, the reference comparison, the
bfloat16 control and the faults a training step can have."""
import json

import pytest

from bench import control
from bench import run as bench_run
from bench.tests import tiny


@pytest.fixture
def tree(tmp_path, monkeypatch):
    from repro.core import tick_engine

    root = tiny.make(tmp_path)
    tiny.steer(monkeypatch, root, tmp_path / "cache")
    # programs are cached per process: a fault below must trace afresh
    monkeypatch.setattr(tick_engine, "_ENTRY_PROGRAMS", {})
    return root


def _run():
    return json.loads(bench_run.execute(tiny.args("tiny.handshake"), cache=False))


def test_result_line_and_correct(tree):
    line = _run()
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert set(line["metrics"]) == {"setup_s", "tick_s"}
    assert set(line["checks"]) == {"failed_entries", "score_gap",
                                   "first_change_gap", "change_gap"}


def test_bf16_control_is_not_correct(tree):
    checks = control.checks("tiny.handshake", seed=7, seconds=0.5)
    assert not all(c.ok for c in checks)


def test_step_that_returns_its_state_unchanged_is_not_correct(tree, monkeypatch):
    from repro.core import tick_engine

    real = tick_engine.entry_graph

    def unchanged(inp, spec):
        out = real(inp, spec)
        out["params"] = dict(inp["params"])
        return out

    monkeypatch.setattr(tick_engine, "entry_graph", unchanged)
    line = _run()
    assert line["correct"] is False
    assert line["checks"]["first_change_gap"]["value"] > 0.5


def test_half_of_the_batch_left_out_is_not_correct(tree, monkeypatch):
    from repro.kge import engine

    real = engine.sparse_sgd_step

    def half(params, spec, pos, neg, lr, **kw):
        b = pos.shape[0] // 2
        return real(params, spec, pos[:b], neg[:b], lr, unique_e=3 * b, unique_r=b)

    monkeypatch.setattr(engine, "sparse_sgd_step", half)
    line = _run()
    assert line["correct"] is False


def test_altered_answer_is_not_correct(tree, monkeypatch):
    import jax.numpy as jnp

    from repro.core import tick_engine

    real = tick_engine.side_counts_graph
    monkeypatch.setattr(tick_engine, "side_counts_graph",
                        lambda *a, **kw: jnp.zeros_like(real(*a, **kw)))
    line = _run()
    assert line["correct"] is False and line["checks"]["score_gap"]["value"] > 0.5
