"""The federate driver reads what the four-chip cell needs from its
configuration: four equal owners, a partner that rotates every tick, and
sharded placement, rehearsed on four forced CPU host devices."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def test_four_owner_sharded_cell_runs_and_is_correct(tmp_path):
    code = textwrap.dedent(f"""
        import json, sys
        sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / "src")!r}]
        import jax, pytest
        from pathlib import Path
        from bench import run
        from bench.tests import tiny
        assert len(jax.devices()) == 4
        mp = pytest.MonkeyPatch()
        root = tiny.make(Path({str(tmp_path)!r}))
        tiny.steer(mp, root, Path({str(tmp_path / "cache")!r}))
        dispatched = []
        from repro.core import tick_engine
        real = tick_engine.TickEngine.execute
        def spy(self, *a, **kw):
            out = real(self, *a, **kw)
            dispatched.append(dict(self.dispatched))
            return out
        mp.setattr(tick_engine.TickEngine, "execute", spy)
        line = run.execute(tiny.args("fede4.tiny"), cache=False)
        print("DISPATCHED", json.dumps(dispatched[-1]))
        print(line)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("REPRO_TICK_PLACEMENT", None)
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True, line["checks"]
    assert line["device"]["count"] == 4
    assert "tick_s" in line["metrics"]
    dispatched = json.loads(lines[-2].split(" ", 1)[1])
    # equal owners whose pairs share an entry signature run in shard_map
    # group programs; a pair whose virtual-extension sizes differ runs alone
    assert dispatched["group"] > 0
