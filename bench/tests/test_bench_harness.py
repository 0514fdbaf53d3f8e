"""The harness finds cells, mixes, drivers and metrics by name, and refuses
to run without a chip."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax

from bench import compcache, harness, peaks

ROOT = Path(__file__).resolve().parents[2]


def test_new_cell_found_by_name(tmp_path, monkeypatch):
    """A config, a traffic mix with its driver, and a metric added as new
    files run through the harness with no edit to any file already there."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "configs" / "toy.json").write_text(json.dumps({"n": 3}))
    (tmp_path / "traffic" / "echo.json").write_text(json.dumps(
        {"driver": "echo_driver", "limits": {"gap": 0.5}}))
    (tmp_path / "traffic" / "echo_driver.py").write_text(textwrap.dedent('''
        def setup(run):
            return {"n": run.config["n"]}

        def window(run):
            with run.span("step"):
                pass
            return {"attempted": run.state["n"], "failed": 0,
                    "completed": run.state["n"]}

        def check(run):
            return {"gap": {"value": 0.0, "limit":
                            run.traffic["limits"]["gap"]}}
        '''))
    (tmp_path / "metrics" / "toy_count.py").write_text(
        "def read(run):\n    return run.window['completed'] * 2\n")
    (tmp_path / "metrics" / "toy_silent.py").write_text(
        "def read(run):\n    return None\n")
    spec = {"configs": [{"name": "toy", "file": "configs/toy.json"}],
            "workloads": [{"name": "toy.echo", "config": "toy",
                           "traffic": "echo", "chips": 1}],
            "end_to_end": [{"name": "toy_count", "unit": "1"},
                           {"name": "toy_silent", "unit": "1"}],
            "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(compcache, "enable", lambda: None)
    cell = harness.find_cell("toy.echo", tmp_path / "BENCHMARK.json",
                             tmp_path)
    run = harness.Run(cell=cell, seed=1, seconds=0.1, trace=False, t0=0.0,
                      devices=jax.devices()[:1])
    res = harness.execute(run, tmp_path)
    assert res["correct"] is True
    assert res["metrics"] == {"toy_count": {"value": 6.0, "unit": "1"}}
    assert list(res)[-1] == "checks"


def test_run_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "whatif.lanes_opt",
         "--seed", str(2**33), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "correct" not in p.stdout
    assert "no TPU" in p.stderr
