"""Whole runs of the cells at a CPU-sized scale, the chip check skipped:
sound runs come out correct, and each fault planted under the timed path
comes out not correct."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import compcache, harness, peaks

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def tiny_spec(dest: Path, source: Path = ROOT / "BENCHMARK.json") -> Path:
    """``source`` with every configuration cut to a CPU-sized scale: each
    configuration's file states its own cut under ``cpu_cut``."""
    spec = json.loads(source.read_text())
    (dest / "configs").mkdir(parents=True, exist_ok=True)
    for c in spec["configs"]:
        path = source.parent / c["file"]
        cfg = json.loads(path.read_text())
        if "cpu_cut" not in cfg:
            raise ValueError(f"{path} states no cpu_cut: add the keys "
                             "that cut this configuration to a CPU-sized "
                             "scale")
        cfg.update(cfg["cpu_cut"])
        c["file"] = f"configs/{c['name']}.json"
        (dest / c["file"]).write_text(json.dumps(cfg))
    (dest / "BENCHMARK.json").write_text(json.dumps(spec))
    return dest / "BENCHMARK.json"


def run_cell(spec_path, name, n_devices=1, seconds=0.5):
    cell = harness.find_cell(name, spec_path)
    run = harness.Run(cell=cell, seed=2**33 + 3, seconds=seconds,
                      trace=False, t0=time.perf_counter(),
                      devices=jax.devices()[:n_devices])
    return harness.execute(run)


def _synthetic_spec(root: Path, cfg: dict) -> Path:
    """A spec with one configuration under a name no real one has."""
    (root / "configs").mkdir(parents=True)
    (root / "configs" / "probe_fleet.json").write_text(json.dumps(cfg))
    spec = {"configs": [{"name": "probe_fleet",
                         "file": "configs/probe_fleet.json"}],
            "workloads": [], "end_to_end": [], "per_layer": []}
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root / "BENCHMARK.json"


def test_tiny_spec_applies_each_files_cpu_cut(tmp_path):
    src = _synthetic_spec(tmp_path / "src", {
        "sites": 4096, "seeds": 3, "horizon_slots": 32768,
        "cpu_cut": {"sites": 16, "horizon_slots": 256}})
    out = tiny_spec(tmp_path / "out", src)
    spec = json.loads(out.read_text())
    (c,) = spec["configs"]
    cfg = json.loads((out.parent / c["file"]).read_text())
    assert (cfg["sites"], cfg["seeds"], cfg["horizon_slots"]) == (16, 3, 256)


def test_tiny_spec_names_the_file_without_cpu_cut(tmp_path):
    src = _synthetic_spec(tmp_path / "src", {"sites": 4096})
    with pytest.raises(ValueError, match="probe_fleet.json.*cpu_cut"):
        tiny_spec(tmp_path / "out", src)


@pytest.fixture
def spec(tmp_path, monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(compcache, "enable", lambda: None)
    return tiny_spec(tmp_path)


LIVE, PRICING = "metro_live.closed_loop", "whatif.lanes_opt"


def _stale_step(monkeypatch):
    """A policy step that returns its state unchanged."""
    from repro.core.policies import alpha_rr
    monkeypatch.setattr(alpha_rr, "alpha_rr_step",
                        lambda params, state, obs: state)


def _half_batch(monkeypatch, cell):
    """Half of the batch left out, the mean of the rest in its place."""
    if cell == LIVE:
        from repro.serve.scheduler import LiveFleetScheduler
        admit = LiveFleetScheduler.admit

        def half(self, x, c):
            x, c = np.array(x), np.array(c)
            h = len(x) // 2
            x[h:] = np.rint(x[:h].mean())
            c[h:] = c[:h].mean()
            return admit(self, x, c)
        monkeypatch.setattr(LiveFleetScheduler, "admit", half)
        return

    def mean_rows(a, axis_rows):
        a = np.array(a)
        h = a.shape[-1] // 2
        a[..., h:] = a[..., :h].mean(axis=-1, keepdims=True)
        return a
    _wrap_results(monkeypatch, mean_rows)


def _wrap_results(monkeypatch, fix):
    """Apply ``fix`` to the row axis of every cost the pricing calls
    return (per lane for ``run_fleet``)."""
    from repro.core import fleet as F
    run_fleet, opt = F.run_fleet, F.offline_opt_fleet

    def run_fleet2(*a, **k):
        res = run_fleet(*a, **k)
        tot = fix(res.policy_view(res.total), None).reshape(-1)
        return dataclasses.replace(res, total=tot)

    def opt2(*a, **k):
        res = opt(*a, **k)
        return dataclasses.replace(res, cost=fix(res.cost, None))
    monkeypatch.setattr(F, "run_fleet", run_fleet2)
    monkeypatch.setattr(F, "offline_opt_fleet", opt2)


def _altered(monkeypatch, cell):
    """An answer altered where it is produced: one site's decision each
    slot, or each row's costs handed its neighbour's."""
    if cell == LIVE:
        from repro.serve.scheduler import LiveFleetScheduler
        admit = LiveFleetScheduler.admit

        def flip(self, x, c):
            r = np.array(admit(self, x, c))
            r[0] = (r[0] + 1) % 3
            return r
        monkeypatch.setattr(LiveFleetScheduler, "admit", flip)
        return

    def neighbours(a, _):
        return np.roll(np.asarray(a), 1, axis=-1)
    _wrap_results(monkeypatch, neighbours)


@pytest.mark.parametrize("cell", [LIVE, PRICING, "whatif.lanes_only"])
def test_sound_run_is_correct(spec, cell):
    res = run_cell(spec, cell)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("fault", ["stale_step", "half_batch", "altered"])
@pytest.mark.parametrize("cell", [LIVE, PRICING])
def test_planted_fault_is_not_correct(spec, monkeypatch, cell, fault):
    if fault == "stale_step":
        _stale_step(monkeypatch)
    elif fault == "half_batch":
        _half_batch(monkeypatch, cell)
    else:
        _altered(monkeypatch, cell)
    res = run_cell(spec, cell)
    assert res["correct"] is False, res["checks"]


FOUR_DEVICES = textwrap.dedent('''
    import dataclasses, json, sys
    from pathlib import Path
    import numpy as np
    sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src"]
    import jax
    from bench import compcache, peaks
    from bench.tests import test_bench_faults as T
    peaks.PEAKS[jax.devices()[0].device_kind] = peaks.PEAKS["TPU v5 lite"]
    compcache.enable = lambda: None
    spec = T.tiny_spec(Path(sys.argv[2]))
    cell = sys.argv[3]
    sound = T.run_cell(spec, cell, n_devices=4)["correct"]
    from repro.core import fleet as F
    run_fleet, opt = F.run_fleet, F.offline_opt_fleet

    def first_shard(a):
        a = np.array(a)
        q = a.shape[-1] // 4
        return np.concatenate([a[..., :q]] * 4, axis=-1)

    def run_fleet2(*a, **k):
        res = run_fleet(*a, **k)
        tot = first_shard(res.policy_view(res.total)).reshape(-1)
        return dataclasses.replace(res, total=tot)

    def opt2(*a, **k):
        res = opt(*a, **k)
        return dataclasses.replace(res, cost=first_shard(res.cost))
    F.run_fleet, F.offline_opt_fleet = run_fleet2, opt2
    broken = T.run_cell(spec, cell, n_devices=4)["correct"]
    print(json.dumps({"sound": sound, "broken": broken}))
    ''')


@pytest.mark.parametrize("cell", [PRICING, "whatif.lanes_only"])
def test_exchange_between_chips_left_out(tmp_path, cell):
    """A pricing cell on a fleet mesh of four host devices, rows checked
    from every shard: sound is correct; results read from the first chip's
    shard only are not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", FOUR_DEVICES, str(ROOT),
                        str(tmp_path), cell], env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert json.loads(p.stdout.strip().splitlines()[-1]) == {
        "sound": True, "broken": False}
