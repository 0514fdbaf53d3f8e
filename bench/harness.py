"""Run one benchmark cell once and print its result line.

Everything a cell is made of is found by name under ``bench/``:

* ``BENCHMARK.json`` (at the checkout root) names the cell's configuration,
  traffic mix and chips, and the metrics with the cells each applies to;
* ``bench/configs/<config>.json`` is the deployment, as it runs on the
  chip; its ``cpu_cut`` holds the keys that the CPU tests override to cut
  it to a CPU-sized scale, and nothing run on the chip reads it;
* ``bench/traffic/<traffic>.json`` is the traffic mix: its ``driver`` names
  ``bench/traffic/<driver>.py``, which builds the deployment (``setup``),
  drives the program for the window (``window``) and compares what the
  window produced with the plain reference (``check``);
* ``bench/metrics/<metric>.py`` reduces the run to one number (``read``),
  or to nothing where it finds nothing to read.

A later change adds a configuration, a cell, a mix or a metric by adding
files and entries, without editing a file that is here.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SPAN = "bench."


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import one file of the benchmark by its path."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names."""

    name: str
    chips: int
    config: dict
    traffic: dict
    driver: Any
    metrics: dict                  # {"end_to_end": [...], "per_layer": [...]}


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(name: str, spec_path: Path = ROOT / "BENCHMARK.json",
              bench_dir: Path = BENCH) -> Cell:
    spec = load_json(spec_path)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {spec_path.name}; known: "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(spec_path.parent / configs[w["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    driver = load_module(bench_dir / "traffic" / f"{traffic['driver']}.py",
                         traffic["driver"])
    metrics = {kind: [m for m in spec[kind] if applies(m, name)]
               for kind in ("end_to_end", "per_layer")}
    return Cell(name, int(w["chips"]), config, traffic, driver, metrics)


@dataclass
class Run:
    """What a traffic driver and the metric readers share in one run."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t0: float                              # process start, perf_counter
    devices: list = field(default_factory=list)
    peaks: Optional[dict] = None
    setup_s: Optional[float] = None
    window: dict = field(default_factory=dict)   # what the window recorded
    view: Any = None                              # tracefile.TraceView
    compiles: int = 0                             # backend compiles counted
    state: Any = None

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    def span(self, name: str, **args):
        """A host span in the profiler's trace (about a microsecond when no
        trace is being taken)."""
        import jax
        return jax.profiler.TraceAnnotation(SPAN + name, **args)


def chips_or_fail(n: int) -> list:
    """The first ``n`` TPU chips, or ``NoChip``: no fallback to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (default platform: "
                     f"{devs[0].platform})")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def count_compiles(run: Run):
    """Count XLA backend compiles while ``run.compiles`` is being watched."""
    import jax

    def on_event(event: str, duration: float, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            run.compiles += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)


def memory_peak(devices) -> Optional[int]:
    """Peak HBM of the fullest chip: the allocator's peak of live buffers
    plus its peak reservation, which holds the programs' temporaries."""
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(st["peak_bytes_in_use"]
                         + st.get("peak_bytes_reserved", 0))
    return max(peaks) if peaks else None


@contextlib.contextmanager
def profiled(run: Run):
    """Profile the window into a temporary directory and reduce it there."""
    if not run.trace:
        with run.span("window"):
            yield
        return
    import jax

    from bench import tracefile
    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        # XLA ops only: the same device events in half the collection time
        opts.advanced_configuration = {"tpu_trace_mode": "TRACE_ONLY_XLA"}
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            with run.span("window"):
                yield
        finally:
            jax.profiler.stop_trace()
        run.view = tracefile.load(tracefile.find_xplane(log_dir),
                                  len(run.devices))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def read_metrics(run: Run, kind: str, bench_dir: Path = BENCH) -> dict:
    out = {}
    for m in run.cell.metrics[kind]:
        reader = load_module(bench_dir / "metrics" / f"{m['name']}.py",
                             m["name"])
        value = reader.read(run)
        if value is None:
            continue
        value = float(value)
        if not math.isfinite(value):
            raise ValueError(f"metric {m['name']} read {value}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def execute(run: Run, bench_dir: Path = BENCH) -> dict:
    """Set up, measure, check; returns the result object."""
    import jax

    from bench import compcache, peaks

    drv = run.cell.driver
    compcache.enable()
    count_compiles(run)
    kind = run.devices[0].device_kind
    run.peaks = peaks.for_kind(kind)
    if run.trace:
        # a traced window is short: the trace of a long one takes minutes
        # to collect and read
        run.seconds = min(run.seconds, run.traffic["trace_seconds"])
    run.state = drv.setup(run)
    run.setup_s = time.perf_counter() - run.t0
    run.compiles = 0
    with profiled(run):
        run.window = drv.window(run)
    compiles = run.compiles
    peak = memory_peak(run.devices)
    metrics = read_metrics(run, "per_layer" if run.trace else "end_to_end",
                           bench_dir)
    checks = drv.check(run)          # frees the program's state first
    checks["compiles_in_window"] = {"value": compiles, "limit": 0}
    info = {"device_kind": kind, "chips": len(run.devices),
            "compiles_in_window": compiles,
            "completed": run.window["completed"],
            "peak_hbm_bytes": peak, "setup_s": run.setup_s}
    print(json.dumps(info), flush=True)
    device = {"platform": run.devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": all(c["value"] <= c["limit"]
                             for c in checks.values()),
              "attempted": run.window["attempted"],
              "failed": run.window["failed"],
              "metrics": metrics, "device": device}
    if run.trace:
        device["busy_s"] = run.view.busy_ns() / 1e9
        device["window_s"] = run.view.window_ns / 1e9
        result["breakdown"] = {"device_ops": run.view.top_ops(),
                               "idle_gaps": run.view.idle_gaps()}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return result


def parse(argv=None):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t0: Optional[float] = None) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    args = parse(argv)
    cell = find_cell(args.workload)
    try:
        devices = chips_or_fail(cell.chips)
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    run = Run(cell=cell, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), t0=t0, devices=devices)
    result = execute(run)
    print(json.dumps(result), flush=True)
    return 0
