"""The names the engine leaves in a profiler trace.

* **Named scopes** — the fused pricing programs (``run_fleet``'s fan-out
  core with generation, the checkpointed scenario DP core) carry
  ``gen_arrivals``, ``gen_rents``, ``policy_step`` and ``dp_fwd`` in their
  HLO's ``op_name`` metadata, which is where a profiler finds an op's
  stage.
* **Host spans** — each ``LiveFleetScheduler.admit`` records exactly one
  ``repro.step.put``, ``repro.step.dispatch`` and ``repro.step.readback``
  span, in that order, each with the admitted slot's index.
"""
import glob
import os
import re

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.core import fleet as F
from repro.core import scenarios as S
from repro.core.costs import HostingCosts, HostingGrid
from repro.core.policies import AlphaRR, RetroRenting
from repro.serve.scheduler import LiveFleetScheduler

B, T, CHUNK = 4, 64, 16
SCOPES = ("gen_arrivals", "gen_rents", "policy_step", "dp_fwd")
COSTS = [HostingCosts.three_level(10.0, 0.4, 0.35)] * B


def _scenario():
    key = jax.random.PRNGKey(3)
    k_arr, k_rent = jax.random.split(key)
    return S.combine(
        S.ge_arrivals(S.split_keys(k_arr, B), 0.3, 0.2, 2.0, 0.2, B),
        S.arma_rents(k_rent, 0.35, B))


def _lowered_hlo(monkeypatch, factory: str, run) -> str:
    """HLO, with its metadata, of the program ``factory`` builds, as
    ``run`` calls it.  Read from the lowering, not from a compiled
    executable, which a persistent compile cache keyed without metadata
    may have kept from other code."""
    texts = []
    build = getattr(F, factory)

    def spy(*a, **kw):
        fn = build(*a, **kw)

        def call(*args):
            texts.append(fn.lower(*args).as_text(dialect="hlo",
                                                 debug_info=True))
            return fn(*args)

        return call

    monkeypatch.setattr(F, factory, spy)
    run()
    assert len(texts) == 1
    return texts[0]


def _scopes(text: str) -> set:
    """The four stage names found in the ops' ``op_name`` paths."""
    paths = re.findall(r'op_name="([^"]*)"', text)
    return {s for s in SCOPES if any(s in p.split("/") for p in paths)}


def test_pricing_programs_name_their_stages(monkeypatch):
    fleet = F.FleetBatch.for_scenario(HostingGrid.from_costs(COSTS), T)
    lanes = [AlphaRR.fleet_lane(fleet), RetroRenting.fleet_lane(fleet)]
    lanes_text = _lowered_hlo(
        monkeypatch, "_compiled_fanout_scenario_core",
        lambda: F.run_fleet(lanes, fleet, scenario=_scenario(), n_seeds=2,
                            chunk_size=CHUNK, collect_trace=False))
    assert _scopes(lanes_text) == {"gen_arrivals", "gen_rents",
                                   "policy_step"}
    opt_text = _lowered_hlo(
        monkeypatch, "_compiled_dp_ckpt_scenario_core",
        lambda: F.offline_opt_fleet(fleet, scenario=_scenario(), n_seeds=2,
                                    chunk_size=CHUNK, checkpointed=True,
                                    collect_schedule=False))
    assert _scopes(opt_text) == {"gen_arrivals", "gen_rents", "dp_fwd"}


def _host_events(log_dir: str):
    """``(name, start_ns, end_ns, stats)`` of the host spans of the one
    trace under ``log_dir`` whose names start with ``repro.`` or
    ``test.``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("repro.", "test.")):
                    out.append((ev.name, ev.start_ns, ev.end_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_admit_spans_put_dispatch_readback(tmp_path):
    sched = LiveFleetScheduler(COSTS)
    rng = np.random.default_rng(0)
    x = rng.poisson(1.0, (4, B)).astype(np.int32)
    c = rng.uniform(0.2, 0.6, (4, B)).astype(np.float32)
    sched.admit(x[0], c[0])                      # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for t in range(1, 4):
            with jax.profiler.TraceAnnotation("test.admit", slot=t):
                sched.admit(x[t], c[t])
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    admits = [e for e in events if e[0] == "test.admit"]
    assert [e[3]["slot"] for e in admits] == [1, 2, 3]
    for _, start, end, stats in admits:
        inside = [e for e in events
                  if e[0].startswith("repro.") and start <= e[1] < end]
        assert [e[0] for e in inside] == ["repro.step.put",
                                          "repro.step.dispatch",
                                          "repro.step.readback"]
        assert all(e[3]["slot"] == stats["slot"] for e in inside)
        assert all(e[2] <= end for e in inside)
        assert all(a[2] <= b[1] for a, b in zip(inside, inside[1:]))
