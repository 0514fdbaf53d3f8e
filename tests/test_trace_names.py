"""The names the engine leaves in a profiler trace.

* **Named scopes** — the fused pricing programs (``run_fleet``'s fan-out
  core with generation, the checkpointed scenario DP core) carry
  ``gen_arrivals``, ``gen_rents``, ``policy_step`` and ``dp_fwd`` in their
  HLO's ``op_name`` metadata, which is where a profiler finds an op's
  stage.  The joint multi-service DP also names its N-to-joint reduction
  ``joint_reduce``, and ``services.JOINT_SCENARIO_TRACES`` counts the
  reduction's traces by ``(N, J)``.  Where a wide frontier's forward runs
  row-blocked (``kernels.hosting.dp_minplus_rows``), that path runs under
  ``dp_fwd_rows`` inside ``dp_fwd``, and ``offline_opt.DP_ROWS_TRACES``
  counts its traces by ``(K, chunk)``.
* **Host spans** — each ``LiveFleetScheduler.admit`` records exactly one
  ``repro.step.put``, ``repro.step.dispatch`` and ``repro.step.readback``
  span, in that order, each with the admitted slot's index.
"""
import glob
import importlib
import os
import re

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro.core import fleet as F
from repro.core import scenarios as S
from repro.core import services as SV
from repro.core.costs import HostingCosts, HostingGrid, ServiceSet
from repro.core.policies import AlphaRR, RetroRenting
from repro.serve.scheduler import LiveFleetScheduler

O = importlib.import_module("repro.core.policies.offline_opt")

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


def _joint_fleet(B: int, N: int, capacity: float, T_len: int):
    sets = [ServiceSet((HostingCosts.three_level(10.0, 0.4, 0.35),) * N,
                       capacity=capacity)] * B
    return SV.service_fleet(sets, T_len)


def _joint_scenario(B: int, seed: int):
    k_arr, k_rent = jax.random.split(jax.random.PRNGKey(seed))
    return S.combine(
        S.ge_arrivals(S.split_keys(k_arr, B), 0.3, 0.2, 2.0, 0.2, B),
        S.arma_rents(k_rent, 0.35, B))


def _joint_opt(sf, seed: int, chunk: int):
    return SV.offline_opt_services(sf, scenario=_joint_scenario(sf.B, seed),
                                   n_seeds=2, chunk_size=chunk,
                                   checkpointed=True, collect_schedule=False)


def test_joint_dp_names_its_reduction(monkeypatch):
    sf = _joint_fleet(B, 3, 1.0, T)
    text = _lowered_hlo(monkeypatch, "_compiled_dp_ckpt_scenario_core",
                        lambda: _joint_opt(sf, 3, CHUNK))
    # the per-service generator runs under a vmap over the services, which
    # wraps its scopes' names: ``vmap(gen_arrivals)``
    parts = {re.sub(r"^(?:vmap\()+(.*?)\)*$", r"\1", part)
             for p in re.findall(r'op_name="([^"]*)"', text)
             for part in p.split("/")}
    assert {"joint_reduce", "gen_arrivals", "gen_rents", "dp_fwd"} <= parts


def test_joint_scenario_traces_count_new_shapes():
    """One trace per new (N, J) program shape, none on a cache hit."""
    def traced(sf, seed, chunk):
        before = dict(SV.JOINT_SCENARIO_TRACES)
        _joint_opt(sf, seed, chunk)
        return {k: v - before.get(k, 0)
                for k, v in SV.JOINT_SCENARIO_TRACES.items()
                if v != before.get(k, 0)}

    # shapes no other test of the suite runs: 5 sites, 40 slots, chunk 8
    sf3, sf4 = _joint_fleet(5, 3, 1.0, 40), _joint_fleet(5, 4, 2.0, 40)
    assert traced(sf3, 1, 8) == {(3, 10): 1}
    assert traced(sf3, 2, 8) == {}                  # new keys, same program
    assert traced(sf4, 1, 8) == {(4, 50): 1}
    assert traced(sf4, 2, 8) == {}


def test_rows_path_names_its_kernel_inside_dp_fwd(monkeypatch):
    """With the row-blocked forward switched on for the CPU, the joint
    program's row-blocked ops sit under ``dp_fwd/dp_fwd_rows``."""
    monkeypatch.setattr(O, "dp_rows_platforms", lambda K: ("cpu",))
    factory = F._compiled_dp_ckpt_scenario_core
    jax.clear_caches()
    factory.cache_clear()
    try:
        sf = _joint_fleet(3, 4, 2.0, 24)
        text = _lowered_hlo(monkeypatch, "_compiled_dp_ckpt_scenario_core",
                            lambda: _joint_opt(sf, 5, 12))
    finally:
        # programs traced here take the switched dispatch: leave none
        jax.clear_caches()
        factory.cache_clear()
    paths = re.findall(r'op_name="([^"]*)"', text)
    assert any("/dp_fwd/dp_fwd_rows/" in p for p in paths)


def test_dp_rows_traces_count_wide_frontiers():
    """The row-blocked path is traced into each new J = 50 program, not
    again when the program is reused, and never into a J = 6 one."""
    def traced(sf, seed, chunk):
        before = dict(O.DP_ROWS_TRACES)
        _joint_opt(sf, seed, chunk)
        return {k: v - before.get(k, 0)
                for k, v in O.DP_ROWS_TRACES.items()
                if v != before.get(k, 0)}

    # shapes no other test of the suite runs: 6 sites, 36 slots, chunk 12
    sf2, sf4 = _joint_fleet(6, 2, 1.0, 36), _joint_fleet(6, 4, 2.0, 36)
    assert sf2.sets[0].J < O.DP_ROWS_MIN_K
    assert traced(sf2, 1, 12) == {}
    assert set(traced(sf4, 1, 12)) == {(50, 12)}
    assert traced(sf4, 2, 12) == {}                 # new keys, same program


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
