"""Every driver configuration against the naive form: the fleet engine's
entry points must give results EXACTLY equal (array_equal, never allclose)
to unchunked ``offline_opt_fleet`` / ``run_fleet`` /
``evaluate_schedule_fleet`` on the scenario's observations materialized up
front (``FleetBatch.from_dense(*S.materialize(...))``, seed-replicated
like the engine's Monte-Carlo axis where ``n_seeds`` is set):

* ``offline_opt_fleet`` — materialized / checkpointed / chunked (divisor
  and non-divisor sizes) / host-streamed / cost-only, mixed horizons,
  mixed K, ``n_seeds`` replication, fused and obs-backed;
* ``run_fleet`` / ``evaluate_schedule_fleet`` — fused generation,
  chunked, streamed and seed-replicated;
* a hypothesis walk over (driver config) x (scenario) x (``n_seeds``);
* a forced-4-CPU-device subprocess leg: the sharded drivers against the
  naive form on one device.

The scenarios are the ones whose every draw goes through
``scenarios.base.slot_uniform``: GE arrivals with BERNOULLI emissions (the
chain innovations and the emissions both, under different salts) next to
ARMA spot rents, and stateless bernoulli arrivals with uniform rents.
``test_scenarios.py`` and ``test_offline_dp_streaming.py`` prove the
fused and checkpointed paths on other streams (GE-Poisson, Model-2).
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import scenarios as S
from repro.core.costs import HostingCosts, HostingGrid
from repro.core.fleet import (FleetBatch, evaluate_schedule_fleet,
                              offline_opt_fleet, run_fleet)
from repro.core.policies import AlphaRR

T = 40
KEY = jax.random.PRNGKey(29)
CHUNKS = [16, 20]      # 20 does not divide 40+pad: padded-tail leg

COST_POOL = [HostingCosts.two_level(4.0),
             HostingCosts.three_level(6.0, 0.25, 0.5),
             HostingCosts.three_level(3.0, 0.5, 0.25),
             HostingCosts(M=5.0, levels=(0.0, 0.3, 0.4, 0.5, 1.0),
                          g=(1.0, 0.4, 0.3, 0.15, 0.0)),
             HostingCosts.three_level(8.0, 0.375, 0.375)]


def make_scenario(B, kind="ge"):
    """"ge": GE arrivals with BERNOULLI emissions (chain innovations and
    emissions both draw through slot_uniform) + ARMA spot rents; "iid":
    stateless bernoulli + uniform."""
    kx = S.split_keys(KEY, B)
    if kind == "ge":
        return S.combine(
            S.ge_arrivals(kx, 0.3, 0.2, 0.9, 0.2, B, emission="bernoulli"),
            S.spot_rents(jax.random.PRNGKey(1), 0.5, B))
    return S.combine(S.bernoulli_arrivals(kx, 0.4, B),
                     S.uniform_rents(jax.random.PRNGKey(1), 0.5, 0.3, B))


def naive_fleet(fleet, sc, n_seeds=None):
    """The naive form's fleet: ``sc``'s observations over the whole
    horizon, with ``fleet``'s grid and horizons; ``n_seeds`` replicates
    rows instance-major, seed-minor, as the engine does."""
    grid, Ts = fleet.grid, np.asarray(fleet.T)
    if n_seeds is not None:
        rep = lambda a: np.repeat(np.asarray(a), n_seeds, axis=0)
        grid = HostingGrid(M=rep(grid.M), levels=rep(grid.levels),
                           g=rep(grid.g), mask=rep(grid.mask))
        sc, Ts = S.replicate_seeds(sc, n_seeds), rep(Ts)
    return FleetBatch.from_dense(grid, *S.materialize(sc, fleet.T_max),
                                 T=Ts)


def assert_same_offline(got, naive):
    assert np.array_equal(got.cost, naive.cost)
    if got.r_hist is None:                     # a cost-only solve
        assert got.sim is None
        return
    assert np.array_equal(got.r_hist, naive.r_hist)
    assert np.array_equal(got.sim.total, naive.sim.total)
    assert np.array_equal(got.sim.level_slots, naive.sim.level_slots)


def assert_same_sim(got, naive, what=None):
    assert np.array_equal(got.total, naive.total), what
    if got.r_hist is not None:
        assert np.array_equal(got.r_hist, naive.r_hist), what
    assert np.array_equal(got.level_slots, naive.level_slots), what


@pytest.fixture(scope="module", params=["ge", "iid"])
def stacked(request):
    grid = HostingGrid.from_costs(COST_POOL)
    sc = make_scenario(grid.B, request.param)
    fleet = FleetBatch.for_scenario(grid, [T, 23, 11, T, 7])
    return grid, sc, fleet, naive_fleet(fleet, sc)


@pytest.fixture(scope="module")
def naive_opt(stacked):
    return offline_opt_fleet(stacked[3])


DRIVER_CONFIGS = [
    {},
    {"checkpointed": True},
    {"chunk_size": CHUNKS[0]},
    {"checkpointed": True, "chunk_size": CHUNKS[1]},
    {"checkpointed": True, "chunk_size": CHUNKS[0], "stream": True},
    {"checkpointed": True, "chunk_size": CHUNKS[1],
     "collect_schedule": False},
]


@pytest.mark.parametrize("kw", DRIVER_CONFIGS)
def test_offline_opt_configs_match_naive(stacked, naive_opt, kw):
    _, sc, fleet, _ = stacked
    assert_same_offline(offline_opt_fleet(fleet, scenario=sc, **kw),
                        naive_opt)


def test_offline_opt_obs_backed_configs_match_naive(stacked, naive_opt):
    """The obs-backed drivers on the materialized observations: chunked
    with a padded tail, and checkpointed, streamed with prefetched
    slabs."""
    naive = stacked[3]
    for kw in ({"chunk_size": CHUNKS[1]},
               {"checkpointed": True, "chunk_size": CHUNKS[0],
                "stream": True, "async_ingest": True}):
        assert_same_offline(offline_opt_fleet(naive, **kw), naive_opt)


def test_offline_opt_n_seeds_matches_naive(stacked):
    _, sc, fleet, _ = stacked
    got = offline_opt_fleet(fleet, scenario=sc, n_seeds=3,
                            checkpointed=True, chunk_size=CHUNKS[0])
    assert got.n_seeds == 3
    assert_same_offline(got, offline_opt_fleet(naive_fleet(fleet, sc, 3)))


def test_run_fleet_configs_match_naive(stacked):
    _, sc, fleet, naive = stacked
    fns = AlphaRR.fleet(fleet)
    base = run_fleet(AlphaRR.fleet(naive), naive)
    for kw in ({}, {"chunk_size": CHUNKS[0]},
               {"chunk_size": CHUNKS[1], "stream": True}):
        assert_same_sim(run_fleet(fns, fleet, scenario=sc, **kw), base, kw)
    naive3 = naive_fleet(fleet, sc, 3)
    got = run_fleet(fns, fleet, scenario=sc, chunk_size=CHUNKS[0],
                    n_seeds=3)
    assert_same_sim(got, run_fleet(AlphaRR.fleet(naive3), naive3))


def test_evaluate_schedule_matches_naive(stacked, naive_opt):
    _, sc, fleet, naive = stacked
    r_hist = naive_opt.r_hist
    got = evaluate_schedule_fleet(fleet, r_hist, scenario=sc,
                                  chunk_size=CHUNKS[0])
    assert_same_sim(got, evaluate_schedule_fleet(naive, r_hist))


@settings(max_examples=8, deadline=None)
@given(st.sampled_from(DRIVER_CONFIGS), st.sampled_from(["ge", "iid"]),
       st.sampled_from([None, 2]))
def test_driver_config_walk(kw, kind, n_seeds):
    """Hypothesis walk over (driver config) x (scenario kind) x
    (``n_seeds``) on a three-instance fleet — every cell equal to the
    naive form."""
    grid = HostingGrid.from_costs(COST_POOL[:3])
    sc = make_scenario(grid.B, kind)
    fleet = FleetBatch.for_scenario(grid, [T, 17, 9])
    assert_same_offline(
        offline_opt_fleet(fleet, scenario=sc, n_seeds=n_seeds, **kw),
        offline_opt_fleet(naive_fleet(fleet, sc, n_seeds)))


# ----------------------------------------------------------------------
# Forced-multi-device leg (subprocess; conftest pins this process to one
# device): the sharded drivers against the naive form on one device.
# ----------------------------------------------------------------------

_SUBPROCESS_SCRIPT = textwrap.dedent("""
    import numpy as np, jax
    assert jax.device_count() == 4, jax.devices()
    from repro.core import scenarios as S
    from repro.core.costs import HostingCosts, HostingGrid
    from repro.core.fleet import FleetBatch, offline_opt_fleet, run_fleet
    from repro.core.policies import AlphaRR
    from repro.sharding.specs import fleet_mesh

    # B=5 is not a multiple of 4: dummy-instance padding on the mesh
    pool = [HostingCosts.two_level(4.0),
            HostingCosts.three_level(6.0, 0.25, 0.5),
            HostingCosts.three_level(3.0, 0.5, 0.25),
            HostingCosts(M=5.0, levels=(0.0, 0.3, 0.4, 0.5, 1.0),
                         g=(1.0, 0.4, 0.3, 0.15, 0.0)),
            HostingCosts.three_level(8.0, 0.375, 0.375)]
    grid = HostingGrid.from_costs(pool)
    kx = S.split_keys(jax.random.PRNGKey(29), grid.B)
    sc = S.combine(
        S.ge_arrivals(kx, 0.3, 0.2, 0.9, 0.2, grid.B, emission="bernoulli"),
        S.spot_rents(jax.random.PRNGKey(1), 0.5, grid.B))
    Ts = [40, 23, 11, 40, 7]
    fleet = FleetBatch.for_scenario(grid, Ts)
    naive = FleetBatch.from_dense(grid, *S.materialize(sc, 40), T=Ts)
    one = fleet_mesh(jax.devices()[:1])
    mesh = fleet_mesh()
    base = offline_opt_fleet(naive, mesh=one)
    for kw in ({}, {"checkpointed": True, "chunk_size": 16, "stream": True}):
        got = offline_opt_fleet(fleet, scenario=sc, mesh=mesh, **kw)
        assert np.array_equal(got.cost, base.cost), kw
        assert np.array_equal(got.r_hist, base.r_hist), kw
        assert np.array_equal(got.sim.total, base.sim.total), kw
    base = run_fleet(AlphaRR.fleet(naive), naive, mesh=one)
    got = run_fleet(AlphaRR.fleet(fleet), fleet, scenario=sc, mesh=mesh,
                    chunk_size=16)
    assert np.array_equal(got.total, base.total)
    assert np.array_equal(got.r_hist, base.r_hist)
    print("DRIVER-CONFIGS-MULTI-DEVICE-OK")
""")


def test_driver_configs_multi_device_match_naive():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS_SCRIPT],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "DRIVER-CONFIGS-MULTI-DEVICE-OK" in out.stdout
