"""Backend dispatch bit-identity: the ``dp_backend=`` / ``prng_backend=``
knobs threaded through the fleet engine are pure performance choices —
every driver configuration must produce results EXACTLY equal (array_equal,
never allclose) to the canonical XLA path, per the engine's
backend-dispatch invariant (ROADMAP.md):

* ``offline_opt_fleet`` — materialized / checkpointed / chunked (divisor
  and non-divisor sizes) / host-streamed / cost-only, mixed horizons,
  mixed K, ``n_seeds`` replication, dp and prng backends independently
  and together;
* ``run_fleet`` / ``evaluate_schedule_fleet`` — prng backend through the
  fused scan, including the GE *bernoulli-emission* path (the one arrival
  stream whose innovations AND emissions both ride ``slot_uniform``);
* argument validation (unknown backends; prng reroute without a scenario);
* a forced-4-CPU-device subprocess leg proving the pallas legs shard (the
  mesh path runs the kernels under ``shard_map`` and needs its own
  proof).
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
    emissions both draw through slot_uniform -> the full pallas chain) +
    ARMA spot rents; "iid": stateless bernoulli + uniform."""
    kx = S.split_keys(KEY, B)
    if kind == "ge":
        return S.combine(
            S.ge_arrivals(kx, 0.3, 0.2, 0.9, 0.2, B, emission="bernoulli"),
            S.spot_rents(jax.random.PRNGKey(1), 0.5, B))
    return S.combine(S.bernoulli_arrivals(kx, 0.4, B),
                     S.uniform_rents(jax.random.PRNGKey(1), 0.5, 0.3, B))


def assert_same_offline(a, b):
    assert np.array_equal(a.cost, b.cost)
    if a.r_hist is None:
        assert b.r_hist is None
        return
    assert np.array_equal(a.r_hist, b.r_hist)
    assert np.array_equal(a.sim.total, b.sim.total)
    assert np.array_equal(a.sim.level_slots, b.sim.level_slots)


@pytest.fixture(scope="module", params=["ge", "iid"])
def stacked(request):
    grid = HostingGrid.from_costs(COST_POOL)
    sc = make_scenario(grid.B, request.param)
    fleet = FleetBatch.for_scenario(grid, [T, 23, 11, T, 7])
    return grid, sc, fleet


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
def test_offline_opt_backends_bitwise(stacked, kw):
    _, sc, fleet = stacked
    base = offline_opt_fleet(fleet, scenario=sc, **kw)
    for bk in ({"dp_backend": "pallas"},
               {"prng_backend": "pallas"},
               {"dp_backend": "pallas", "prng_backend": "pallas"}):
        assert_same_offline(
            offline_opt_fleet(fleet, scenario=sc, **kw, **bk), base)


def test_offline_opt_backends_obs_backed(stacked):
    """dp_backend on materialized observations (no scenario at all)."""
    grid, sc, fleet = stacked
    x, c, svc, side = S.materialize(sc, T)
    fl = FleetBatch.from_dense(grid, x, c, T=np.asarray(fleet.T))
    base = offline_opt_fleet(fl)
    for kw in ({}, {"checkpointed": True, "chunk_size": CHUNKS[0]}):
        assert_same_offline(
            offline_opt_fleet(fl, dp_backend="pallas", **kw), base)


def test_offline_opt_backends_n_seeds(stacked):
    _, sc, fleet = stacked
    base = offline_opt_fleet(fleet, scenario=sc, n_seeds=3,
                             checkpointed=True, chunk_size=CHUNKS[0])
    assert_same_offline(
        offline_opt_fleet(fleet, scenario=sc, n_seeds=3, checkpointed=True,
                          chunk_size=CHUNKS[0], dp_backend="pallas",
                          prng_backend="pallas"), base)


def test_run_fleet_prng_backend_bitwise(stacked):
    _, sc, fleet = stacked
    fns = AlphaRR.fleet(fleet)
    for kw in ({}, {"chunk_size": CHUNKS[0]},
               {"chunk_size": CHUNKS[1], "stream": True},
               {"chunk_size": CHUNKS[0], "n_seeds": 3}):
        base = run_fleet(fns, fleet, scenario=sc, **kw)
        got = run_fleet(fns, fleet, scenario=sc, prng_backend="pallas", **kw)
        assert np.array_equal(got.total, base.total), kw
        assert np.array_equal(got.r_hist, base.r_hist), kw
        assert np.array_equal(got.level_slots, base.level_slots), kw


def test_evaluate_schedule_prng_backend_bitwise(stacked):
    _, sc, fleet = stacked
    r_hist = offline_opt_fleet(fleet, scenario=sc).r_hist
    base = evaluate_schedule_fleet(fleet, r_hist, scenario=sc,
                                   chunk_size=CHUNKS[0])
    got = evaluate_schedule_fleet(fleet, r_hist, scenario=sc,
                                  chunk_size=CHUNKS[0],
                                  prng_backend="pallas")
    assert np.array_equal(got.total, base.total)
    assert np.array_equal(got.level_slots, base.level_slots)


def test_backend_validation(stacked):
    _, sc, fleet = stacked
    with pytest.raises(ValueError, match="dp_backend"):
        offline_opt_fleet(fleet, scenario=sc, dp_backend="cuda")
    with pytest.raises(ValueError, match="prng_backend"):
        offline_opt_fleet(fleet, scenario=sc, prng_backend="tpu")
    with pytest.raises(ValueError, match="needs scenario"):
        offline_opt_fleet(fleet, prng_backend="pallas")
    with pytest.raises(ValueError, match="prng_backend"):
        run_fleet(AlphaRR.fleet(fleet), fleet, scenario=sc,
                  prng_backend="nope")
    with pytest.raises(ValueError):
        S.with_prng_backend(sc, "nope")


def test_with_prng_backend_identity(stacked):
    """"xla" is a no-op wrap; "pallas" renames and caches: wrapping the
    same scenario twice yields the SAME function objects (the identity-
    keyed compile caches depend on it)."""
    _, sc, _ = stacked
    assert S.with_prng_backend(sc, "xla") is sc
    a = S.with_prng_backend(sc, "pallas")
    b = S.with_prng_backend(sc, "pallas")
    assert a.name.endswith("@pallas")
    assert a.init_fn is b.init_fn and a.chunk_fn is b.chunk_fn


@settings(max_examples=8, deadline=None)
@given(st.sampled_from(DRIVER_CONFIGS), st.sampled_from(["ge", "iid"]),
       st.sampled_from(["pallas-dp", "pallas-prng", "pallas-both"]))
def test_backend_config_walk(kw, kind, mode):
    """Hypothesis walk over (driver config) x (scenario kind) x (backend
    combination) — every cell bit-identical to XLA."""
    grid = HostingGrid.from_costs(COST_POOL[:3])
    sc = make_scenario(grid.B, kind)
    fleet = FleetBatch.for_scenario(grid, [T, 17, 9])
    bk = {}
    if mode in ("pallas-dp", "pallas-both"):
        bk["dp_backend"] = "pallas"
    if mode in ("pallas-prng", "pallas-both"):
        bk["prng_backend"] = "pallas"
    assert_same_offline(
        offline_opt_fleet(fleet, scenario=sc, **kw, **bk),
        offline_opt_fleet(fleet, scenario=sc, **kw))


# ----------------------------------------------------------------------
# Forced-multi-device leg (subprocess; conftest pins this process to one
# device): sharded pallas == XLA needs an explicit proof.
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
    fleet = FleetBatch.for_scenario(grid, [40, 23, 11, 40, 7])
    mesh = fleet_mesh()
    for kw in ({}, {"checkpointed": True, "chunk_size": 16, "stream": True}):
        base = offline_opt_fleet(fleet, scenario=sc, mesh=mesh, **kw)
        got = offline_opt_fleet(fleet, scenario=sc, mesh=mesh,
                                dp_backend="pallas",
                                prng_backend="pallas", **kw)
        assert np.array_equal(got.cost, base.cost), kw
        assert np.array_equal(got.r_hist, base.r_hist), kw
        assert np.array_equal(got.sim.total, base.sim.total), kw
    fns = AlphaRR.fleet(fleet)
    base = run_fleet(fns, fleet, scenario=sc, mesh=mesh, chunk_size=16)
    got = run_fleet(fns, fleet, scenario=sc, mesh=mesh, chunk_size=16,
                    prng_backend="pallas")
    assert np.array_equal(got.total, base.total)
    assert np.array_equal(got.r_hist, base.r_hist)
    print("BACKEND-MULTI-DEVICE-OK")
""")


def test_backend_dispatch_multi_device_bitwise():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS_SCRIPT],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "BACKEND-MULTI-DEVICE-OK" in out.stdout
