"""The multi-tenant edge cell: the joint plain reference against the
program, its draws against the program's tiled generator, whole runs of
``multitenant.joint_opt`` at a CPU-sized scale (sound, planted faults), the
bf16 control and the DP's work count."""
import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from bench import jointwork, plainref_joint, workload
from bench.tests.test_bench_faults import run_cell, tiny_spec
from bench.traffic.joint_pricing import service_sets
from bench.traffic.pricing_jobs import _scenario

BENCH = Path(__file__).resolve().parents[1]
CFG = json.loads((BENCH / "configs" / "multitenant_edge.json").read_text())
LIMITS = json.loads((BENCH / "traffic" / "joint_opt.json").read_text())[
    "limits"]
CELL = "multitenant.joint_opt"


def _sets(M, capacity):
    return service_sets(dict(CFG, capacity=capacity), M)


def _inputs(seed, B, N, T):
    rng = np.random.default_rng(seed)
    x = rng.poisson(np.where(rng.random((B, N, T)) < 0.4, 2.0, 0.2))
    c = rng.uniform(0.07, 1.05, (B, T)).astype(np.float32)
    M = rng.choice(CFG["M_choices"], size=(B, N))
    return x.astype(np.int32), c, M


def _reference(x, c, M, capacity):
    N = x.shape[1]
    return plainref_joint.joint_opt(x, c.astype(np.float64), M,
                                    [CFG["levels"]] * N, [CFG["g"]] * N,
                                    capacity)


@pytest.mark.parametrize("capacity", [1.0, 2.0])
@pytest.mark.parametrize("N", [3, 4])
def test_reference_matches_joint_program(N, capacity):
    """The float64 reference equals ``offline_opt_services`` on recorded
    per-service arrivals and one rent per site (B = 4, T = 64)."""
    from repro.core import scenarios as S
    from repro.core import services as SV

    B, T = 4, 64
    x, c, M = _inputs(10 * N + int(capacity), B, N, T)
    sf = SV.service_fleet(_sets(M, capacity), T)
    assert plainref_joint.joint_states([CFG["levels"]] * N, capacity).shape \
        == sf.sets[0].joint_states().shape
    sc = S.trace_scenario(x.reshape(B * N, T), np.repeat(c, N, axis=0))
    got = SV.offline_opt_services(sf, scenario=sc, chunk_size=16,
                                  checkpointed=True,
                                  collect_schedule=False).cost
    np.testing.assert_allclose(got, _reference(x, c, M, capacity),
                               rtol=1e-5)


@pytest.mark.parametrize("capacity", [1.0, 2.0])
@pytest.mark.parametrize("N", [3, 4])
def test_reference_matches_brute_force(N, capacity):
    """The reference equals the exhaustive ``J ** T`` oracle, at a horizon
    the oracle can enumerate (T = 3)."""
    from repro.core.policies.offline_opt import brute_force_joint_opt

    B, T = 4, 3
    x, c, M = _inputs(20 * N + int(capacity), B, N, T)
    ref = _reference(x, c, M, capacity)
    for b, sset in enumerate(_sets(M, capacity)[:2]):
        want = brute_force_joint_opt(sset, x[b], c[b]).cost
        assert ref[b] == pytest.approx(want, rel=1e-6)


def test_reference_draws_match_tiled_generator():
    """Per-service arrivals and the shared rent drawn by the reference equal
    the program's tiled generator bit for bit (rows ``(b * N + n) * S + s``
    of ``replicate_seeds(tile_services(...))``)."""
    from repro.core import scenarios as S

    B, N, n_seeds, T = 6, 4, 2, 200
    key = workload.job_key(2**33 + 5, 3)
    cfg = dict(CFG, sites=B)
    sc = S.replicate_seeds(S.tile_services(_scenario(cfg, key), N), n_seeds)
    xp, cp, _, _ = S.materialize(sc, T, chunk_size=50)
    rows = np.array([0, 3, 5, 8, 11])                     # b * S + s
    x, c = plainref_joint.observations(key, B, n_seeds, N, rows, T,
                                       CFG["arrivals"], CFG["rents"])
    b, s = rows // n_seeds, rows % n_seeds
    for n in range(N):
        prog = (b * N + n) * n_seeds + s
        np.testing.assert_array_equal(xp[prog], x[:, n])
        np.testing.assert_array_equal(cp[prog], cp[b * N * n_seeds + s])
        np.testing.assert_allclose(cp[prog], c, rtol=1e-5)
    assert not np.array_equal(x[:, 0], x[:, 1])


@pytest.fixture
def spec(tmp_path, monkeypatch):
    from bench import compcache, peaks
    monkeypatch.setitem(peaks.PEAKS, jax.devices()[0].device_kind,
                        peaks.PEAKS["TPU v5 lite"])
    monkeypatch.setattr(compcache, "enable", lambda: None)
    return tiny_spec(tmp_path)


def test_sound_run_is_correct(spec):
    res = run_cell(spec, CELL)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]["priced_row_slots_per_s"]["value"] > 0


def _no_capacity(monkeypatch):
    """Every level tuple a state: the capacity taken as N."""
    from repro.core.costs import ServiceSet
    monkeypatch.setattr(ServiceSet, "cap", property(lambda self: self.N))


def _service_zero(monkeypatch):
    """Every service of a site draws service 0's arrivals."""
    from repro.core import services as SV
    tile = SV.tile_services

    def same(obj, n, shared=("rent",)):
        out = tile(obj, n, shared)
        B = out.params["arr"]["key"].shape[0] // n
        first = np.repeat(np.arange(B) * n, n)
        arr = jax.tree_util.tree_map(lambda a: jnp.asarray(a)[first],
                                     out.params["arr"])
        return out._replace(params=dict(out.params, arr=arr))
    monkeypatch.setattr(SV, "tile_services", same)


def _neighbours(monkeypatch):
    """Each row handed its neighbour's cost where the DP produces it."""
    from repro.core import services as SV
    opt = SV.offline_opt_fleet

    def rolled(*a, **k):
        res = opt(*a, **k)
        return dataclasses.replace(res, cost=np.roll(res.cost, 1))
    monkeypatch.setattr(SV, "offline_opt_fleet", rolled)


@pytest.mark.parametrize("fault", [_no_capacity, _service_zero, _neighbours],
                         ids=["capacity_dropped", "service_fold_dropped",
                              "neighbour_cost"])
def test_planted_fault_is_not_correct(spec, monkeypatch, fault):
    fault(monkeypatch)
    res = run_cell(spec, CELL)
    assert res["correct"] is False, res["checks"]


def test_bf16_control_fails_the_limit():
    """The reference computed in bfloat16 reads above the cell's limit."""
    B, N, n_seeds, T = 8, CFG["services"], 2, 2048
    key = workload.job_key(13, 0)
    rows = np.arange(8)
    args = (key, B, n_seeds, N, rows, T, CFG["arrivals"], CFG["rents"])
    x, c = plainref_joint.observations(*args)
    xb, cb = plainref_joint.observations(*args, dt=ml_dtypes.bfloat16)
    M = np.array([[5.0, 10.0, 20.0, 10.0]] * 8)
    lv, g = [CFG["levels"]] * N, [CFG["g"]] * N
    ref = plainref_joint.joint_opt(x, c, M, lv, g, CFG["capacity"])
    ctl = np.asarray(plainref_joint.joint_opt(xb, cb, M, lv, g,
                                              CFG["capacity"],
                                              ml_dtypes.bfloat16),
                     np.float64)
    assert np.max(np.abs(ctl - ref) / ref) > LIMITS["opt_cost_gap"]


def test_jointwork_counts():
    # J = 50 feasible joint states of the configuration
    assert len(plainref_joint.joint_states([CFG["levels"]] * 4, 2.0)) == 50
    # rows 2, T 3, J 5, N 2: (2 * 25 + 5 * 5) per row-slot
    assert jointwork.dp_ops(2, 3, 5, 2) == 2 * 3 * 75
    # (2 * 4 + 4) bytes per row-slot, 4 per row
    assert jointwork.dp_bytes(2, 3, 5, 2) == 2 * (3 * 12 + 4)
    peaks = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}
    assert jointwork.least_time(2, 3, 5, 2, peaks) == (0.45, "ops")
    peaks = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1.0}
    assert jointwork.least_time(2, 3, 5, 2, peaks) == (80.0, "bytes")
