"""The plain reference against the repo's per-instance engine, and the
bf16 control that the benchmark's comparison must refuse."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from bench import plainref, workload

BENCH = Path(__file__).resolve().parents[1]
CFG = json.loads((BENCH / "configs" / "whatif_pricing.json").read_text())
LEVELS, G = CFG["levels"], CFG["g"]


def _obs(seed, R, T):
    rng = np.random.default_rng(seed)
    x = rng.poisson(np.where(rng.random((R, T)) < 0.4, 2.0, 0.2))
    c = rng.uniform(0.07, 1.05, (R, T)).astype(np.float32)
    return x.astype(np.int32), c


@pytest.mark.parametrize("lane", ["alpha_rr", "rr", "static_bottom",
                                  "static_top", "opt"])
def test_reference_matches_per_instance_engine(lane):
    from repro.core.costs import HostingCosts
    from repro.core.policies import (AlphaRR, RetroRenting, StaticPolicy,
                                     offline_opt)
    from repro.core.simulator import run_policy

    R, T = 6, 400
    x, c = _obs(3, R, T)
    Ms = np.array([5.0, 10.0, 20.0, 5.0, 10.0, 20.0])
    lv, g = np.tile(LEVELS, (R, 1)), np.tile(G, (R, 1))
    c64 = c.astype(np.float64)
    if lane == "opt":
        ref = plainref.offline_opt(x, c64, Ms, lv, g)
    else:
        ref = plainref.price_lane(lane, x, c64, Ms, lv, g)
    for i in range(R):
        costs = HostingCosts.three_level(Ms[i], LEVELS[1], G[1])
        if lane == "opt":
            got = offline_opt(costs, x[i], c[i]).cost
        else:
            pol = {"alpha_rr": lambda: AlphaRR(costs),
                   "rr": lambda: RetroRenting(costs),
                   "static_bottom": lambda: StaticPolicy(costs, 0),
                   "static_top": lambda: StaticPolicy(costs, 2)}[lane]()
            got = run_policy(pol, pol.costs, x[i], c[i]).total
        assert got == pytest.approx(ref[i], rel=1e-5)


def test_reference_decisions_match_alpha_rr():
    from repro.core.costs import HostingCosts
    from repro.core.policies import AlphaRR
    from repro.core.simulator import run_policy

    R, T = 4, 600
    x, c = _obs(5, R, T)
    Ms = np.array([5.0, 10.0, 20.0, 10.0])
    lv, g = np.tile(LEVELS, (R, 1)), np.tile(G, (R, 1))
    *_, levels = plainref.retro_renting(x, c.astype(np.float64), Ms, lv, g)
    for i in range(R):
        costs = HostingCosts.three_level(Ms[i], LEVELS[1], G[1])
        sim = run_policy(AlphaRR(costs), costs, x[i], c[i])
        np.testing.assert_array_equal(levels[i], sim.r_hist)


def test_reference_observations_match_program_generator():
    """The reference draws the same observations as the program's fused
    generator from the same job key (rows b * S + s)."""
    from repro.core import scenarios as S
    from repro.core.scenarios.combinators import replicate_seeds

    B, n_seeds, T = 8, 2, 300
    a, r = CFG["arrivals"], CFG["rents"]
    key = workload.job_key(2**33 + 1, 4)
    k_arr, k_rent = jax.random.split(jnp.asarray(key, jnp.uint32))
    sc = S.combine(
        S.ge_arrivals(S.split_keys(k_arr, B), a["p_hl"], a["p_lh"],
                      a["rate_h"], a["rate_l"], B),
        S.arma_rents(k_rent, r["mean"], B, ar=tuple(r["ar"]),
                     ma=tuple(r["ma"]), sigma=r["rel_sigma"] * r["mean"],
                     c_min=r["min_frac"] * r["mean"],
                     c_max=r["max_frac"] * r["mean"]))
    rows = np.array([0, 3, 6, 9, 15])
    rs = replicate_seeds(sc, n_seeds)
    sub = rs._replace(params=jax.tree_util.tree_map(lambda v: v[rows],
                                                    rs.params))
    xp, cp, _, _ = S.materialize(sub, T, chunk_size=100)
    x, c = plainref.observations(key, B, n_seeds, rows, T, a, r)
    np.testing.assert_array_equal(xp, x)
    np.testing.assert_allclose(cp, c, rtol=1e-5)


def _limits(mix):
    return json.loads((BENCH / "traffic" / f"{mix}.json").read_text())[
        "limits"]


def test_bf16_control_fails_the_pricing_limits():
    """The reference computed in bfloat16, put in the program's place, reads
    above every pricing limit (lane totals and the optimum)."""
    B, n_seeds, T = 8, 2, 2048
    key = workload.job_key(11, 0)
    rows = np.arange(8)
    args = (key, B, n_seeds, rows, T, CFG["arrivals"], CFG["rents"])
    x, c = plainref.observations(*args)
    xb, cb = plainref.observations(*args, dt=ml_dtypes.bfloat16)
    Ms = np.array([5.0, 10.0, 20.0, 10.0] * 2)
    lv, g = np.tile(LEVELS, (8, 1)), np.tile(G, (8, 1))
    bf = ml_dtypes.bfloat16
    lim = _limits("lanes_opt")
    for lane in ("alpha_rr", "rr"):
        ref = plainref.price_lane(lane, x, c, Ms, lv, g)
        ctl = plainref.price_lane(lane, xb, cb, Ms, lv, g, bf)
        assert np.max(np.abs(ctl - ref) / ref) > lim["lane_cost_gap"]
    ref = plainref.offline_opt(x, c, Ms, lv, g)
    ctl = np.asarray(plainref.offline_opt(xb, cb, Ms, lv, g, bf), np.float64)
    assert np.max(np.abs(ctl - ref) / ref) > lim["opt_cost_gap"]


def test_bf16_control_fails_the_live_limits():
    """The control must fail one of the cell's numbers: here the cost gap;
    its decisions differ too, if less at this size than at the cell's."""
    cfg = json.loads((BENCH / "configs" / "metro_edge_fleet.json")
                     .read_text())
    cfg["sites"] = 16
    x, c = workload.Telemetry(cfg, 7).extend(2048)
    X, C = x.T, c.T.astype(np.float64)
    Ms = workload.fetch_costs(cfg, 7)
    lv, g = np.tile(LEVELS, (16, 1)), np.tile(G, (16, 1))
    bf = ml_dtypes.bfloat16
    ref = plainref.retro_renting(X, C, Ms, lv, g)
    ctl = plainref.retro_renting(X, C, Ms, lv, g, bf)
    lim = _limits("closed_loop")
    total = sum(ref[:3])
    gap = max(np.max(np.abs(np.asarray(b, np.float64) - a) / total)
              for a, b in zip(ref[:3], ctl[:3]))
    assert gap > lim["cost_gap"]
    assert np.mean(ref[3] != ctl[3]) > 0
