"""Offline what-if pricing jobs, back to back.

A job prices the whole deployment over a fresh Monte-Carlo draw of its
workload: the base key comes from ``--seed`` and the job's index, and the
program generates the Gilbert-Elliott arrivals and ARMA spot rents of every
row on the device.  It runs ``run_fleet`` with the mix's policy lanes and,
where the mix asks for it, the exact optimum by the checkpointed
``offline_opt_fleet``; each call returns its costs on the host.

Set-up builds the deployment and runs one whole job (compile or cache
load).  The window starts jobs while the last job's duration still fits in
``--seconds``.  The check draws one job of the window and rows from every
chip's shard, regenerates their observations in the plain reference and
compares each lane's total cost and the optimum.
"""
from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp
from repro.core import costs as C
from repro.core import fleet as F
from repro.core import scenarios as S
from repro.core.policies import AlphaRR, RetroRenting, StaticPolicy
from repro.sharding.specs import fleet_mesh

from bench import plainref, workload

LANES = {"alpha_rr": AlphaRR.fleet_lane,
         "rr": RetroRenting.fleet_lane,
         "static_bottom": lambda f: StaticPolicy.fleet(f, 0),
         "static_top": lambda f: StaticPolicy.fleet(f, f.grid.top_index())}


def setup(run):
    cfg, tr = run.config, run.traffic
    M = workload.fetch_costs(cfg, run.seed)
    costs = [C.HostingCosts(M=float(m), levels=tuple(cfg["levels"]),
                            g=tuple(cfg["g"])) for m in M]
    fleet = F.FleetBatch.for_scenario(C.HostingGrid.from_costs(costs),
                                      cfg["horizon_slots"])
    st = {"M": M, "fleet": fleet, "mesh": fleet_mesh(run.devices),
          "lanes": [LANES[name](fleet) for name in tr["lanes"]],
          "jobs": []}
    run.state = st
    t = time.perf_counter()
    job(run, 0)
    st["last_s"] = time.perf_counter() - t
    st["jobs"].clear()
    return st


def _scenario(cfg, key):
    B, a, r = cfg["sites"], cfg["arrivals"], cfg["rents"]
    k_arr, k_rent = jax.random.split(jnp.asarray(key, jnp.uint32))
    return S.combine(
        S.ge_arrivals(S.split_keys(k_arr, B), a["p_hl"], a["p_lh"],
                      a["rate_h"], a["rate_l"], B),
        S.arma_rents(k_rent, r["mean"], B, ar=tuple(r["ar"]),
                     ma=tuple(r["ma"]), sigma=r["rel_sigma"] * r["mean"],
                     c_min=max(r["min_frac"] * r["mean"], 1e-3),
                     c_max=r["max_frac"] * r["mean"]))


def job(run, j: int):
    """One pricing job; records its lane totals [P, rows] and optimum."""
    st, cfg = run.state, run.config
    key = workload.job_key(run.seed, j)
    with run.span("scenario", job=j):
        sc = _scenario(cfg, key)
    chunk, n_seeds = cfg["chunk_slots"], cfg["seeds"]
    with run.span("run_fleet", job=j):
        on = F.run_fleet(st["lanes"], st["fleet"], scenario=sc,
                         mesh=st["mesh"], n_seeds=n_seeds, chunk_size=chunk,
                         collect_trace=False)
        totals = on.policy_view(on.total)
    opt = None
    if run.traffic["opt"]:
        with run.span("offline_opt_fleet", job=j):
            opt = F.offline_opt_fleet(st["fleet"], scenario=sc,
                                      mesh=st["mesh"], n_seeds=n_seeds,
                                      chunk_size=chunk, checkpointed=True,
                                      collect_schedule=False).cost
    st["jobs"].append({"job": j, "key": key, "totals": totals, "opt": opt})


def _valid(rec) -> bool:
    ok = bool(np.all(np.isfinite(rec["totals"])) and
              np.all(rec["totals"] > 0))
    if rec["opt"] is not None:
        ok = ok and bool(np.all(np.isfinite(rec["opt"]))
                         and np.all(rec["opt"] > 0))
    return ok


def window(run):
    st = run.state
    start = time.perf_counter()
    deadline = start + run.seconds
    ends, j = [], 1
    now = start
    while not ends or now + st["last_s"] <= deadline:
        a = time.perf_counter()
        job(run, j)
        now = time.perf_counter()
        st["last_s"] = now - a
        ends.append(now)
        j += 1
    failed = sum(not _valid(r) for r in st["jobs"])
    cfg = run.config
    rows = cfg["sites"] * cfg["seeds"]
    return {"jobs": len(ends), "window_s": ends[-1] - start,
            "row_slots_per_job": rows * cfg["horizon_slots"],
            "attempted": len(ends),
            "completed": len(ends) - failed, "failed": failed}


def _reference(run, dt):
    """The plain reference on the checked job's sampled rows: lane totals
    [P, R] and the optimum [R] (None without one)."""
    st, cfg, tr = run.state, run.config, run.traffic
    if "checked" not in st:
        pick = workload.rng(run.seed, workload.SAMPLE_SALT, 1)
        B, n_seeds = cfg["sites"], cfg["seeds"]
        st["checked"] = (
            st["jobs"][int(pick.integers(len(st["jobs"])))],
            workload.sample_rows(run.seed, B * n_seeds, len(run.devices),
                                 tr["reference_rows"]))
    rec, rows = st["checked"]
    n_seeds = cfg["seeds"]
    x, c = plainref.observations(rec["key"], cfg["sites"], n_seeds, rows,
                                 cfg["horizon_slots"], cfg["arrivals"],
                                 cfg["rents"], dt)
    M = st["M"][rows // n_seeds]
    lv = np.tile(cfg["levels"], (len(rows), 1))
    g = np.tile(cfg["g"], (len(rows), 1))
    lanes = np.stack([plainref.price_lane(lane, x, c, M, lv, g, dt)
                      for lane in tr["lanes"]])
    opt = None
    if tr["opt"]:
        opt = np.asarray(plainref.offline_opt(x, c, M, lv, g, dt),
                         np.float64)
    return lanes, opt


def _gaps(run, lanes, opt, ref_lanes, ref_opt) -> dict:
    lim = run.traffic["limits"]
    out = {"lane_cost_gap": {
        "value": float(np.max(np.abs(lanes - ref_lanes) / ref_lanes)),
        "limit": lim["lane_cost_gap"]}}
    if ref_opt is not None:
        out["opt_cost_gap"] = {
            "value": float(np.max(np.abs(opt - ref_opt) / ref_opt)),
            "limit": lim["opt_cost_gap"]}
    return out


def check(run):
    """Sampled rows of one job of the window against the reference."""
    st = run.state
    for k in ("fleet", "lanes", "mesh"):
        st[k] = None                       # free the program's device state
    ref_lanes, ref_opt = _reference(run, np.float64)
    rec, rows = st["checked"]
    opt = None if rec["opt"] is None else rec["opt"][rows]
    return _gaps(run, rec["totals"][:, rows], opt, ref_lanes, ref_opt)


def control(run):
    """The same comparison with the reference computed in bfloat16 put in
    the program's place."""
    import ml_dtypes

    ref = _reference(run, np.float64)
    return _gaps(run, *_reference(run, ml_dtypes.bfloat16), *ref)
