"""Offline pricing of a multi-tenant edge's joint optimum, jobs back to back.

Every site hosts N services under one storage cap.  A job prices the whole
deployment over a fresh Monte-Carlo draw of its workload: the base key comes
from ``--seed`` and the job's index, the program generates each service's
Gilbert-Elliott arrivals (salted per service) and the site's one ARMA spot
rent on the device, reduces them to the feasible joint states and runs the
checkpointed exact DP over those states (``offline_opt_services``).  There
are no policy lanes.

Set-up builds the deployment and runs one whole job (compile or cache
load).  The window starts jobs while the last job's duration still fits in
``--seconds``.  The check draws one job of the window and rows from every
chip's shard, regenerates their observations in the plain reference
(``bench.plainref_joint``) and compares the joint optimum.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import services as SV
from repro.core.costs import HostingCosts, ServiceSet
from repro.sharding.specs import fleet_mesh

from bench import plainref_joint, workload
from bench.traffic.pricing_jobs import _scenario


def fetch_costs(cfg: dict, seed: int) -> np.ndarray:
    """Per-site, per-service full-service fetch costs M [B, N], drawn from
    the config's mix."""
    return workload.rng(seed, workload.SITES_SALT).choice(
        np.asarray(cfg["M_choices"], np.float64),
        size=(cfg["sites"], cfg["services"]))


def service_sets(cfg: dict, M) -> list:
    """One ``ServiceSet`` per site: a service per column of M [B, N], each
    on the config's levels and service costs, under its capacity."""
    return [ServiceSet(tuple(HostingCosts(M=float(m),
                                          levels=tuple(cfg["levels"]),
                                          g=tuple(cfg["g"])) for m in row),
                       capacity=cfg["capacity"]) for row in M]


def setup(run):
    cfg = run.config
    M = fetch_costs(cfg, run.seed)
    sets = service_sets(cfg, M)
    st = {"M": M, "sfleet": SV.service_fleet(sets, cfg["horizon_slots"]),
          "mesh": fleet_mesh(run.devices), "jobs": []}
    run.state = st
    t = time.perf_counter()
    job(run, 0)
    st["last_s"] = time.perf_counter() - t
    st["jobs"].clear()
    return st


def job(run, j: int):
    """One pricing job; records the joint optimum of every row."""
    st, cfg = run.state, run.config
    key = workload.job_key(run.seed, j)
    with run.span("scenario", job=j):
        sc = _scenario(cfg, key)
    with run.span("offline_opt_services", job=j):
        opt = SV.offline_opt_services(
            st["sfleet"], scenario=sc, mesh=st["mesh"], n_seeds=cfg["seeds"],
            chunk_size=cfg["chunk_slots"], checkpointed=True,
            collect_schedule=False).cost
    st["jobs"].append({"job": j, "key": key, "opt": opt})


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
    failed = sum(not (np.all(np.isfinite(r["opt"])) and np.all(r["opt"] > 0))
                 for r in st["jobs"])
    cfg = run.config
    rows = cfg["sites"] * cfg["seeds"]
    return {"jobs": len(ends), "window_s": ends[-1] - start,
            "row_slots_per_job": rows * cfg["horizon_slots"],
            "attempted": len(ends),
            "completed": len(ends) - failed, "failed": failed}


def _reference(run, dt):
    """The plain reference's joint optimum [R] on the checked job's sampled
    rows."""
    st, cfg = run.state, run.config
    n_seeds, N = cfg["seeds"], cfg["services"]
    if "checked" not in st:
        pick = workload.rng(run.seed, workload.SAMPLE_SALT, 1)
        st["checked"] = (
            st["jobs"][int(pick.integers(len(st["jobs"])))],
            workload.sample_rows(run.seed, cfg["sites"] * n_seeds,
                                 len(run.devices),
                                 run.traffic["reference_rows"]))
    rec, rows = st["checked"]
    x, c = plainref_joint.observations(rec["key"], cfg["sites"], n_seeds, N,
                                       rows, cfg["horizon_slots"],
                                       cfg["arrivals"], cfg["rents"], dt)
    return np.asarray(plainref_joint.joint_opt(
        x, c, st["M"][rows // n_seeds], [cfg["levels"]] * N,
        [cfg["g"]] * N, cfg["capacity"], dt), np.float64)


def _gaps(run, opt, ref) -> dict:
    return {"opt_cost_gap": {
        "value": float(np.max(np.abs(opt - ref) / ref)),
        "limit": run.traffic["limits"]["opt_cost_gap"]}}


def check(run):
    """Sampled rows of one job of the window against the reference."""
    st = run.state
    st["sfleet"] = st["mesh"] = None       # free the program's device state
    ref = _reference(run, np.float64)
    rec, rows = st["checked"]
    return _gaps(run, rec["opt"][rows], ref)


def control(run):
    """The same comparison with the reference computed in bfloat16 put in
    the program's place."""
    import ml_dtypes

    ref = _reference(run, np.float64)
    return _gaps(run, _reference(run, ml_dtypes.bfloat16), ref)
