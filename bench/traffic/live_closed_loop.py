"""Live admission by one closed-loop client.

The caller admits one slot of telemetry for the whole fleet and waits for
the [B] hosting decisions before it admits the next, because each slot's
decisions depend on the last: one client, a closed loop.  The telemetry
(Gilbert-Elliott arrivals and ARMA spot rents for every site) is made by the
benchmark before the window, sized from the rate of a few admits in
set-up with headroom; a run that uses it up fails, it never wraps.

Set-up builds ``LiveFleetScheduler`` over the deployment and admits the
first slots (compile or cache load, then a rate probe).  The window admits
slot after slot for ``--seconds``, timing each admit from handing over the
slot to holding the decisions on the host.  The check replays every
admitted slot of sampled sites through the plain reference (Algorithm 1 in
float64) and compares each decision and the accumulated rent, service and
fetch of ``report()``.
"""
from __future__ import annotations

import math
import time

import numpy as np

from repro.core.costs import HostingCosts
from repro.serve import scheduler as SCH

from bench import plainref, workload


def setup(run):
    cfg, tr = run.config, run.traffic
    M = workload.fetch_costs(cfg, run.seed)
    costs = [HostingCosts(M=float(m), levels=tuple(cfg["levels"]),
                          g=tuple(cfg["g"])) for m in M]
    sched = SCH.LiveFleetScheduler(costs)
    sites = workload.sample_rows(run.seed, cfg["sites"], 1,
                                 tr["reference_sites"])
    probe = 1 + tr["rate_probe_admits"]
    tel = workload.Telemetry(cfg, run.seed)
    x, c = tel.extend(probe)
    st = {"M": M, "sites": sites, "sched": sched, "K": len(cfg["levels"])}
    decisions = [sched.admit(x[0], c[0])[sites]]
    t = time.perf_counter()
    for i in range(1, probe):
        decisions.append(sched.admit(x[i], c[i])[sites])
    rate = (probe - 1) / (time.perf_counter() - t)
    n = probe + max(tr["telemetry_min_slots"],
                    math.ceil(rate * run.seconds * tr["telemetry_headroom"]))
    x, c = tel.extend(n)
    st.update(x=x, c=c, next=probe, decisions=decisions)
    return st


def window(run):
    st = run.state
    sched, x, c, sites = st["sched"], st["x"], st["c"], st["sites"]
    K, B = st["K"], run.config["sites"]
    t, n = st["next"], x.shape[0]
    lat, failed = [], 0
    start = time.perf_counter()
    deadline = start + run.seconds
    end = start
    while end < deadline:
        if t >= n:
            raise RuntimeError(f"telemetry used up after {t} slots: the "
                               f"window admitted faster than sized for")
        with run.span("admit", slot=t):
            a = time.perf_counter()
            r = sched.admit(x[t], c[t])
            end = time.perf_counter()
        lat.append(end - a)
        if r.shape != (B,) or r.min() < 0 or r.max() >= K:
            failed += 1
        st["decisions"].append(r[sites])
        t += 1
    st["next"] = t
    n_admits = len(lat)
    return {"latency_s": np.asarray(lat), "window_s": end - start,
            "attempted": n_admits, "completed": n_admits - failed,
            "failed": failed}


def _reference(run, dt):
    """Algorithm 1 on every admitted slot of the sampled sites:
    ``(rent, service, fetch, levels [R, N])``."""
    st, cfg = run.state, run.config
    sites, n = st["sites"], st["next"]
    R = len(sites)
    return plainref.retro_renting(
        st["x"][:n, sites].T, st["c"][:n, sites].T.astype(np.float64),
        st["M"][sites], np.tile(cfg["levels"], (R, 1)),
        np.tile(cfg["g"], (R, 1)), dt)


def _gaps(run, got, ref) -> dict:
    total = sum(ref[:3])
    gap = max(float(np.max(np.abs(np.asarray(p, np.float64) - q) / total))
              for p, q in zip(got[:3], ref[:3]))
    lim = run.traffic["limits"]
    return {"decisions_differ_share": {
                "value": float(np.mean(got[3] != ref[3])),
                "limit": lim["decisions_differ_share"]},
            "cost_gap": {"value": gap, "limit": lim["cost_gap"]}}


def check(run):
    """Every admitted slot of the sampled sites against the reference."""
    st = run.state
    rep = st["sched"].report()
    st["sched"] = None                     # free the program's device state
    sites = st["sites"]
    got = (rep.rent[sites], rep.service[sites], rep.fetch[sites],
           np.stack(st["decisions"], axis=1))
    return _gaps(run, got, _reference(run, np.float64))


def control(run):
    """The same comparison with the reference computed in bfloat16 put in
    the program's place."""
    import ml_dtypes

    return _gaps(run, _reference(run, ml_dtypes.bfloat16),
                 _reference(run, np.float64))
