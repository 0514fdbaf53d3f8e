#!/usr/bin/env python3
"""Smoke run of the hosting engine on a TPU: its main path, once, at the
size of a metro edge fleet, checked against the engine's own references.

    python chip_smoke.py [--seed N]        # one chip: live, offline, pallas
    python chip_smoke.py --chips 4         # four chips: the fleet mesh only

The workload is the README's: ``HostingCosts.three_level`` sites with mixed
fetch costs M, Gilbert-Elliott arrivals and ARMA spot rents, all generated
from ``--seed``.  Phases (one chip):

* live     — ``LiveFleetScheduler`` admits telemetry one slot per call;
             its decisions and costs equal ``run_fleet`` on the same
             telemetry bitwise, and sampled rows equal the per-instance
             ``simulator.run_policy`` on the host CPU (decisions exact,
             costs within ``REF_RTOL``);
* offline  — the alpha-RR and RR lanes priced by ``run_fleet`` with fused
             generation, and exact OPT by the checkpointed
             ``offline_opt_fleet``; every lane row costs at least OPT (less
             ``OPT_RTOL``), and sampled OPT rows equal ``offline_opt`` on
             the host CPU within ``REF_RTOL``;
* pallas   — the offline phase at a shorter T with the compiled Pallas
             kernels (``dp_backend`` / ``prng_backend="pallas"``), bitwise
             equal to the XLA leg, schedules included.

``--chips 4`` runs only the offline phase on ``fleet_mesh`` over four chips
against one chip, at one B that four divides and one it does not (the
padding path); results must be bitwise equal.

Each phase prints one JSON line with its checks and ``setup_s`` (the
first call of each engine entry point: compile included), which is not a
benchmark.  The last line is
``{"ok": true, "device": {...}}`` only if every phase passed; a run that
finds no TPU exits non-zero before any phase.  The compile cache is
``repro.compile_cache`` (``JAX_COMPILATION_CACHE_DIR`` or
``<checkout>/.jax_cache``), so a second run in the same checkout loads
its programs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# The per-instance references run on the host's CPU backend beside the
# chip; keep it available when the platform list is pinned.
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402

# Full size: a metro fleet of 4096 edge sites; 256 live slots; 131072
# offline slots (about 36 hours of 1 s slots) in 4096-slot chunks.
B_FLEET = 4096
LIVE_SLOTS = 256
T_OFFLINE = 131072
T_PALLAS = 8192
CHUNK = 4096
N_REF_ROWS = 8
B_UNEVEN = 4093                 # not a multiple of 4: the padded mesh path

M_CHOICES = (5.0, 10.0, 20.0)   # README Quickstart: three_level(M, .4, .35)
ALPHA, G_ALPHA = 0.4, 0.35
GE = dict(p_hl=0.3, p_lh=0.2, rate_h=2.0, rate_l=0.2)
C_MEAN = 0.35

# float32 on the chip vs float32 on the host CPU: the same ops, but XLA
# may fuse them differently, so costs agree to rounding, not bitwise.
REF_RTOL = 1e-5
# An online lane prices the same sample path as OPT, which is the minimum;
# the slack covers float32 accumulation over T slots on both sides.
OPT_RTOL = 1e-4


def workload(B: int, seed: int):
    """``(costs_list, scenario)`` for B sites, all from ``seed``."""
    from repro.core import scenarios as S
    from repro.core.costs import HostingCosts

    rng = np.random.default_rng(seed)
    Ms = rng.choice(M_CHOICES, size=B)
    costs = [HostingCosts.three_level(float(m), ALPHA, G_ALPHA) for m in Ms]
    k_arr, k_rent = jax.random.split(jax.random.PRNGKey(seed))
    sc = S.combine(
        S.ge_arrivals(S.split_keys(k_arr, B), GE["p_hl"], GE["p_lh"],
                      GE["rate_h"], GE["rate_l"], B),
        S.spot_rents(k_rent, C_MEAN, B))
    return costs, sc


def rows_of(sc, rows):
    """The scenario restricted to ``rows`` (counter keys: same draws)."""
    return sc._replace(params=jax.tree_util.tree_map(
        lambda a: a[np.asarray(rows)], sc.params))


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def first_and_warm(fn):
    """Call ``fn`` twice: ``(result, first_s)``; the two results must be
    equal (the same program on the same inputs)."""
    a, first = timed(fn)
    b = fn()
    same = jax.tree_util.tree_map(
        lambda u, v: np.array_equal(np.asarray(u), np.asarray(v)), a, b)
    if not all(jax.tree_util.tree_leaves(same)):
        raise AssertionError("a repeated call returned different results")
    return a, first


def _sim_arrays(res, *extra):
    return {k: getattr(res, k) for k in
            ("total", "rent", "service", "fetch", "level_slots") + extra}


def _assert_equal(name, a, b):
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape or not np.array_equal(a, b):
        bad = (np.size(a) if a.shape != b.shape
               else int(np.sum(a != b)))
        raise AssertionError(f"{name}: not bitwise equal ({bad} entries "
                             f"differ)")


def _rel_err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30)))


def live_phase(B: int, n_slots: int, seed: int, n_ref: int,
               ref_device) -> dict:
    from repro.core import scenarios as S
    from repro.core.fleet import FleetBatch, run_fleet
    from repro.core.policies import AlphaRR
    from repro.core.simulator import run_policy
    from repro.serve.scheduler import LiveFleetScheduler

    costs, sc = workload(B, seed)
    x, c, _, _ = S.materialize(sc, n_slots)
    sched = LiveFleetScheduler(costs)
    r = np.empty((B, n_slots), np.int64)
    r[:, 0], setup_s = timed(lambda: sched.admit(x[:, 0], c[:, 0]))
    for t in range(1, n_slots):
        r[:, t] = sched.admit(x[:, t], c[:, t])
    live = sched.report()

    fleet = FleetBatch.from_dense(sched.fleet.grid, x, c)
    ref, ref_first = first_and_warm(lambda: _sim_arrays(
        run_fleet(AlphaRR.fleet(fleet), fleet), "r_hist"))
    _assert_equal("live decisions vs run_fleet", r, ref.pop("r_hist"))
    for k, v in ref.items():
        _assert_equal(f"live {k} vs run_fleet", getattr(live, k), v)

    rows = np.random.default_rng(seed + 1).choice(B, n_ref, replace=False)
    worst = 0.0
    with jax.default_device(ref_device):
        for i in rows:
            sim = run_policy(AlphaRR(costs[i]), costs[i], x[i], c[i])
            _assert_equal(f"live row {i} decisions vs run_policy",
                          r[i], sim.r_hist)
            for k in ("total", "rent", "service", "fetch"):
                worst = max(worst, _rel_err(getattr(live, k)[i],
                                            getattr(sim, k)))
    if worst > REF_RTOL:
        raise AssertionError(f"live costs vs run_policy: rel err {worst} > "
                             f"{REF_RTOL}")
    return {"B": B, "slots": n_slots, "ref_rows": [int(i) for i in rows],
            "decisions_vs_run_fleet": "bitwise",
            "costs_vs_run_fleet": "bitwise",
            "decisions_vs_run_policy_cpu": "equal",
            "costs_vs_run_policy_cpu_max_rel_err": worst,
            "setup_s": setup_s + ref_first}


def _offline_legs(B, T, chunk, seed, *, mesh=None, backend="xla",
                  collect_schedule=False):
    """The offline pricing calls of one leg: ``(costs, scenario, online,
    opt, setup_s)`` — both entry points called twice, the first call
    timed."""
    from repro.core.costs import HostingGrid
    from repro.core.fleet import FleetBatch, offline_opt_fleet, run_fleet
    from repro.core.policies import AlphaRR, RetroRenting

    costs, sc = workload(B, seed)
    fleet = FleetBatch.for_scenario(HostingGrid.from_costs(costs), T)
    lanes = [AlphaRR.fleet_lane(fleet), RetroRenting.fleet_lane(fleet)]
    online, on_first = first_and_warm(lambda: _sim_arrays(run_fleet(
        lanes, fleet, scenario=sc, mesh=mesh, chunk_size=chunk,
        collect_trace=False, prng_backend=backend)))

    def price_opt():
        res = offline_opt_fleet(
            fleet, scenario=sc, mesh=mesh, chunk_size=chunk,
            checkpointed=True, collect_schedule=collect_schedule,
            dp_backend=backend, prng_backend=backend)
        if not collect_schedule:
            return {"cost": res.cost}
        return dict(cost=res.cost, r_hist=res.r_hist, **_sim_arrays(res.sim))

    opt, op_first = first_and_warm(price_opt)
    return costs, sc, online, opt, on_first + op_first


def offline_phase(B: int, T: int, chunk: int, seed: int, n_ref: int,
                  ref_device) -> dict:
    from repro.core import scenarios as S
    from repro.core.policies.offline_opt import offline_opt

    costs, sc, online, opt, setup_s = _offline_legs(B, T, chunk, seed)
    lane_total = online["total"].reshape(2, B)        # policy-major
    ratio = lane_total / opt["cost"][None, :]
    if not np.all(lane_total >= opt["cost"][None, :] * (1 - OPT_RTOL)):
        raise AssertionError(f"an online lane beat OPT: min ratio "
                             f"{float(ratio.min())}")

    rows = np.random.default_rng(seed + 2).choice(B, n_ref, replace=False)
    x, c, _, _ = S.materialize(rows_of(sc, rows), T, chunk_size=chunk)
    worst = 0.0
    with jax.default_device(ref_device):
        for j, i in enumerate(rows):
            ref = offline_opt(costs[i], x[j], c[j])
            worst = max(worst, _rel_err(opt["cost"][i], ref.cost))
    if worst > REF_RTOL:
        raise AssertionError(f"OPT vs offline_opt: rel err {worst} > "
                             f"{REF_RTOL}")
    return {"B": B, "T": T, "chunk": chunk, "lanes": ["alpha-RR", "RR"],
            "min_lane_over_opt": float(ratio.min()),
            "mean_lane_over_opt": [float(v) for v in ratio.mean(axis=1)],
            "ref_rows": [int(i) for i in rows],
            "opt_vs_offline_opt_cpu_max_rel_err": worst,
            "setup_s": setup_s}


def _kernels_compiled(B: int, chunk: int, K: int) -> bool:
    """Both hosting kernels, vmapped over B as the engine runs them, lower
    to a Mosaic custom call (not the interpreter)."""
    import jax.numpy as jnp

    from repro.kernels.hosting import dp_minplus_kc, slot_uniform_tc

    f32 = jnp.float32
    dp = jax.jit(jax.vmap(dp_minplus_kc)).lower(
        jax.ShapeDtypeStruct((B, K), f32),
        jax.ShapeDtypeStruct((B, chunk, K), f32),
        jax.ShapeDtypeStruct((B, K, K), f32),
        jax.ShapeDtypeStruct((B,), jnp.int32)).compile()
    prng = jax.jit(jax.vmap(lambda k, t: slot_uniform_tc(k, t, 1),
                            in_axes=(0, None))).lower(
        jax.ShapeDtypeStruct((B, 2), jnp.uint32),
        jax.ShapeDtypeStruct((chunk,), jnp.int32)).compile()
    return all("tpu_custom_call" in p.as_text() for p in (dp, prng))


def _kernels_at_odd_shapes(seed: int) -> list:
    """Both kernels, vmapped, at unaligned (B, chunk, K) with frozen tails
    and counter offsets, bitwise against their XLA references."""
    import jax.numpy as jnp

    from repro.core.policies.offline_opt import dp_fetch_matrix, dp_fwd_chunk
    from repro.core.scenarios.base import prng_dispatch, slot_uniform

    rng = np.random.default_rng(seed)
    shapes = [(5, 1000, 5), (3, 37, 8), (2, 4096, 3)]
    for B, chunk, K in shapes:
        lv = jnp.asarray(np.sort(rng.random((B, K)), axis=1), jnp.float32)
        M = jnp.asarray(rng.uniform(2, 8, B), jnp.float32)
        args = (jnp.asarray(rng.uniform(0, 5, (B, K)), jnp.float32),
                1000 + jnp.arange(chunk, dtype=jnp.int32),
                jnp.asarray(rng.uniform(0.1, 2, (B, chunk)), jnp.float32),
                jnp.asarray(rng.uniform(0, 3, (B, chunk, K)), jnp.float32),
                lv, jnp.arange(K)[None, :] < rng.integers(2, K + 1, (B, 1)),
                jax.vmap(dp_fetch_matrix)(M, lv),
                jnp.asarray(1000 + rng.integers(0, chunk + 1, B), jnp.int32))
        out = {}
        for backend in ("xla", "pallas"):
            out[backend] = jax.jit(jax.vmap(
                lambda *a, b=backend: dp_fwd_chunk(*a, backend=b),
                in_axes=(0, None, 0, 0, 0, 0, 0, 0)))(*args)
        for a, b in zip(out["pallas"], out["xla"]):
            _assert_equal(f"dp_minplus_kc at {(B, chunk, K)}", a, b)
        keys = jax.random.split(jax.random.PRNGKey(seed + B), B)
        tids = 12345 + jnp.arange(chunk, dtype=jnp.int32)
        draw = {}
        for backend in ("xla", "pallas"):
            with prng_dispatch(backend):
                draw[backend] = jax.jit(jax.vmap(
                    lambda k: slot_uniform(k, tids, 3)))(keys)
        _assert_equal(f"slot_uniform_tc at {(B, chunk)}", draw["pallas"],
                      draw["xla"])
    return shapes


def pallas_phase(B: int, T: int, chunk: int, seed: int) -> dict:
    from repro.kernels.utils import default_interpret

    if default_interpret() or not _kernels_compiled(B, chunk, 3):
        raise AssertionError("the Pallas kernels did not compile to Mosaic")
    odd = _kernels_at_odd_shapes(seed)
    _, _, on_x, opt_x, setup_x = _offline_legs(
        B, T, chunk, seed, collect_schedule=True)
    _, _, on_p, opt_p, setup_p = _offline_legs(
        B, T, chunk, seed, backend="pallas", collect_schedule=True)
    for k in on_x:
        _assert_equal(f"pallas run_fleet {k}", on_p[k], on_x[k])
    for k in opt_x:
        _assert_equal(f"pallas offline_opt_fleet {k}", opt_p[k], opt_x[k])
    return {"B": B, "T": T, "chunk": chunk, "kernels_compiled": True,
            "kernel_shapes_bitwise": odd,
            "pallas_vs_xla": "bitwise (lane costs, OPT cost, schedule, "
                             "schedule costs)",
            "setup_s": setup_x + setup_p}


def mesh_phase(B: int, T: int, chunk: int, seed: int, n_dev: int) -> dict:
    from repro.sharding.specs import fleet_mesh

    devs = jax.devices()
    if len(devs) < n_dev:
        raise AssertionError(f"{n_dev} devices needed, {len(devs)} found")
    _, _, on_1, opt_1, setup_1 = _offline_legs(
        B, T, chunk, seed, mesh=fleet_mesh(devs[:1]))
    _, _, on_n, opt_n, setup_n = _offline_legs(
        B, T, chunk, seed, mesh=fleet_mesh(devs[:n_dev]))
    for k in on_1:
        _assert_equal(f"{n_dev}-device run_fleet {k}", on_n[k], on_1[k])
    _assert_equal(f"{n_dev}-device OPT cost", opt_n["cost"], opt_1["cost"])
    return {"B": B, "T": T, "chunk": chunk, "devices": n_dev,
            "padded_rows": (-B) % n_dev,
            "mesh_vs_one_device": "bitwise (lane costs, OPT cost)",
            "setup_s": setup_1 + setup_n}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (default device: "
              f"{dev.platform}); nothing was run", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(json.dumps({"device": device, "compile_cache":
                      enable_compile_cache(), "seed": args.seed}),
          flush=True)
    cpu = jax.devices("cpu")[0]
    s = args.seed
    if args.chips == 4:
        phases = [(f"mesh4_B{b}",
                   lambda b=b: mesh_phase(b, T_OFFLINE, CHUNK, s, 4))
                  for b in (B_FLEET, B_UNEVEN)]
    else:
        phases = [
            ("live", lambda: live_phase(B_FLEET, LIVE_SLOTS, s, N_REF_ROWS,
                                        cpu)),
            ("offline", lambda: offline_phase(B_FLEET, T_OFFLINE, CHUNK, s,
                                              N_REF_ROWS, cpu)),
            ("pallas", lambda: pallas_phase(B_FLEET, T_PALLAS, CHUNK, s)),
        ]
    ok = True
    for name, run in phases:
        try:
            rec = {"phase": name, "ok": True, "device_kind": dev.device_kind,
                   **run()}
        except Exception as e:  # report the phase, keep going, fail at end
            traceback.print_exc()
            rec = {"phase": name, "ok": False, "device_kind": dev.device_kind,
                   "error": f"{type(e).__name__}: {e}"[:2000]}
            ok = False
        print(json.dumps(rec), flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
