"""Kernel microbenchmarks: wall time of the interpret-mode Pallas kernels vs
their jnp oracles (correctness-weighted; CPU wall times are NOT TPU
projections — see the roofline table for the perf story), plus the hosting
engine's throughput axes:

* ``hosting_batch_throughput`` — one jit(vmap(scan)) vs the per-instance
  Python loop it replaced (PR 1's acceptance number);
* ``fleet_throughput`` — the B x devices axes of the fleet engine
  (core/fleet.py): fleet vs batched engine at 1 device in-process, and
  device scaling on a forced-CPU multi-device mesh in a subprocess (this
  process is pinned to one device; the CPU subprocess runs only when this
  process is on the CPU as well).  The scaling axis uses a wide batch
  (B >> devices): the per-slot math vectorises across B on one core, so
  sharding only wins wall-clock once per-step work dominates scan-step
  overhead.
* ``scenario_fused_throughput`` — fused on-device generation
  (``run_fleet(scenario=...)``) vs the host-materialize-then-``stream=True``
  pipeline at long T: same keys, same workload, same chunk size, identical
  results.  The end-to-end ratio (``fused_vs_host_e2e``) counts what each
  path actually does to go from keys to totals — the fused path generates
  inside the scan (O(B * chunk) device memory, zero observation bytes
  shipped per chunk), the host path materializes a [B, T] obs array and
  streams slabs.  ``fused_vs_stream`` isolates the sim-only phase (obs
  already materialized): on CPU the "transfer" is a memcpy, so that ratio
  is the floor of the accelerator-side story, not the win.
* ``mc_driver_throughput`` — the Monte-Carlo seed axis
  (``run_fleet(..., n_seeds=S)``, one compiled program over [B*S] replicas)
  vs the per-seed stacking path it replaced (S separate ``run_fleet``
  dispatches on seed-folded scenarios — the old benchmark-layer loop).
  Identical bits, so the row first *asserts* the seed-fold law on this
  workload, then reports slots x instances x seeds per second both ways.
  The row also carries the antithetic-pairs CI comparison
  (``antithetic_ci_ratio``): same S, ``antithetic=True`` replica pairs
  summarised by ``mc_summary(..., antithetic=True)`` pair-means vs the
  plain independent-seed CI — the variance-reduction number the ROADMAP
  open item asked for.
* ``offline_dp_streaming`` — the checkpointed two-pass offline DP
  (``offline_opt_fleet(checkpointed=True)``) vs the materialized
  [B, T, K]-backpointer path on the same fused scenario: bit-equality of
  cost/schedule asserted in-row, slots x instances/sec both ways, and the
  XLA-reported peak-temp-memory ratio (``offline_dp_memory_stats``) that
  the acceptance bar gates — the checkpointed core must never hold a
  [B, T, K] (or [B, T] backpointer) buffer.  In the full (non ``--fast``)
  run the row additionally completes a T = 10^6 cost-only solve
  (``long_T``) to pin the 10^6-10^7-horizon claim to a measured number.
* ``live_fleet_step`` — the live serving axis (``fleet_stepper``): a
  persistent donated-carry chunk=1 stepper admitting one slot of
  per-instance telemetry per call, measured at several fleet widths B —
  slots admitted/sec plus p50/p99 per-step latency (the real-time bound a
  deployment plans around).  Zero retraces across the measured steps is
  asserted in-row via ``STREAM_TRACES``.
* ``stream_overlap`` — async double-buffered ingestion
  (``run_fleet(..., stream=True, async_ingest=True)``, a prefetch thread
  device-putting slab n+1 while XLA executes slab n) vs the synchronous
  slab feed on the same wide workload; bit-equality of the two runs is
  asserted in-row (same slabs, same order — see ``core/ingest.py``).
* ``policy_fanout`` — the policy fan-out axis (``run_fleet(policies=
  [...])``): P ∈ {2, 4} policy families sharing ONE generated stream in
  one fused scan (each slab generated exactly once and stepped by every
  lane) vs P separate ``run_fleet`` dispatches that each regenerate the
  identical counter-keyed stream.  Bit-equality of every lane against its
  standalone run is asserted in-row (the tentpole invariant); the row
  reports ``fanout_vs_separate`` (P=4 headline, same-machine
  engine-vs-engine) and the generation passes saved per sweep.
* ``multi_service`` — the service axis (``core/services.py``): B instances
  x N services as per-service fleet lanes (rows b*N+n) plus the
  capacity-respecting joint DP on the matrix-M joint grid.  The row first
  *asserts* the axis's two correctness claims — N=1 collapses to the
  single-service engine bit-for-bit (``run_fleet_services`` vs
  ``run_fleet``, ``offline_opt_services`` vs ``offline_opt_fleet``), and
  the joint DP equals the brute-force J**T oracle with exact float
  equality — then reports the lane-engine rate (slots x lanes/sec, the
  guarded key) and the joint DP's wall time (informational).
* ``multihost_scaling`` — the process axis of the fleet engine, FULL mode
  on the CPU only (``--fast`` and a process on an accelerator emit a row
  with null ratios: the cluster
  spawn + two-leg compile dominates a fast run, and the cross-process
  bit-equality claim stays covered by tests/test_multihost.py): a
  2-process local JAX cluster (``sharding.distributed.run_local_cluster``,
  each process feeding only its own [B_local, chunk] slab shard) vs a
  1-process run of the same global workload, both in subprocess workers so
  the legs share an identical environment.  Bit-equality of the
  ``gather=True`` global totals across legs is asserted in-row; the ratio
  is cores-dependent (two processes need two cores to overlap) so, like
  ``scaling_vs_1dev``, only the rates feed the regression gate.
* ``dp_minplus_kernel`` / ``counter_prng_kernel`` — the hosting Pallas
  kernels (``kernels.hosting``) vs their canonical XLA references, on the
  exact chunk ops the fleet engine dispatches through ``dp_backend=`` /
  ``prng_backend=``.  Each row asserts bit-equality in-row (the portable
  claim), records both rates plus the speedup ratio, and labels the
  ``backend`` ("pallas-interpret" on CPU — wall time there is NOT an
  accelerator projection) and ``device_kind``; ``check()`` gates the
  ratio only on a compiled backend.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref

FLEET_SCALE_B = 8192
FLEET_SCALE_T = 256
FLEET_SCALE_DEVICES = 4


def _time(fn, *args, reps=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        fn(*args).block_until_ready()
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
    return (time.time() - t0) / reps * 1e6


def _workload_costs(B):
    """The one hosting-instance mix every throughput row measures on."""
    from repro.core.costs import HostingCosts
    return [HostingCosts.three_level(M=float(5 + 5 * (i % 4)),
                                     alpha=0.25 + 0.05 * (i % 3),
                                     g_alpha=0.4)
            for i in range(B)]


def _workload_traces(B, T, seed=0):
    """Bernoulli arrivals + ARMA spot rents, one independent draw per
    instance (the PR-1 benchmark workload)."""
    from repro.core import arrivals, rentcosts
    kx, kc = jax.random.split(jax.random.PRNGKey(seed))
    x = np.stack([np.asarray(arrivals.bernoulli(jax.random.fold_in(kx, i),
                                                0.35, T))
                  for i in range(B)])
    c = np.stack([np.asarray(rentcosts.aws_spot_like(jax.random.fold_in(kc, i),
                                                     0.35, T))
                  for i in range(B)])
    return x, c


def hosting_batch_throughput(B=64, T=4096, reps=5, seed=0):
    """Batched engine vs per-instance loop on B alpha-RR instances."""
    from repro.core.costs import HostingGrid
    from repro.core.policies import AlphaRR
    from repro.core.simulator import run_policy, run_policy_batch

    costs_list = _workload_costs(B)
    x, c = _workload_traces(B, T, seed)
    grid = HostingGrid.from_costs(costs_list)
    fns = AlphaRR.batch(grid)

    run_policy_batch(fns, grid, x, c)                  # warm the jit cache
    t0 = time.time()
    for _ in range(reps):
        run_policy_batch(fns, grid, x, c)
    batched_s = (time.time() - t0) / reps

    policies = [AlphaRR(cc) for cc in costs_list]
    # one call warms the per-T compile; all instances share the cached core
    run_policy(policies[0], costs_list[0], x[0], c[0])
    t0 = time.time()
    for i in range(B):
        run_policy(policies[i], costs_list[i], x[i], c[i])
    loop_s = time.time() - t0

    slots = B * T
    return {
        "name": "hosting_batch_throughput",
        "B": B, "T": T,
        "batched_slots_instances_per_sec": slots / batched_s,
        "loop_slots_instances_per_sec": slots / loop_s,
        "speedup_vs_loop": loop_s / batched_s,
    }


def _fleet_scale_workload(B, T, seed=0):
    """Wide-batch workload for the device-scaling axis (numpy RNG: sampling
    8k ARMA traces through jax scans would dwarf the measurement)."""
    from repro.core.costs import HostingGrid
    from repro.core.fleet import FleetBatch
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 2, (B, T))
    c = rng.uniform(0.1, 0.6, (B, T))
    grid = HostingGrid.from_costs(_workload_costs(B))
    return FleetBatch.from_dense(grid, x, c)


def _time_fleet(fleet, mesh, reps):
    from repro.core.fleet import run_fleet
    from repro.core.policies import AlphaRR
    fns = AlphaRR.fleet(fleet)
    run_fleet(fns, fleet, mesh=mesh)               # warm the jit cache
    t0 = time.time()
    for _ in range(reps):
        run_fleet(fns, fleet, mesh=mesh)
    return (time.time() - t0) / reps


def _fleet_scaling_main(B, T, reps):
    """Subprocess entry (forced multi-device CPU): 1-device vs all-device
    end-to-end run_fleet wall time on the same wide batch; prints JSON."""
    from repro.sharding.specs import fleet_mesh
    fleet = _fleet_scale_workload(B, T)
    t_1 = _time_fleet(fleet, fleet_mesh(jax.devices()[:1]), reps)
    t_n = _time_fleet(fleet, fleet_mesh(), reps)
    print(json.dumps({"devices": jax.device_count(),
                      "slots_per_sec_1dev": B * T / t_1,
                      "slots_per_sec_ndev": B * T / t_n,
                      "scaling_vs_1dev": t_1 / t_n}))


def _cpu_child_legs_allowed() -> bool:
    """The forced-CPU child legs (device scaling, the process cluster)
    measure the CPU backend.  They run only when this process is itself on
    the CPU: a parent that holds an accelerator keeps it, and a child there
    would quietly measure the host instead."""
    return jax.default_backend() == "cpu"


def _fleet_scaling_subprocess(B, T, reps, devices):
    env = dict(os.environ)
    # append: keep any reproducibility flags the caller set
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={devices}"
                        ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.kernel_bench",
         "--fleet-scaling", str(B), str(T), str(reps)],
        env=env, capture_output=True, text=True, timeout=900,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    if out.returncode != 0:
        raise RuntimeError("fleet scaling leg failed: "
                           + (out.stderr or out.stdout).strip()[-400:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def fleet_throughput(B=64, T=4096, reps=5, seed=0,
                     scale_B=FLEET_SCALE_B, scale_T=FLEET_SCALE_T,
                     scale_devices=FLEET_SCALE_DEVICES):
    """Fleet engine vs batched engine at 1 device, plus multi-device scaling.

    The 1-device comparison reuses ``hosting_batch_throughput``'s exact
    workload (``_workload_costs`` + ``_workload_traces``) so the two rows
    are directly comparable; the scaling run uses the wide
    [scale_B, scale_T] batch in a forced-``scale_devices``-CPU subprocess,
    only when this process is on the CPU too (``scaling_vs_1dev`` is None
    otherwise).  A failed scaling leg fails the row.
    """
    from repro.core.costs import HostingGrid
    from repro.core.fleet import FleetBatch
    from repro.core.policies import AlphaRR
    from repro.core.simulator import run_policy_batch
    from repro.sharding.specs import fleet_mesh

    costs_list = _workload_costs(B)
    x, c = _workload_traces(B, T, seed)
    grid = HostingGrid.from_costs(costs_list)
    fns = AlphaRR.batch(grid)
    run_policy_batch(fns, grid, x, c)              # warm the jit cache
    t0 = time.time()
    for _ in range(reps):
        run_policy_batch(fns, grid, x, c)
    batched_s = (time.time() - t0) / reps

    fleet = FleetBatch.from_dense(grid, x, c)
    # pin to ONE device: the row tracks the 1-device engine comparison even
    # if this process sees a multi-device platform
    fleet_s = _time_fleet(fleet, fleet_mesh(jax.devices()[:1]), reps)

    row = {
        "name": "fleet_throughput",
        "B": B, "T": T,
        "fleet_slots_instances_per_sec": B * T / fleet_s,
        "batched_slots_instances_per_sec": B * T / batched_s,
        "fleet_vs_batched_1dev": batched_s / fleet_s,
        "scale_B": scale_B, "scale_T": scale_T,
        "scale_devices": scale_devices,
    }
    if not _cpu_child_legs_allowed():
        row["scaling_vs_1dev"] = None
        return row
    scaling = _fleet_scaling_subprocess(scale_B, scale_T, max(3, reps // 2),
                                        scale_devices)
    row["scaling_vs_1dev"] = scaling["scaling_vs_1dev"]
    row["fleet_slots_instances_per_sec_multidev"] = \
        scaling["slots_per_sec_ndev"]
    return row


def scenario_fused_throughput(B=32, T=65536, chunk=4096, reps=3, seed=0):
    """Keys -> totals two ways: fused on-device generation in one program,
    vs materialize a [B, T] obs array then stream it (identical results;
    both trace-free so the compared work matches)."""
    from repro.core import scenarios as S
    from repro.core.costs import HostingGrid
    from repro.core.fleet import FleetBatch, run_fleet
    from repro.core.policies import AlphaRR

    grid = HostingGrid.from_costs(_workload_costs(B))
    kx, kc = jax.random.split(jax.random.PRNGKey(seed))
    sc = S.combine(S.bernoulli_arrivals(kx, 0.35, B),
                   S.spot_rents(S.split_keys(kc, B), 0.35, B))
    fleet = FleetBatch.for_scenario(grid, T)
    fns = AlphaRR.fleet(fleet)

    kw = dict(chunk_size=chunk, collect_trace=False)
    run_fleet(fns, fleet, scenario=sc, **kw)           # warm the jit cache
    t0 = time.time()
    for _ in range(reps):
        run_fleet(fns, fleet, scenario=sc, **kw)
    fused_s = (time.time() - t0) / reps

    FleetBatch.from_scenario(grid, sc, T, chunk_size=chunk)  # warm
    t0 = time.time()
    for _ in range(reps):
        fleet_m = FleetBatch.from_scenario(grid, sc, T, chunk_size=chunk)
    materialize_s = (time.time() - t0) / reps
    run_fleet(fns, fleet_m, stream=True, **kw)         # warm
    t0 = time.time()
    for _ in range(reps):
        run_fleet(fns, fleet_m, stream=True, **kw)
    stream_s = (time.time() - t0) / reps

    slots = B * T
    return {
        "name": "scenario_fused_throughput",
        "B": B, "T": T, "chunk": chunk,
        "fused_slots_instances_per_sec": slots / fused_s,
        "stream_slots_instances_per_sec": slots / stream_s,
        "fused_vs_host_e2e": (materialize_s + stream_s) / fused_s,
        "fused_vs_stream": stream_s / fused_s,
        "materialize_seconds": materialize_s,
    }


def mc_driver_throughput(B=64, S=4, T=2048, chunk=None, reps=3, seed=0):
    """Fused seed axis (one run_fleet over [B*S] replicas) vs the old
    per-seed stacking path (S sequential run_fleet dispatches, one per
    seed-folded scenario).  Both paths produce bit-identical totals —
    asserted here — so the ratio is pure driver overhead + vectorization
    width."""
    from repro.core import scenarios as S_
    from repro.core.costs import HostingGrid
    from repro.core.fleet import FleetBatch, run_fleet
    from repro.core.policies import AlphaRR

    grid = HostingGrid.from_costs(_workload_costs(B))
    kx, kc = jax.random.split(jax.random.PRNGKey(seed))
    sc = S_.combine(S_.bernoulli_arrivals(S_.split_keys(kx, B), 0.35, B),
                    S_.spot_rents(S_.split_keys(kc, B), 0.35, B))
    fleet = FleetBatch.for_scenario(grid, T)
    fns = AlphaRR.fleet(fleet)
    kw = dict(chunk_size=chunk, collect_trace=False)

    def fused():
        return run_fleet(fns, fleet, scenario=sc, n_seeds=S, **kw)

    def per_seed():
        return [run_fleet(fns, fleet, scenario=S_.with_seed(sc, s), **kw)
                for s in range(S)]

    f = fused()                                    # warm the jit caches
    rs = per_seed()
    # the seed-fold law on this exact workload: fused row (b, s) == the
    # standalone seed-s run's row b, bit for bit
    fv = f.seed_view(f.total)
    assert all(np.array_equal(fv[:, s], rs[s].total) for s in range(S))

    t0 = time.time()
    for _ in range(reps):
        fused()
    fused_s = (time.time() - t0) / reps
    t0 = time.time()
    for _ in range(reps):
        per_seed()
    stacked_s = (time.time() - t0) / reps

    # antithetic seed pairs on a flip-capable workload: same seed budget,
    # replicas (2m, 2m+1) share a pair fold + flip, summarised with the
    # pair-mean estimator.  Measured where the design applies — a monotone
    # (rent-dominated static-policy) statistic at S >= 8, so the S/2
    # pair-means don't pay a dominating small-df t-quantile — and
    # deterministic for fixed keys, so the ratio is a stable tracked
    # number, not a flaky sample.
    from repro.core.fleet import mc_summary
    from repro.core.policies import StaticPolicy
    S_ci = max(8, 2 * S)
    sc_flip = S_.combine(
        S_.bernoulli_arrivals(S_.split_keys(kx, B), 0.35, B),
        S_.uniform_rents(S_.split_keys(kc, B), 0.35, 0.2, B))
    static = StaticPolicy.fleet(fleet, fleet.grid.top_index())
    plain = run_fleet(static, fleet, scenario=sc_flip, n_seeds=S_ci, **kw)
    anti = run_fleet(static, fleet, scenario=sc_flip, n_seeds=S_ci,
                     antithetic=True, **kw)
    ci_plain = float(np.mean(mc_summary(plain)["total_ci95"]))
    ci_anti = float(np.mean(
        mc_summary(anti, antithetic=True)["total_ci95"]))

    work = B * S * T
    return {
        "name": "mc_driver_throughput",
        "B": B, "S": S, "T": T,
        "fused_slots_instances_seeds_per_sec": work / fused_s,
        "per_seed_slots_instances_seeds_per_sec": work / stacked_s,
        "fused_vs_per_seed": stacked_s / fused_s,
        "S_ci": S_ci,
        "antithetic_ci_ratio": ci_anti / ci_plain,
    }


def offline_dp_streaming(B=8, T=65536, chunk=4096, reps=3, seed=0,
                         long_T=None):
    """Checkpointed two-pass offline DP vs the materialized-backpointer
    path, on one fused scenario workload: identical bits (asserted), wall
    time both ways, and the XLA peak-temp-memory ratio between the two
    compiled cores.  ``long_T`` additionally times a cost-only
    (``collect_schedule=False``) checkpointed solve at that horizon — the
    T = 10^6 acceptance run."""
    from repro.core import scenarios as S_
    from repro.core.costs import HostingGrid
    from repro.core.fleet import (FleetBatch, offline_dp_memory_stats,
                                  offline_opt_fleet)

    grid = HostingGrid.from_costs(_workload_costs(B))
    kx, kc = jax.random.split(jax.random.PRNGKey(seed))
    sc = S_.combine(S_.bernoulli_arrivals(S_.split_keys(kx, B), 0.35, B),
                    S_.spot_rents(S_.split_keys(kc, B), 0.35, B))
    fleet = FleetBatch.for_scenario(grid, T)

    def materialized():
        return offline_opt_fleet(fleet, scenario=sc, chunk_size=chunk)

    def checkpointed():
        return offline_opt_fleet(fleet, scenario=sc, chunk_size=chunk,
                                 checkpointed=True)

    base = materialized()                          # warm the jit caches
    ck = checkpointed()
    # the tentpole claim on this exact workload: checkpointed backtracking
    # is BIT-identical to the materialized table, cost and schedule
    identical = (np.array_equal(base.cost, ck.cost)
                 and np.array_equal(base.r_hist, ck.r_hist)
                 and np.array_equal(base.sim.total, ck.sim.total))
    assert identical

    t0 = time.time()
    for _ in range(reps):
        materialized()
    mat_s = (time.time() - t0) / reps
    t0 = time.time()
    for _ in range(reps):
        checkpointed()
    ck_s = (time.time() - t0) / reps

    mem_mat = offline_dp_memory_stats(fleet, scenario=sc, chunk_size=chunk)
    mem_ck = offline_dp_memory_stats(fleet, scenario=sc, chunk_size=chunk,
                                     checkpointed=True)
    slots = B * T
    row = {
        "name": "offline_dp_streaming",
        "B": B, "T": T, "chunk": chunk,
        "ckpt_slots_instances_per_sec": slots / ck_s,
        "materialized_slots_instances_per_sec": slots / mat_s,
        "ckpt_vs_materialized": mat_s / ck_s,
        "identical_bits": bool(identical),
        "materialized_temp_bytes": mem_mat["temp_bytes"],
        "ckpt_temp_bytes": mem_ck["temp_bytes"],
        "peak_mem_ratio": mem_mat["temp_bytes"] / mem_ck["temp_bytes"],
    }
    if long_T:
        fleet_long = FleetBatch.for_scenario(grid, int(long_T))
        t0 = time.time()
        offline_opt_fleet(fleet_long, scenario=sc, chunk_size=8192,
                          checkpointed=True, collect_schedule=False)
        row["long_T"] = int(long_T)
        row["long_T_cost_only_seconds"] = time.time() - t0
    return row


def live_fleet_step(widths=(64, 512), n_steps=200, warmup=5, seed=0):
    """Live serving loop: a persistent chunk=1 ``fleet_stepper`` admitting
    one telemetry slot per call, at several fleet widths B.  Reports slots
    admitted/sec and p50/p99 per-step latency per width (flat keys carry
    the widest configuration, which is what a deployment sizes against),
    and asserts IN-ROW that the measured steps triggered zero retraces."""
    from repro.core.costs import HostingGrid
    from repro.core.fleet import STREAM_TRACES, FleetBatch, fleet_stepper
    from repro.core.policies import AlphaRR

    rng = np.random.default_rng(seed)
    per_width = []
    for B in widths:
        grid = HostingGrid.from_costs(_workload_costs(B))
        fleet = FleetBatch.for_scenario(grid, 1 << 20)  # open-ended horizon
        st = fleet_stepper(AlphaRR.fleet(fleet), fleet, chunk_size=1)
        x = rng.integers(0, 3, (n_steps + warmup, B))
        c = rng.uniform(0.1, 2.0, (n_steps + warmup, B))
        for t in range(warmup):
            st.step(x=x[t], c=c[t])
        traces = dict(STREAM_TRACES)
        lat = np.empty(n_steps)
        for t in range(n_steps):
            t0 = time.time()
            st.step(x=x[warmup + t], c=c[warmup + t])
            lat[t] = time.time() - t0
        assert dict(STREAM_TRACES) == traces, "live stepper retraced"
        per_width.append({
            "B": B,
            "slots_admitted_per_sec": B / float(lat.mean()),
            "p50_step_latency_us": float(np.percentile(lat, 50) * 1e6),
            "p99_step_latency_us": float(np.percentile(lat, 99) * 1e6),
        })
    widest = per_width[-1]
    return {
        "name": "live_fleet_step",
        "widths": list(widths), "n_steps": n_steps,
        "per_width": per_width,
        "live_slots_admitted_per_sec": widest["slots_admitted_per_sec"],
        "p50_step_latency_us": widest["p50_step_latency_us"],
        "p99_step_latency_us": widest["p99_step_latency_us"],
        "zero_retraces": True,
    }


def stream_overlap(B=256, T=65536, chunk=4096, reps=3, seed=0):
    """Async double-buffered ingestion vs the synchronous slab feed on one
    wide obs-backed streamed workload (``run_fleet(..., stream=True)``).
    Bit-equality of the two runs is asserted in-row; both rates and the
    async/sync ratio are reported.  The ratio is machine-dependent (it
    needs a spare core for the prefetch thread), so only the rates feed
    the regression gate — see check_regression.RATIO_KEYS."""
    from repro.core.fleet import run_fleet
    from repro.core.policies import AlphaRR

    fleet = _fleet_scale_workload(B, T, seed)
    fns = AlphaRR.fleet(fleet)
    kw = dict(chunk_size=chunk, stream=True, collect_trace=False)

    sync = run_fleet(fns, fleet, **kw)                 # warm the jit cache
    asyn = run_fleet(fns, fleet, async_ingest=True, **kw)
    identical = (np.array_equal(sync.total, asyn.total)
                 and np.array_equal(sync.level_slots, asyn.level_slots))
    assert identical

    t0 = time.time()
    for _ in range(reps):
        run_fleet(fns, fleet, **kw)
    sync_s = (time.time() - t0) / reps
    t0 = time.time()
    for _ in range(reps):
        run_fleet(fns, fleet, async_ingest=True, **kw)
    async_s = (time.time() - t0) / reps

    slots = B * T
    return {
        "name": "stream_overlap",
        "B": B, "T": T, "chunk": chunk,
        "identical_bits": bool(identical),
        "sync_stream_slots_instances_per_sec": slots / sync_s,
        "async_stream_slots_instances_per_sec": slots / async_s,
        "async_vs_sync": sync_s / async_s,
    }


def _multihost_shard_workload(lo, hi, T):
    """Global rows [lo, hi) of the multihost-scaling workload: every row's
    trace comes from its own per-GLOBAL-row generator, so any process
    count partitions the identical global fleet (the bit-equality assert
    across legs needs nothing more)."""
    from repro.core.costs import HostingCosts, HostingGrid
    from repro.core.fleet import FleetBatch
    costs = [HostingCosts.three_level(M=float(5 + 5 * (i % 4)),
                                      alpha=0.25 + 0.05 * (i % 3),
                                      g_alpha=0.4)
             for i in range(lo, hi)]
    B = hi - lo
    x = np.empty((B, T), np.int64)
    c = np.empty((B, T), np.float64)
    for j, i in enumerate(range(lo, hi)):
        rng = np.random.default_rng(1000 + i)
        x[j] = rng.integers(0, 2, T)
        c[j] = rng.uniform(0.1, 0.6, T)
    return FleetBatch.from_dense(HostingGrid.from_costs(costs), x, c)


def _multihost_worker_main(B, T, chunk, reps):
    """Cluster-worker entry for the multihost_scaling row: join the
    cluster (no-op in the 1-process leg), stream this process's shard of
    the global [B, T] fleet through ``run_fleet``, print JSON with the
    per-rep wall time and the gathered global totals."""
    from repro.sharding import distributed
    distributed.initialize()   # BEFORE any jax computation (engine imports
    from repro.core.fleet import run_fleet      # build jnp constants)
    from repro.core.policies import AlphaRR
    from repro.sharding.specs import fleet_mesh
    n, pid = jax.process_count(), jax.process_index()
    lo = pid * (B // n)
    fleet = _multihost_shard_workload(lo, lo + B // n, T)
    fns = AlphaRR.fleet(fleet)
    kw = dict(mesh=fleet_mesh(), chunk_size=chunk, stream=True,
              collect_trace=False)
    run_fleet(fns, fleet, **kw)                    # warm the jit cache
    t0 = time.time()
    for _ in range(reps):
        run_fleet(fns, fleet, **kw)
    dt = (time.time() - t0) / reps
    total = run_fleet(fns, fleet, gather=True, **kw).total
    print(json.dumps({"pid": pid, "n_processes": n, "seconds": dt,
                      "total": np.asarray(total, np.float64).tolist()}))
    distributed.shutdown()


def multihost_scaling(B=512, T=4096, chunk=1024, reps=3):
    """2-process local cluster vs 1 process on the same wide-B fleet, both
    legs in subprocess workers (identical environment; this process's JAX
    runtime stays single-process).  Asserts the gathered global totals are
    bit-identical across legs; reports aggregate slots/sec both ways and
    the scaling ratio.  The legs are CPU workers, so a parent that holds an
    accelerator skips them (explicit nulls, which check_regression skips
    with a note); a failed leg fails the row."""
    from repro.sharding import distributed
    argv = ["-m", "benchmarks.kernel_bench", "--multihost-worker",
            str(B), str(T), str(chunk), str(reps)]
    root = os.path.join(os.path.dirname(__file__), "..")
    row = {"name": "multihost_scaling", "B": B, "T": T, "chunk": chunk,
           "n_processes": 2}
    if not _cpu_child_legs_allowed():
        row.update({"multihost_scaling_vs_1proc": None,
                    "single_process_slots_instances_per_sec": None,
                    "multi_process_slots_instances_per_sec": None})
        return row
    legs = {}
    for n in (1, 2):
        outs = distributed.run_local_cluster(
            argv, n_processes=n, timeout=900, cwd=root)
        legs[n] = [json.loads(o.strip().splitlines()[-1]) for o in outs]
    # every worker gathered the full global totals; all must agree with
    # the 1-process leg bit for bit (json round-trips floats exactly)
    ref = legs[1][0]["total"]
    identical = all(w["total"] == ref for w in legs[2])
    assert identical
    t1 = legs[1][0]["seconds"]
    t2 = max(w["seconds"] for w in legs[2])        # slowest shard bounds
    slots = B * T
    row.update({
        "identical_bits": bool(identical),
        "single_process_slots_instances_per_sec": slots / t1,
        "multi_process_slots_instances_per_sec": slots / t2,
        "multihost_scaling_vs_1proc": t1 / t2,
    })
    return row


def policy_fanout(B=64, T=2048, chunk=None, reps=3, seed=0):
    """Shared-stream policy fan-out vs P separate ``run_fleet`` calls.

    P=2 is the classic figure pair {alpha-RR, RR-on-endpoints}; P=4 adds
    the static host-everything / host-nothing baselines.  Every lane of
    the fused run must be bit-identical to its standalone dispatch
    (asserted in-row, unconditionally); the separate path regenerates the
    identical counter-keyed stream P times, so the ratio is the
    generation + dispatch overhead the axis deletes — same-machine
    engine-vs-engine, gated > 1.0 at P=4 in ``check()``."""
    from repro.core import scenarios as S_
    from repro.core.costs import HostingGrid
    from repro.core.fleet import FleetBatch, run_fleet
    from repro.core.policies import AlphaRR, RetroRenting, StaticPolicy

    grid = HostingGrid.from_costs(_workload_costs(B))
    kx, kc = jax.random.split(jax.random.PRNGKey(seed))
    sc = S_.combine(S_.bernoulli_arrivals(S_.split_keys(kx, B), 0.35, B),
                    S_.spot_rents(S_.split_keys(kc, B), 0.35, B))
    fleet = FleetBatch.for_scenario(grid, T)
    efleet = FleetBatch.for_scenario(grid.restrict_to_endpoints(), T)
    rr_lane = RetroRenting.fleet_lane(fleet)
    lanes4 = [AlphaRR.fleet_lane(fleet), rr_lane,
              StaticPolicy.fleet(fleet, fleet.grid.top_index()),
              StaticPolicy.fleet(fleet, jnp.zeros(B, jnp.int32))]
    # each lane's standalone dispatch: the RR lane scores on its own
    # endpoint grid, so its separate leg runs on the endpoint fleet
    separate4 = [(lanes4[0].fns, fleet), (rr_lane.fns, efleet),
                 (lanes4[2], fleet), (lanes4[3], fleet)]
    kw = dict(scenario=sc, chunk_size=chunk, collect_trace=False)

    row = {"name": "policy_fanout", "B": B, "T": T}
    identical = True
    for P in (2, 4):
        lanes, seps = lanes4[:P], separate4[:P]
        fused = run_fleet(lanes, fleet, **kw)          # warm the jit caches
        singles = [run_fleet(fns, fl, **kw) for fns, fl in seps]
        pv = fused.policy_view(fused.total)
        for p, res in enumerate(singles):
            identical = (identical and np.array_equal(pv[p], res.total)
                         and np.array_equal(
                             fused.policy_view(fused.level_slots)[p]
                             [:, :res.level_slots.shape[1]],
                             res.level_slots))
        assert identical

        t0 = time.time()
        for _ in range(reps):
            run_fleet(lanes, fleet, **kw)
        fanout_s = (time.time() - t0) / reps
        t0 = time.time()
        for _ in range(reps):
            for fns, fl in seps:
                run_fleet(fns, fl, **kw)
        separate_s = (time.time() - t0) / reps
        row[f"fanout_vs_separate_p{P}"] = separate_s / fanout_s
        row[f"fanout_p{P}_slots_instances_per_sec"] = P * B * T / fanout_s

    row.update({
        "identical_bits": bool(identical),
        # the committed-baseline rate key the regression gate tracks
        "slots_instances_per_sec": row["fanout_p4_slots_instances_per_sec"],
        "fanout_vs_separate": row["fanout_vs_separate_p4"],
        # the separate path generates the stream once per policy; the
        # fused scan generates it once, full stop
        "generation_passes_saved": 4 - 1,
    })
    return row


def _hosting_backend_env():
    """(backend label, device kind) for the hosting-kernel rows.  On CPU
    the only executable Pallas path is interpret mode — labelled
    "pallas-interpret" so the perf gate and check() can tell the modes
    apart (interpret wall time is NOT an accelerator projection; the
    bit-identity assert is the portable part of the row)."""
    from repro.kernels.utils import default_interpret
    interpret = default_interpret()
    return ("pallas-interpret" if interpret else "pallas",
            jax.devices()[0].device_kind, interpret)


def dp_minplus_kernel(B=8, K=8, chunk=2048, reps=5, seed=0):
    """Fused DP min-plus kernel vs the canonical lax.scan reference on one
    [B]-vmapped chunk relaxation (the exact op ``offline_opt_fleet`` runs
    per chunk per instance).  Bit-equality of (J', argmin table) is
    asserted in-row; both rates are recorded and the ratio is gated in
    ``check()`` only on a compiled (non-interpret) backend."""
    from repro.core.policies.offline_opt import (dp_fetch_matrix,
                                                 dp_frontier0, dp_fwd_chunk)
    backend, device_kind, interpret = _hosting_backend_env()

    rng = np.random.default_rng(seed)
    lv32 = jnp.asarray(np.sort(rng.random((B, K)), axis=1).astype(np.float32))
    fetch = jax.vmap(dp_fetch_matrix)(
        jnp.asarray(rng.uniform(2, 8, B).astype(np.float32)), lv32)
    kmask = jnp.asarray(rng.integers(2, K + 1, B))[:, None] > jnp.arange(K)
    cck = jnp.asarray(rng.uniform(0.1, 2.0, (B, chunk)).astype(np.float32))
    sck = jnp.asarray(rng.uniform(0, 3.0, (B, chunk, K)).astype(np.float32))
    T_len = jnp.asarray(rng.integers(chunk // 2, chunk + 1, B), jnp.int32)
    J = jnp.broadcast_to(dp_frontier0(K), (B, K))
    tids = jnp.arange(chunk, dtype=jnp.int32)

    def make(bk):
        fn = jax.jit(jax.vmap(
            lambda j, c, s, lv, km, f, tl: dp_fwd_chunk(
                j, tids, c, s, lv, km, f, tl, bk),
            in_axes=(0, 0, 0, 0, 0, 0, 0)))
        return lambda: fn(J, cck, sck, lv32, kmask, fetch, T_len)

    xla, pallas = make("xla"), make("pallas")
    Jx, ax = jax.tree_util.tree_map(np.asarray, xla())
    Jp, ap = jax.tree_util.tree_map(np.asarray, pallas())
    identical = np.array_equal(Jx, Jp) and np.array_equal(ax, ap)
    assert identical

    def clock(fn):
        fn()[0].block_until_ready()
        t0 = time.time()
        for _ in range(reps):
            fn()[0].block_until_ready()
        return (time.time() - t0) / reps

    xla_s, pallas_s = clock(xla), clock(pallas)
    slots = B * chunk
    return {
        "name": "dp_minplus_kernel",
        "B": B, "K": K, "chunk": chunk,
        "backend": backend, "device_kind": device_kind,
        "identical_bits": bool(identical),
        "xla_dp_slots_instances_per_sec": slots / xla_s,
        "pallas_dp_slots_instances_per_sec": slots / pallas_s,
        "dp_pallas_vs_xla": xla_s / pallas_s,
    }


def counter_prng_kernel(B=8, chunk=65536, reps=5, seed=0):
    """Fused threefry counter-PRNG kernel vs the vmapped ``jax.random``
    fold/salt/uniform chain (the exact ``slot_uniform`` op the hot streams
    draw through).  Bit-equality asserted in-row; ratio gated only on a
    compiled backend, like the DP row."""
    from repro.core.scenarios.base import slot_uniform
    backend, device_kind, interpret = _hosting_backend_env()

    keys = jax.random.split(jax.random.PRNGKey(seed), B)
    tids = jnp.arange(chunk, dtype=jnp.int32)
    salt = 1

    xla = jax.jit(jax.vmap(lambda k: slot_uniform(k, tids, salt)))
    pallas = jax.jit(lambda ks: ops.counter_uniforms(ks, tids, salt=salt,
                                                     interpret=interpret))
    ux = np.asarray(xla(keys))
    up = np.asarray(pallas(jnp.asarray(keys, jnp.uint32)))
    identical = np.array_equal(ux, up)
    assert identical

    def clock(fn, arg):
        fn(arg).block_until_ready()
        t0 = time.time()
        for _ in range(reps):
            fn(arg).block_until_ready()
        return (time.time() - t0) / reps

    xla_s = clock(xla, keys)
    pallas_s = clock(pallas, jnp.asarray(keys, jnp.uint32))
    draws = B * chunk
    return {
        "name": "counter_prng_kernel",
        "B": B, "chunk": chunk,
        "backend": backend, "device_kind": device_kind,
        "identical_bits": bool(identical),
        "xla_prng_draws_per_sec": draws / xla_s,
        "pallas_prng_draws_per_sec": draws / pallas_s,
        "prng_pallas_vs_xla": xla_s / pallas_s,
    }


def multi_service(B=2, N=2, T=2048, chunk=1024, reps=3, seed=0):
    """Multi-service axis row (``core.services``): per-service lane-engine
    throughput at B instances x N services, with the service-axis
    correctness claims asserted in-row before any timing is reported:

    * **N=1 identity** — ``run_fleet_services`` / ``offline_opt_services``
      on a one-service fleet are bit-identical to the single-service
      ``run_fleet`` / ``offline_opt_fleet`` (exact bits, never allclose);
    * **joint DP == oracle** — the capacity-respecting joint DP through
      the fleet engine's matrix-M grid equals the brute-force ``J**T``
      enumeration with EXACT float equality on a tiny N x K grid.

    The guarded rate is lane slots x lanes per second; ``joint_states``
    records the per-instance joint grid width the DP leg solved, and
    ``joint_dp_seconds`` the checkpointed joint DP's wall time (recorded,
    not gated — it scales with J**2 and is tiny at bench sizes).
    """
    from repro.core import scenarios as S_
    from repro.core import services as SV
    from repro.core.costs import HostingCosts, HostingGrid, ServiceSet
    from repro.core.fleet import FleetBatch, offline_opt_fleet, run_fleet
    from repro.core.policies import AlphaRR
    from repro.core.policies.offline_opt import brute_force_joint_opt
    from repro.core.scenarios.base import materialize

    def scn(grid, rows, s):
        return S_.combine(
            S_.ge_arrivals(S_.split_keys(jax.random.PRNGKey(s), rows),
                           0.3, 0.2, 2.0, 0.2, rows),
            S_.spot_rents(jax.random.PRNGKey(s + 1), 0.5, rows),
            svc=S_.model2_service(jax.random.PRNGKey(s + 2), grid.g, rows,
                                  max_per_slot=6))

    # ---- in-row assert 1: N=1 bitwise identity (small, fast) ----------
    costs1 = [HostingCosts.three_level(4.0, 0.3, 0.4),
              HostingCosts.two_level(5.0)]
    grid1 = HostingGrid.from_costs(costs1)
    fleet1 = FleetBatch.for_scenario(grid1, 256)
    sf1 = SV.service_fleet([ServiceSet(services=(cc,)) for cc in costs1],
                           256)
    sc1 = scn(grid1, 2, seed)
    ref = run_fleet(AlphaRR.fleet(fleet1), fleet1, scenario=sc1,
                    chunk_size=128)
    got = SV.run_fleet_services(SV.alpha_rr_per_service(sf1), sf1,
                                scenario=sc1, chunk_size=128)
    identical = all(
        np.array_equal(np.asarray(getattr(got.fleet, f)),
                       np.asarray(getattr(ref, f)))
        for f in ("total", "rent", "service", "fetch", "r_hist"))
    oref = offline_opt_fleet(fleet1, scenario=sc1, chunk_size=128)
    ogot = SV.offline_opt_services(sf1, scenario=sc1, chunk_size=128)
    identical = identical and np.array_equal(np.asarray(ogot.cost),
                                             np.asarray(oref.cost))
    assert identical

    # ---- in-row assert 2: joint DP == brute-force oracle --------------
    T_o = 5
    ss = ServiceSet((HostingCosts.three_level(3.0, 0.5, 0.4),
                     HostingCosts.two_level(2.5)), capacity=1.0)
    sfo = SV.service_fleet([ss], T_o)
    sco = scn(sfo.lane_grid(), 2, seed + 7)
    jres = SV.offline_opt_services(sfo, scenario=sco)
    x, c, svc, _ = materialize(sco, T_o, chunk_size=T_o)
    svcs = [svc[n][:, :ss.services[n].K] for n in range(2)]
    oracle = brute_force_joint_opt(ss, x[:2], c[0], svcs=svcs)
    oracle_ok = (float(np.asarray(jres.cost)[0]) == float(oracle.cost)
                 and np.array_equal(jres.service_schedules()[0],
                                    oracle.r_hist))
    assert oracle_ok
    identical = bool(identical and oracle_ok)

    # ---- lane-engine throughput at B x N ------------------------------
    sets = [ServiceSet(tuple(HostingCosts.three_level(4.0 + i + n, 0.3, 0.4)
                             for n in range(N)), capacity=1.0)
            for i in range(B)]
    sf = SV.service_fleet(sets, T)
    sc = scn(sf.lane_grid(), B * N, seed + 13)
    pol = SV.alpha_rr_per_service(sf)
    kw = dict(scenario=sc, chunk_size=chunk, collect_trace=False)

    SV.run_fleet_services(pol, sf, **kw)         # warm the jit caches
    t0 = time.time()
    for _ in range(reps):
        SV.run_fleet_services(pol, sf, **kw)
    lane_s = (time.time() - t0) / reps

    t0 = time.time()
    SV.offline_opt_services(sf, scenario=sc, chunk_size=chunk,
                            checkpointed=True, collect_schedule=False)
    joint_dp_s = time.time() - t0

    return {
        "name": "multi_service",
        "B": B, "T": T, "n_services": N, "chunk": chunk,
        "joint_states": int(sf.joint_grid().M.shape[-1]),
        "identical_bits": bool(identical),
        "slots_instances_per_sec": B * N * T / lane_s,
        "joint_dp_seconds": joint_dp_s,
    }


def run(T=4096):
    # run.py --fast passes a small T, shrinking the in-process throughput
    # rows; the scaling subprocess keeps its fixed wide-B workload (device
    # scaling is meaningless on a thin batch — see fleet_throughput)
    rows = []
    rows.append(hosting_batch_throughput(T=T))
    rows.append(fleet_throughput(T=T))
    # long-T axis: 16x the in-process T, chunked; --fast shrinks with T
    rows.append(scenario_fused_throughput(T=16 * T, chunk=min(4096, 4 * T)))
    rows.append(mc_driver_throughput(T=T // 2))
    # checkpointed offline DP: same long-T axis as the fused row; the full
    # run (default T) additionally prices a T=1e6 cost-only fleet — the
    # 10^6-horizon acceptance number (--fast shrinks T and skips it)
    rows.append(offline_dp_streaming(T=16 * T, chunk=min(4096, 4 * T),
                                     long_T=10**6 if T >= 4096 else None))
    # live serving + async ingestion axes; --fast shrinks the step count
    # and the streamed horizon with T
    rows.append(live_fleet_step(n_steps=max(40, min(200, T // 20))))
    rows.append(stream_overlap(T=16 * T, chunk=min(4096, 4 * T)))
    # policy fan-out axis: P families on one generated stream; --fast
    # shrinks the horizon with T (the in-row bit-equality asserts run in
    # both modes)
    rows.append(policy_fanout(T=T // 2, chunk=min(1024, T // 4)))
    # service axis: B x N per-service lanes plus the joint capacity DP;
    # the N=1 bitwise identity and joint-DP-vs-oracle asserts run in both
    # modes (they are small fixed-size legs, not scaled by T)
    rows.append(multi_service(T=T // 2, chunk=min(1024, T // 4)))
    # process axis: 2-process local cluster vs 1 process — FULL mode only:
    # the cluster spawn + two-leg compile is most of a --fast run's wall
    # time, and the cross-process bit-equality claim stays covered by
    # tests/test_multihost.py.  Fast mode emits a skip-marker row so the
    # schema (and check()'s one-row-per-name invariant) is mode-invariant.
    if T >= 4096:
        rows.append(multihost_scaling(T=T, chunk=min(1024, T // 4)))
    else:
        rows.append({"name": "multihost_scaling", "skipped_fast": True,
                     "multihost_scaling_vs_1proc": None,
                     "single_process_slots_instances_per_sec": None,
                     "multi_process_slots_instances_per_sec": None})
    # hosting-kernel backend rows: sizes track T so --fast stays fast
    rows.append(dp_minplus_kernel(chunk=min(2048, T // 2)))
    rows.append(counter_prng_kernel(chunk=min(65536, 16 * T)))
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 2, 64), jnp.float32)
    rows.append({"name": "flash_attention_pallas_interp_us",
                 "us": _time(lambda a, b, c: ops.flash_attention(a, b, c), q, k, v)})
    rows.append({"name": "flash_attention_ref_us",
                 "us": _time(lambda a, b, c: ref.flash_attention_ref(a, b, c), q, k, v)})
    x = jax.random.normal(ks[0], (1, 256, 4, 32), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, 256, 4)))
    A = -jnp.exp(jax.random.normal(ks[2], (4,)) * 0.3)
    B = jax.random.normal(ks[1], (1, 256, 1, 32), jnp.float32)
    C = jax.random.normal(ks[2], (1, 256, 1, 32), jnp.float32)
    rows.append({"name": "ssd_scan_pallas_interp_us",
                 "us": _time(lambda *a: ops.ssd_scan(*a, chunk=64), x, dt, A, B, C)})
    rows.append({"name": "ssd_scan_ref_us",
                 "us": _time(lambda *a: ref.ssd_scan_ref(*a), x, dt, A, B, C)})
    return rows


def check(rows, cores=None):
    """Acceptance gate over one ``run()`` row set.

    ``cores`` injects the visible-core count the cores-aware throughput
    bars key on (None -> ``os.cpu_count()``).  Those bars —
    ``fused_vs_per_seed``, ``async_vs_sync``, ``scaling_vs_1dev``,
    ``multihost_scaling_vs_1proc`` — need a spare core to mean anything;
    on a 1-core container they are scheduling noise around 1 and are NOT
    applied.  Every in-row bit-equality flag is gated unconditionally.
    The parameter exists so tests can pin the gating logic itself
    (tests/test_regression_gate.py) instead of inheriting the CI
    machine's core count."""
    cores = (os.cpu_count() or 1) if cores is None else cores
    ok = all(r["us"] > 0 for r in rows if "us" in r)
    tp = [r for r in rows if r["name"] == "hosting_batch_throughput"]
    # acceptance: one compiled vmap(scan) beats the per-instance loop >= 10x
    ok = ok and all(r["speedup_vs_loop"] >= 10.0 for r in tp)
    for r in rows:
        if r["name"] != "fleet_throughput":
            continue
        # fleet engine must not cost throughput vs the batched engine (0.9:
        # wall-clock noise margin on a timesliced CPU)
        ok = ok and r["fleet_vs_batched_1dev"] >= 0.9
        # device scaling needs real cores to show up (None: the forced-CPU
        # leg did not run beside an accelerator).  Full bar with
        # a core per forced device; a sanity bar on 2-3 cores (the wide-B
        # workload leaves the 1-device run ~single-threaded, so headroom
        # exists — measured ~1.7x on a 2-core host); nothing on 1 core.
        scaling = r.get("scaling_vs_1dev")
        if scaling is not None and cores >= 2:
            bar = 1.5 if cores >= r.get("scale_devices", 4) else 1.1
            ok = ok and scaling > bar
    mc = [r for r in rows if r["name"] == "mc_driver_throughput"]
    # acceptance: folding the seed axis into one compiled program must not
    # lose to S sequential per-seed dispatches (it deletes S-1 dispatches
    # and widens the vmap; measured well above 1x on CPU).  The in-row
    # seed-fold bit-equality assert is unconditional; the throughput bar
    # (0.95 wall-clock noise margin) is cores-aware like stream_overlap's:
    # on a 1-core container the wider fused program timeslices against the
    # suite's own subprocess benches and the ratio is scheduling noise
    # around 1, occasionally dipping under any fixed margin.
    ok = ok and len(mc) == 1
    if cores >= 2:
        ok = ok and all(r["fused_vs_per_seed"] >= 0.95 for r in mc)
    # antithetic pairs must CLEARLY beat independent seeds on the monotone
    # workload the row measures them on (fixed keys -> deterministic;
    # measured ~0.13, and the regression gate pins rises past the
    # committed baseline)
    ok = ok and all(r["antithetic_ci_ratio"] < 0.5 for r in mc)
    dp = [r for r in rows if r["name"] == "offline_dp_streaming"]
    # acceptance: checkpointed backtracking must be bit-identical AND must
    # actually shrink the DP's working set — the materialized [B, T, K]
    # argmin table dominates its temp memory, so the XLA-reported ratio
    # must clear 2x at T/chunk = 16 (measured ~4x; the bar is the
    # pathological-regression line, e.g. a silently re-materialized table
    # would push the ratio to ~1).  Throughput-wise the two-pass recompute
    # costs < 2x the one-pass solve by construction; 0.25 is the noise bar.
    ok = ok and len(dp) == 1
    ok = ok and all(r["identical_bits"] and r["peak_mem_ratio"] > 2.0
                    and r["ckpt_vs_materialized"] > 0.25 for r in dp)
    sf = [r for r in rows if r["name"] == "scenario_fused_throughput"]
    # acceptance: going keys -> totals, fusing generation into the scan is
    # in the same league as materialize-then-stream end-to-end (measured
    # ~1.5x faster standalone on CPU — it deletes the [B, T] array and its
    # round trip — but this row shares the suite with a 4-process scaling
    # bench, so the bar only rejects pathological regressions, not noise).
    # The sim-only fused_vs_stream ratio is informational: the streamed
    # path's generation is untimed and its CPU "transfer" is a memcpy.
    ok = ok and len(sf) == 1
    ok = ok and all(r["fused_slots_instances_per_sec"] > 0
                    and r["fused_vs_host_e2e"] > 0.5 for r in sf)
    lf = [r for r in rows if r["name"] == "live_fleet_step"]
    # acceptance: the live stepper admitted every slot without a retrace
    # and produced positive rates/latencies at every width; no absolute
    # latency bar (CPU wall time is machine-dependent — the regression
    # gate pins the committed baseline's rates instead)
    ok = ok and len(lf) == 1
    ok = ok and all(r["zero_retraces"]
                    and all(w["slots_admitted_per_sec"] > 0
                            and w["p99_step_latency_us"] > 0
                            for w in r["per_width"]) for r in lf)
    mh = [r for r in rows if r["name"] == "multihost_scaling"]
    # acceptance: the 2-process leg's gathered global totals are
    # bit-identical to the 1-process leg's (the in-row assert; None: the
    # CPU cluster legs did not run beside an accelerator, or fast mode
    # skipped them).  The >1.0 aggregate-throughput bar
    # needs a core per process, so it applies only with >= 2 cores.
    ok = ok and len(mh) == 1
    for r in mh:
        if r.get("multihost_scaling_vs_1proc") is not None:
            ok = ok and r["identical_bits"]
            if cores >= 2:
                ok = ok and r["multihost_scaling_vs_1proc"] > 1.0
    so = [r for r in rows if r["name"] == "stream_overlap"]
    # acceptance: async ingestion is bit-identical unconditionally.  The
    # throughput bar (async at least matches sync, 0.9 wall-clock noise
    # margin) needs a spare physical core for the prefetch thread — on a
    # 1-core runner the thread merely timeslices with XLA and the ratio
    # is scheduling noise around 1, so (like scaling_vs_1dev above) the
    # bar only applies with >= 2 cores.
    ok = ok and len(so) == 1
    ok = ok and all(r["identical_bits"] for r in so)
    if cores >= 2:
        ok = ok and all(r["async_vs_sync"] >= 0.9 for r in so)
    pf = [r for r in rows if r["name"] == "policy_fanout"]
    # acceptance: every fan-out lane is bit-identical to its standalone
    # dispatch (unconditional — it IS the tentpole invariant), and at P=4
    # the fused sweep beats 4 separate dispatches outright: the separate
    # path regenerates the same stream 4 times ON THE SAME CORE, so the
    # ratio is engine-vs-engine and needs no cores gate.
    ok = ok and len(pf) == 1
    ok = ok and all(r["identical_bits"] and r["fanout_vs_separate"] > 1.0
                    for r in pf)
    ms = [r for r in rows if r["name"] == "multi_service"]
    # acceptance: the service axis collapses to the single-service engine
    # bit-for-bit at N=1 AND the joint capacity DP matches the brute-force
    # oracle exactly (both asserted in-row, folded into identical_bits);
    # the lane-engine rate must be positive — its level is pinned by the
    # committed baseline through the _per_sec regression guard.
    ok = ok and len(ms) == 1
    ok = ok and all(r["identical_bits"] and r["slots_instances_per_sec"] > 0
                    and r["joint_dp_seconds"] > 0 for r in ms)
    # hosting-kernel backend rows: bit-identity is unconditional (it IS
    # the backend-dispatch invariant); the speedup bar applies only to a
    # compiled (non-interpret) backend — interpret mode re-traces the
    # kernel body through the HLO interpreter and is expected to LOSE to
    # XLA on CPU, which is why "xla" stays the default backend there.
    dpk = [r for r in rows if r["name"] == "dp_minplus_kernel"]
    prk = [r for r in rows if r["name"] == "counter_prng_kernel"]
    ok = ok and len(dpk) == 1 and len(prk) == 1
    for r in dpk:
        ok = ok and r["identical_bits"]
        ok = ok and r["xla_dp_slots_instances_per_sec"] > 0
        ok = ok and r["pallas_dp_slots_instances_per_sec"] > 0
        if not r["backend"].endswith("-interpret"):
            ok = ok and r["dp_pallas_vs_xla"] > 1.0
    for r in prk:
        ok = ok and r["identical_bits"]
        ok = ok and r["xla_prng_draws_per_sec"] > 0
        ok = ok and r["pallas_prng_draws_per_sec"] > 0
        if not r["backend"].endswith("-interpret"):
            ok = ok and r["prng_pallas_vs_xla"] > 1.0
    return ok


if __name__ == "__main__":
    if "--fleet-scaling" in sys.argv:
        i = sys.argv.index("--fleet-scaling")
        _fleet_scaling_main(int(sys.argv[i + 1]), int(sys.argv[i + 2]),
                            int(sys.argv[i + 3]))
    elif "--multihost-worker" in sys.argv:
        i = sys.argv.index("--multihost-worker")
        _multihost_worker_main(int(sys.argv[i + 1]), int(sys.argv[i + 2]),
                               int(sys.argv[i + 3]), int(sys.argv[i + 4]))
    else:
        for row in run():
            print(row)
