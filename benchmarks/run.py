"""Benchmark harness: one module per paper figure/table.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only fig01]
                                            [--json OUT.json]

Each module exposes run() -> rows and check(rows) -> bool (the figure's
qualitative claims as assertions).  Output: 'module,status,seconds' summary
plus per-row CSV lines.  ``--json OUT.json`` additionally writes a stable
machine-readable report (schema below) used to track the perf trajectory
across PRs (BENCH_*.json):

    {
      "schema_version": 1,
      "fast": bool,
      "modules": [{"name", "status", "seconds", "n_rows"}, ...],
      "throughput": {<name>: {"slots_instances_per_sec", "speedup_vs_loop",
                              "B", "T"}},
      "totals": {"seconds", "failures"}
    }

``fleet_throughput`` rows add keys *inside* their throughput entry
(``fleet_vs_batched_1dev``, ``scaling_vs_1dev``, ``devices``) — additive,
so the schema version stays 1 and existing consumers keep working;
``scenario_fused_throughput`` rows likewise add ``fused_vs_stream`` and
``materialize_seconds`` (fused on-device generation vs host-materialized
streaming), ``mc_driver_throughput`` adds ``fused_vs_per_seed``,
``antithetic_ci_ratio`` and ``S`` (one fused seed-axis program vs S
per-seed dispatches), and ``offline_dp_streaming`` adds
``ckpt_vs_materialized`` and ``peak_mem_ratio`` (checkpointed two-pass DP
backtracking vs the materialized [B, T, K] table).  ``live_fleet_step``
adds ``live_slots_admitted_per_sec`` plus ``p50_step_latency_us`` /
``p99_step_latency_us`` (the persistent chunk=1 ``fleet_stepper`` at its
widest measured fleet), and ``stream_overlap`` adds
``async_stream_slots_instances_per_sec`` / ``async_vs_sync`` (double
buffered prefetch vs the synchronous slab feed, bit-equality asserted
in-row).  ``policy_fanout`` adds ``fanout_vs_separate`` /
``fanout_vs_separate_p2`` / ``generation_passes_saved`` (P policy
families fused on one generated stream vs P separate ``run_fleet``
dispatches, every lane bit-equality-asserted in-row; in fast mode the
``multihost_scaling`` entry instead carries explicit nulls — the cluster
leg runs in full mode only).  ``multi_service`` adds ``n_services`` /
``joint_states`` / ``joint_dp_seconds`` (B x N per-service fleet lanes
plus the capacity-respecting joint DP; the N=1 bitwise identity and
joint-DP-vs-oracle claims are asserted in-row and folded into its
``identical_bits``).  The report itself carries top-level ``backend`` /
``device_kind`` keys (additive, still schema 1) recording which Pallas
mode the kernel rows ran in ("pallas-interpret" on CPU) and
``jax.devices()[0].device_kind`` — so baselines from different
machines/modes are distinguishable.  ``multihost_scaling`` adds its
2-process-vs-1 rates and ``multihost_scaling_vs_1proc`` ratio, and the
report gains top-level ``process_count`` / ``host_count`` /
``local_device_count`` keys (additive, still schema 1) recording the JAX
process topology the report was produced under — the benchmark process
itself is single-process (the row's cluster legs run in subprocesses),
but a report produced inside a real multi-host launch is then
distinguishable from a laptop run.

``benchmarks/check_regression.py`` compares a report's ``throughput``
section against the committed ``BENCH_baseline.json`` (the perf-regression
CI gate); regenerate the baseline with this command whenever a PR
intentionally shifts a gated number.

Sweep modules accept ``n_seeds`` (Monte-Carlo sample paths per grid point),
folded into the stream keys by the fleet engine (``run_fleet(n_seeds=)``);
``--fast`` shrinks both the horizon T and n_seeds for smoke runs.
"""
from __future__ import annotations

import json
import sys
import time

MODULES = [
    "fig01_02_alpha_sweep",
    "fig03_06_m_p_sweeps",
    "fig07_08_multiple_rr",
    "fig10_11_trace",
    "fig12_15_poisson_model2",
    "fig17_22_markov_mdp",
    "fig23_25_geolife",
    "beyond_knapsack_levels",
    "theorems",
    "kernel_bench",
]

FAST_T = 1500
FAST_SEEDS = 2


def main() -> None:
    import importlib
    import inspect
    only = None
    if "--only" in sys.argv:
        only = sys.argv[sys.argv.index("--only") + 1]
    json_out = None
    if "--json" in sys.argv:
        json_out = sys.argv[sys.argv.index("--json") + 1]
    fast = "--fast" in sys.argv
    failures = []
    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.kernels.utils import default_interpret
    enable_compile_cache()
    report = {"schema_version": 1, "fast": fast, "modules": [],
              "throughput": {},
              # the Pallas mode the kernel rows (flash / SSD) ran in and
              # the device (additive, schema stays 1)
              "backend": ("pallas-interpret" if default_interpret()
                          else "pallas"),
              "device_kind": jax.devices()[0].device_kind,
              # JAX process topology of THIS benchmark process (additive,
              # schema stays 1).  Single-process on CI — the
              # multihost_scaling row's cluster legs are subprocesses —
              # but a report from a real multi-host launch self-labels.
              "process_count": jax.process_count(),
              "host_count": len({d.process_index for d in jax.devices()}),
              "local_device_count": jax.local_device_count()}
    t_all = time.time()
    print("module,status,seconds,rows")
    for name in MODULES:
        if only and only not in name:
            continue
        mod = importlib.import_module(f"benchmarks.{name}")
        t0 = time.time()
        try:
            params = inspect.signature(mod.run).parameters
            kwargs = {}
            if fast and "T" in params:
                kwargs["T"] = FAST_T
            if fast and "n_seeds" in params:
                kwargs["n_seeds"] = FAST_SEEDS
            rows = mod.run(**kwargs)
            ok = mod.check(rows)
            status = "ok" if ok else "check-failed"
        except Exception as e:                      # pragma: no cover
            import traceback; traceback.print_exc()
            rows, status = [], f"error:{type(e).__name__}"
        if not status == "ok":
            failures.append(name)
        dt = time.time() - t0
        print(f"{name},{status},{dt:.1f},{len(rows)}")
        for r in rows:
            kv = ",".join(f"{k}={v}" for k, v in r.items())
            print(f"  {name},{kv}")
            if isinstance(r, dict) and "speedup_vs_loop" in r:
                report["throughput"][r.get("name", name)] = {
                    "slots_instances_per_sec":
                        r.get("batched_slots_instances_per_sec"),
                    "speedup_vs_loop": r["speedup_vs_loop"],
                    "B": r.get("B"), "T": r.get("T"),
                }
            if isinstance(r, dict) and "fleet_vs_batched_1dev" in r:
                report["throughput"][r.get("name", name)] = {
                    "slots_instances_per_sec":
                        r.get("fleet_slots_instances_per_sec"),
                    "fleet_vs_batched_1dev": r["fleet_vs_batched_1dev"],
                    "scaling_vs_1dev": r.get("scaling_vs_1dev"),
                    "devices": r.get("scale_devices"),
                    "B": r.get("B"), "T": r.get("T"),
                }
            if isinstance(r, dict) and "fused_vs_per_seed" in r:
                report["throughput"][r.get("name", name)] = {
                    "slots_instances_per_sec":
                        r.get("fused_slots_instances_seeds_per_sec"),
                    "fused_vs_per_seed": r["fused_vs_per_seed"],
                    "antithetic_ci_ratio": r.get("antithetic_ci_ratio"),
                    "B": r.get("B"), "S": r.get("S"), "T": r.get("T"),
                }
            if isinstance(r, dict) and "ckpt_vs_materialized" in r:
                report["throughput"][r.get("name", name)] = {
                    "slots_instances_per_sec":
                        r.get("ckpt_slots_instances_per_sec"),
                    "ckpt_vs_materialized": r["ckpt_vs_materialized"],
                    "peak_mem_ratio": r.get("peak_mem_ratio"),
                    "B": r.get("B"), "T": r.get("T"),
                }
            if isinstance(r, dict) and "live_slots_admitted_per_sec" in r:
                report["throughput"][r.get("name", name)] = {
                    "live_slots_admitted_per_sec":
                        r["live_slots_admitted_per_sec"],
                    "p50_step_latency_us": r.get("p50_step_latency_us"),
                    "p99_step_latency_us": r.get("p99_step_latency_us"),
                    "zero_retraces": r.get("zero_retraces"),
                    "widths": r.get("widths"), "n_steps": r.get("n_steps"),
                }
            if isinstance(r, dict) and "async_vs_sync" in r:
                report["throughput"][r.get("name", name)] = {
                    "sync_stream_slots_instances_per_sec":
                        r.get("sync_stream_slots_instances_per_sec"),
                    "async_stream_slots_instances_per_sec":
                        r.get("async_stream_slots_instances_per_sec"),
                    "async_vs_sync": r["async_vs_sync"],
                    "identical_bits": r.get("identical_bits"),
                    "B": r.get("B"), "T": r.get("T"),
                    "chunk": r.get("chunk"),
                }
            if isinstance(r, dict) and "fanout_vs_separate" in r:
                report["throughput"][r.get("name", name)] = {
                    "slots_instances_per_sec":
                        r.get("slots_instances_per_sec"),
                    "fanout_vs_separate": r["fanout_vs_separate"],
                    "fanout_vs_separate_p2": r.get("fanout_vs_separate_p2"),
                    "generation_passes_saved":
                        r.get("generation_passes_saved"),
                    "identical_bits": r.get("identical_bits"),
                    "B": r.get("B"), "T": r.get("T"),
                }
            if isinstance(r, dict) and "n_services" in r:
                report["throughput"][r.get("name", name)] = {
                    "slots_instances_per_sec":
                        r.get("slots_instances_per_sec"),
                    "joint_dp_seconds": r.get("joint_dp_seconds"),
                    "identical_bits": r.get("identical_bits"),
                    "n_services": r.get("n_services"),
                    "joint_states": r.get("joint_states"),
                    "B": r.get("B"), "T": r.get("T"),
                    "chunk": r.get("chunk"),
                }
            if isinstance(r, dict) and "multihost_scaling_vs_1proc" in r:
                report["throughput"][r.get("name", name)] = {
                    "single_process_slots_instances_per_sec":
                        r.get("single_process_slots_instances_per_sec"),
                    "multi_process_slots_instances_per_sec":
                        r.get("multi_process_slots_instances_per_sec"),
                    "multihost_scaling_vs_1proc":
                        r["multihost_scaling_vs_1proc"],
                    "identical_bits": r.get("identical_bits"),
                    "n_processes": r.get("n_processes"),
                    "B": r.get("B"), "T": r.get("T"),
                    "chunk": r.get("chunk"),
                }
            if isinstance(r, dict) and "fused_vs_stream" in r:
                report["throughput"][r.get("name", name)] = {
                    "slots_instances_per_sec":
                        r.get("fused_slots_instances_per_sec"),
                    "fused_vs_host_e2e": r.get("fused_vs_host_e2e"),
                    "fused_vs_stream": r["fused_vs_stream"],
                    "materialize_seconds": r.get("materialize_seconds"),
                    "B": r.get("B"), "T": r.get("T"),
                }
        report["modules"].append({"name": name, "status": status,
                                  "seconds": round(dt, 2),
                                  "n_rows": len(rows)})
    report["totals"] = {"seconds": round(time.time() - t_all, 2),
                        "failures": len(failures)}
    if json_out:
        with open(json_out, "w") as fh:
            json.dump(report, fh, indent=2, sort_keys=True)
        print(f"wrote {json_out}")
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
