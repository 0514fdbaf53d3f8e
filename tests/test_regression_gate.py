"""Unit tests for the CI perf-regression gate
(``benchmarks/check_regression.py``): drop detection on ratio and rate
keys, machine-speed normalization of rates, additive-key tolerance, and
the disappeared-entry failure.  Also pins ``benchmarks.kernel_bench
.check``'s cores-aware gating through its ``cores`` injection point
(synthetic rows — no benchmark runs).
"""

import copy
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks.check_regression import compare, main
from benchmarks.kernel_bench import check as kernel_check


def entry(rate, ratio, **extra):
    row = {
        "slots_instances_per_sec": rate,
        "speedup_vs_loop": ratio,
        "B": 64,
        "T": 4096,
    }
    row.update(extra)
    return row


def baseline_tp():
    return {
        "a": entry(1000.0, 12.0),
        "b": entry(2000.0, 1.5),
        "c": entry(500.0, 4.0),
    }


def test_identical_reports_pass():
    base = baseline_tp()
    failures, _ = compare(json.loads(json.dumps(base)), base)
    assert failures == []


def test_ratio_drop_fails_and_metadata_is_ignored():
    base = baseline_tp()
    new = json.loads(json.dumps(base))
    new["a"]["speedup_vs_loop"] = 12.0 * 0.7  # 30% drop
    new["a"]["B"] = 1  # metadata: never guarded
    failures, _ = compare(new, base)
    assert len(failures) == 1
    assert "a.speedup_vs_loop" in failures[0]


def test_small_ratio_drop_passes():
    base = baseline_tp()
    new = json.loads(json.dumps(base))
    new["a"]["speedup_vs_loop"] = 12.0 * 0.8  # 20% < threshold
    failures, _ = compare(new, base)
    assert failures == []


def test_uniform_rate_shift_is_calibrated_away():
    """Half-speed runner: every rate drops 50% together — the median
    machine-speed factor absorbs it and the gate stays green."""
    base = baseline_tp()
    new = json.loads(json.dumps(base))
    for row in new.values():
        row["slots_instances_per_sec"] *= 0.5
    failures, _ = compare(new, base)
    assert failures == []


def test_single_rate_regression_still_fails():
    base = baseline_tp()
    new = json.loads(json.dumps(base))
    new["b"]["slots_instances_per_sec"] *= 0.5  # alone among its peers
    failures, _ = compare(new, base)
    assert any("b.slots_instances_per_sec" in f for f in failures)


def test_additive_keys_and_entries_pass():
    base = baseline_tp()
    new = json.loads(json.dumps(base))
    new["zz_new_row"] = entry(123.0, 9.9)
    new["a"]["brand_new_ratio"] = 0.001
    failures, notes = compare(new, base)
    assert failures == []
    assert any("zz_new_row" in n and "additive" in n for n in notes)


def test_disappeared_entry_fails():
    base = baseline_tp()
    new = json.loads(json.dumps(base))
    del new["c"]
    failures, _ = compare(new, base)
    assert any("c:" in f and "disappeared" in f for f in failures)


def test_none_values_skip_with_note():
    base = baseline_tp()
    base["a"]["fused_vs_host_e2e"] = 1.7
    new = json.loads(json.dumps(base))
    new["a"]["fused_vs_host_e2e"] = None  # recorded measurement failure
    failures, notes = compare(new, base)
    assert failures == []
    assert any("fused_vs_host_e2e" in n and "skipped" in n for n in notes)


def test_machine_dependent_scaling_key_is_not_guarded():
    """scaling_vs_1dev tracks the runner's cores, not the code — a slow
    runner must not fail the gate on it (kernel_bench.check owns it)."""
    base = baseline_tp()
    base["a"]["scaling_vs_1dev"] = 1.99
    new = json.loads(json.dumps(base))
    new["a"]["scaling_vs_1dev"] = 1.05  # 2-vCPU runner
    failures, _ = compare(new, base)
    assert failures == []


def test_lower_is_better_ratio_guards_rises_not_drops():
    base = baseline_tp()
    base["a"]["antithetic_ci_ratio"] = 0.13
    new = json.loads(json.dumps(base))
    new["a"]["antithetic_ci_ratio"] = 0.05  # improvement: passes
    failures, _ = compare(new, base)
    assert failures == []
    new["a"]["antithetic_ci_ratio"] = 0.50  # variance reduction lost
    failures, _ = compare(new, base)
    assert any("a.antithetic_ci_ratio" in f and "rose" in f for f in failures)


def test_guarded_key_missing_from_surviving_entry_fails():
    """A guarded key silently dropped from a still-present entry is a
    schema regression, distinct from an explicit None measurement."""
    base = baseline_tp()
    new = json.loads(json.dumps(base))
    del new["a"]["speedup_vs_loop"]
    failures, _ = compare(new, base)
    assert any(
        "a.speedup_vs_loop" in f and "missing" in f for f in failures
    )


def test_threshold_is_respected():
    base = baseline_tp()
    new = json.loads(json.dumps(base))
    new["a"]["speedup_vs_loop"] = 12.0 * 0.7
    failures, _ = compare(new, base, threshold=0.5)
    assert failures == []


def test_main_end_to_end(tmp_path):
    report = {"schema_version": 1, "throughput": baseline_tp()}
    good = tmp_path / "bench.json"
    basef = tmp_path / "BENCH_baseline.json"
    good.write_text(json.dumps(report))
    basef.write_text(json.dumps(report))
    assert main([str(good), str(basef)]) == 0
    bad = dict(report)
    bad["throughput"] = json.loads(json.dumps(baseline_tp()))
    bad["throughput"]["a"]["speedup_vs_loop"] = 1.0
    badf = tmp_path / "bad.json"
    badf.write_text(json.dumps(bad))
    assert main([str(badf), str(basef)]) == 1
    wrong = tmp_path / "wrong.json"
    wrong.write_text(json.dumps({"schema_version": 2}))
    assert main([str(wrong), str(basef)]) == 1


# ----------------------------------------------------------------------
# kernel_bench.check: cores-aware gating, pinned through the `cores`
# injection point (so the logic is tested, not the CI machine's cores).
# ----------------------------------------------------------------------


def healthy_rows():
    """A minimal synthetic row set that clears every acceptance bar in
    ``kernel_bench.check`` at any core count."""
    return [
        {"name": "hosting_batch_throughput", "speedup_vs_loop": 20.0},
        {
            "name": "fleet_throughput",
            "fleet_vs_batched_1dev": 1.0,
            "scaling_vs_1dev": 2.0,
            "scale_devices": 4,
        },
        {
            "name": "mc_driver_throughput",
            "fused_vs_per_seed": 1.2,
            "antithetic_ci_ratio": 0.1,
        },
        {
            "name": "offline_dp_streaming",
            "identical_bits": True,
            "peak_mem_ratio": 4.0,
            "ckpt_vs_materialized": 1.0,
        },
        {
            "name": "scenario_fused_throughput",
            "fused_slots_instances_per_sec": 1.0,
            "fused_vs_host_e2e": 1.0,
        },
        {
            "name": "live_fleet_step",
            "zero_retraces": True,
            "per_width": [
                {"slots_admitted_per_sec": 1.0, "p99_step_latency_us": 1.0},
            ],
        },
        {
            "name": "multihost_scaling",
            "identical_bits": True,
            "multihost_scaling_vs_1proc": 1.8,
        },
        {
            "name": "stream_overlap",
            "identical_bits": True,
            "async_vs_sync": 1.0,
        },
        {
            "name": "policy_fanout",
            "identical_bits": True,
            "fanout_vs_separate": 1.5,
        },
        {
            "name": "multi_service",
            "identical_bits": True,
            "slots_instances_per_sec": 1.0,
            "joint_dp_seconds": 1.0,
        },
    ]


def _with(name, key, value):
    rows = copy.deepcopy(healthy_rows())
    next(r for r in rows if r["name"] == name)[key] = value
    return rows


def test_kernel_check_healthy_rows_pass_any_cores():
    assert kernel_check(healthy_rows(), cores=1) is True
    assert kernel_check(healthy_rows(), cores=8) is True


@pytest.mark.parametrize(
    "name,key,bad",
    [
        ("mc_driver_throughput", "fused_vs_per_seed", 0.2),
        ("stream_overlap", "async_vs_sync", 0.2),
        ("fleet_throughput", "scaling_vs_1dev", 1.0),
        ("multihost_scaling", "multihost_scaling_vs_1proc", 0.5),
    ],
)
def test_cores_aware_bars_gate_only_with_spare_cores(name, key, bad):
    """The throughput bars that need a spare core are scheduling noise on
    a 1-core container: they must pass at cores=1 and fail at cores=2."""
    rows = _with(name, key, bad)
    assert kernel_check(rows, cores=1) is True
    assert kernel_check(rows, cores=2) is False


@pytest.mark.parametrize(
    "name,key,bad",
    [
        ("stream_overlap", "identical_bits", False),
        ("multihost_scaling", "identical_bits", False),
        ("policy_fanout", "identical_bits", False),
        ("policy_fanout", "fanout_vs_separate", 0.9),
        ("multi_service", "identical_bits", False),
        ("offline_dp_streaming", "identical_bits", False),
    ],
)
def test_bit_flags_and_fanout_gate_unconditionally(name, key, bad):
    """Bit-equality flags — and the engine-vs-engine fan-out ratio, which
    needs no spare core — gate even on a 1-core container."""
    assert kernel_check(_with(name, key, bad), cores=1) is False


def test_multihost_skip_marker_row_passes():
    """The fast-mode skip-marker entry (explicit nulls, FULL-mode-only
    cluster legs) must not trip the gate at any core count."""
    rows = _with("multihost_scaling", "multihost_scaling_vs_1proc", None)
    for r in rows:
        if r["name"] == "multihost_scaling":
            r["single_process_slots_instances_per_sec"] = None
            r["multi_process_slots_instances_per_sec"] = None
            del r["identical_bits"]
    assert kernel_check(rows, cores=1) is True
    assert kernel_check(rows, cores=8) is True
