"""CI perf-regression gate: compare a fresh ``benchmarks.run --json`` report
against the committed ``BENCH_baseline.json``.

    python benchmarks/check_regression.py bench.json BENCH_baseline.json \
        [--threshold 0.25]

Every throughput entry in the baseline must still exist in the new report,
and every *guarded* key of it must not have dropped by more than
``threshold`` (default 25%).  Additive changes — new throughput entries,
new keys inside an entry — pass silently: the schema grows, the gate only
ever pins what a previous PR already achieved.

Three kinds of guarded keys:

* **dimensionless ratios** (``speedup_vs_loop``, ``fused_vs_per_seed``,
  ``peak_mem_ratio``, ...) are compared raw — they measure one engine path
  against another on the same machine in the same process, so they are
  runner-independent and a drop is a real regression;
* **absolute rates** (``*_per_sec``) are first normalized by the median
  new/baseline ratio across all rate keys — one shared machine-speed
  factor.  A uniformly slower runner moves every rate together and the
  median absorbs it; a *single* path regressing >25% against its peers
  still fails.  (With fewer than 3 common rate keys there is no robust
  factor; rates are then compared raw.)
* **lower-is-better ratios** (``antithetic_ci_ratio``) are guarded
  against *rises* past the same threshold — they are pure functions of
  fixed PRNG keys, so a rise is a real loss, not noise.

Explicit ``None`` values on either side (e.g. ``scaling_vs_1dev`` on a
1-core runner — a recorded measurement failure) skip that key with a
note; a guarded key *absent* from a surviving entry fails the gate like a
disappeared entry would.  Regenerate the baseline by committing
a fresh report whenever a PR intentionally shifts a gated number — the
workflow is documented in ROADMAP.md.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys

# dimensionless engine-vs-engine ratios: runner-independent, guarded raw.
# Extend this set when a new bench row adds a ratio the trajectory should
# pin (keys absent from it are additive/informational and never gate).
RATIO_KEYS = {
    "speedup_vs_loop",
    "fleet_vs_batched_1dev",
    "fused_vs_host_e2e",
    "fused_vs_per_seed",
    "ckpt_vs_materialized",
    "peak_mem_ratio",
    "fanout_vs_separate",
}
# NOT guarded: fused_vs_stream — kernel_bench documents it as
# informational (the streamed side's generation is untimed and its CPU
# "transfer" is a memcpy), and it swings ~20% between machines; gating it
# would fail clean PRs on runner noise.  scaling_vs_1dev — real multi-core
# speedup, so it tracks the runner's physical cores and contention, not
# the code; kernel_bench.check already gates it with cores-aware bars.
# async_vs_sync — the ingestion-overlap win needs a spare physical core
# for the prefetch thread, so it tracks the runner's core count and load
# like scaling_vs_1dev does; kernel_bench.check gates it >= 0.9 in-row
# and the row's ``*_per_sec`` rates ride the machine-normalized guard.
# multihost_scaling_vs_1proc — real 2-process-vs-1 speedup, so exactly
# like scaling_vs_1dev it measures the runner's physical cores (two
# cluster workers on one core timeslice to ~0.5x, on two cores to ~2x),
# not the code; kernel_bench.check gates it > 1.0 with >= 2 cores, the
# in-row bit-equality assert is unconditional, and the row's
# ``{single,multi}_process_slots_instances_per_sec`` rates ride the
# machine-normalized rate guard so a real ingestion/engine regression
# still fails.
# ``*_latency_us`` keys (live_fleet_step p50/p99) are absolute wall times
# with no per-key normalization story; the row's
# ``live_slots_admitted_per_sec`` rate carries the gated trajectory.

# lower-is-better ratios: guarded against *rises* past the same threshold
# (a pure function of the fixed PRNG keys, so runner-independent).
LOWER_IS_BETTER_KEYS = {
    "antithetic_ci_ratio",
}

RATE_SUFFIX = "_per_sec"
MIN_RATES_FOR_CALIBRATION = 3


def _num(v):
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def _rate_pairs(new_tp, base_tp):
    """All (entry, key, new, old) rate pairs present and numeric on both
    sides — the population the machine-speed factor is estimated from."""
    pairs = []
    for name, base_row in base_tp.items():
        new_row = new_tp.get(name)
        if not isinstance(new_row, dict):
            continue
        for key, old in base_row.items():
            if not key.endswith(RATE_SUFFIX):
                continue
            new = new_row.get(key)
            if _num(old) and _num(new) and old > 0:
                pairs.append((name, key, float(new), float(old)))
    return pairs


def compare(new_tp: dict, base_tp: dict, threshold: float = 0.25):
    """Gate ``new_tp`` (a report's ``throughput`` section) against
    ``base_tp``.  Returns ``(failures, notes)`` — lists of human-readable
    lines; empty ``failures`` means the gate passes."""
    failures, notes = [], []
    rates = _rate_pairs(new_tp, base_tp)
    if len(rates) >= MIN_RATES_FOR_CALIBRATION:
        factor = statistics.median(new / old for _, _, new, old in rates)
        notes.append(
            f"machine-speed factor (median rate ratio): "
            f"{factor:.3f} over {len(rates)} rate keys"
        )
    else:
        factor = 1.0
        notes.append(
            f"only {len(rates)} common rate keys — rates "
            f"compared raw (no machine-speed calibration)"
        )
    for name, base_row in sorted(base_tp.items()):
        new_row = new_tp.get(name)
        if new_row is None:
            failures.append(
                f"{name}: throughput entry disappeared from the new report"
            )
            continue
        for key, old in sorted(base_row.items()):
            is_rate = key.endswith(RATE_SUFFIX)
            lower_better = key in LOWER_IS_BETTER_KEYS
            if not (is_rate or key in RATIO_KEYS or lower_better):
                continue  # metadata / informational
            if key not in new_row:
                # a guarded key vanishing from a surviving entry is a
                # schema regression, not a skip — the gate must never
                # silently lose a metric the baseline pinned
                failures.append(
                    f"{name}.{key}: guarded key missing from the new "
                    f"report"
                )
                continue
            new = new_row[key]
            if old is None or new is None:
                # an explicit null is a recorded measurement failure
                # (e.g. the scaling subprocess on a starved runner) —
                # noted, not fatal
                notes.append(f"{name}.{key}: None on one side, skipped")
                continue
            if not (_num(old) and _num(new)) or old <= 0:
                notes.append(f"{name}.{key}: non-numeric, skipped")
                continue
            if lower_better:
                ceil = (1.0 + threshold) * float(old)
                if float(new) > ceil:
                    failures.append(
                        f"{name}.{key}: lower-is-better ratio rose "
                        f">{threshold:.0%}: {float(new):.4g} > ceiling "
                        f"{ceil:.4g} (baseline {float(old):.4g})"
                    )
                else:
                    notes.append(
                        f"{name}.{key}: ok "
                        f"({float(new):.4g} vs ceiling {ceil:.4g})"
                    )
                continue
            scale = factor if is_rate else 1.0
            floor = (1.0 - threshold) * float(old) * scale
            if float(new) < floor:
                kind = "rate (machine-normalized)" if is_rate else "ratio"
                failures.append(
                    f"{name}.{key}: {kind} dropped >{threshold:.0%}: "
                    f"{float(new):.4g} < floor {floor:.4g} "
                    f"(baseline {float(old):.4g})"
                )
            else:
                notes.append(
                    f"{name}.{key}: ok "
                    f"({float(new):.4g} vs floor {floor:.4g})"
                )
    for name in sorted(set(new_tp) - set(base_tp)):
        notes.append(
            f"{name}: additive entry (not in baseline) — update "
            f"the baseline to start gating it"
        )
    return failures, notes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("report", help="fresh benchmarks.run --json output")
    ap.add_argument("baseline", help="committed BENCH_baseline.json")
    ap.add_argument(
        "--threshold",
        type=float,
        default=0.25,
        help="max fractional drop per guarded key (default .25)",
    )
    args = ap.parse_args(argv)
    with open(args.report) as fh:
        new = json.load(fh)
    with open(args.baseline) as fh:
        base = json.load(fh)
    for r in (new, base):
        if r.get("schema_version") != 1:
            print(f"unsupported schema_version {r.get('schema_version')}")
            return 1
    failures, notes = compare(
        new.get("throughput", {}),
        base.get("throughput", {}),
        threshold=args.threshold,
    )
    for line in notes:
        print(f"  note: {line}")
    if failures:
        print(f"PERF REGRESSION GATE FAILED ({len(failures)}):")
        for line in failures:
            print(f"  FAIL: {line}")
        print(
            "(intentional change? regenerate BENCH_baseline.json — see "
            "ROADMAP.md)"
        )
        return 1
    print(
        f"perf regression gate ok: {len(base.get('throughput', {}))} "
        f"baseline entries held (threshold {args.threshold:.0%})"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
