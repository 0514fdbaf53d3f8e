#!/usr/bin/env python3
"""Readings from which a cell's limits are set, on the chip, in one process.

    python bench/control.py --workload <cell> --seconds <s> --seeds <n> ...

For each seed it builds the cell as a run does, drives the timed path for a
short window at the cell's own size, and prints one JSON line with both
readings of every number the check compares:

* ``program``: the program's output against the plain reference (the lower
  readings: a limit must sit above the largest over a dozen seeds);
* ``control``: the reference computed in bfloat16, put in the program's
  place, against the reference (the upper readings: the control must fail).

The benchmark's own runs never run the control.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

from bench import compcache, harness, peaks  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    try:
        devices = harness.chips_or_fail(cell.chips)
    except harness.NoChip as e:
        print(f"control: {e}; nothing was run", file=sys.stderr)
        return 2
    compcache.enable()
    for seed in args.seeds:
        t = time.perf_counter()
        run = harness.Run(cell=cell, seed=seed, seconds=args.seconds,
                          trace=False, t0=t, devices=devices)
        run.peaks = peaks.for_kind(devices[0].device_kind)
        run.state = cell.driver.setup(run)
        run.window = cell.driver.window(run)
        control = cell.driver.control(run)
        program = cell.driver.check(run)
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "completed": run.window["completed"],
            "program": {k: v["value"] for k, v in program.items()},
            "control": {k: v["value"] for k, v in control.items()},
            "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
