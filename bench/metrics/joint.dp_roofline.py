"""The joint-state DP's share of its roofline, in %: the least time one
chip needs for its rows' DP work (``bench.jointwork``: operations and bytes
from rows, T, J and N alone, so any implementation is credited with the same
work) over the device-busy time of the ``offline_opt_services`` span on the
busiest chip.  The operations term uses the bf16 peak because v5e publishes
no float32 one, which can only flatter it.  The span also generates the
scenario and reduces it to joint states, so this share is a lower bound of
the recursion's own."""
import numpy as np

from bench import jointwork, plainref_joint


def read(run):
    v = run.view
    spans = (v.spans_named("bench.offline_opt_services") if v is not None
             else [])
    if not spans:
        return None
    cfg = run.config
    N = cfg["services"]
    J = len(plainref_joint.joint_states([cfg["levels"]] * N, cfg["capacity"]))
    rows = cfg["sites"] * cfg["seeds"] // len(run.devices)
    least, _ = jointwork.least_time(rows, cfg["horizon_slots"], J, N,
                                    run.peaks)
    busy = float(np.mean([v.device_in(s) for s in spans])) / 1e9
    return 100.0 * least / busy
