"""The min-plus DP's share of its roofline, in %: the least time one chip
needs for its rows' DP work (``bench.dpwork``: operations and bytes from
rows, T and K alone, so any implementation is credited with the same work)
over the device-busy time of the ``offline_opt_fleet`` span on the busiest
chip.  The least time is bytes-bound at these shapes (the slot's
observations read once per row); the operations term uses the bf16 peak
because v5e publishes no float32 one, which can only flatter it.  The span
also regenerates the scenario, so this share is a lower bound of the
recursion's own."""
import numpy as np

from bench import dpwork


def read(run):
    v = run.view
    spans = v.spans_named("bench.offline_opt_fleet") if v is not None else []
    if not spans:
        return None
    cfg = run.config
    rows = cfg["sites"] * cfg["seeds"] // len(run.devices)
    least, _ = dpwork.least_time(rows, cfg["horizon_slots"],
                                 len(cfg["levels"]), run.peaks)
    busy = float(np.mean([v.device_in(s) for s in spans])) / 1e9
    return 100.0 * least / busy
