"""Median over the traced admits of the admit span minus the device-busy
time inside it (busiest chip), in ms: the host's share of an admit."""
import numpy as np


def read(run):
    v = run.view
    spans = v.spans_named("bench.admit") if v is not None else []
    if not spans:
        return None
    host = [(s.end - s.start) - v.device_in(s) for s in spans]
    return float(np.median(host)) / 1e6
