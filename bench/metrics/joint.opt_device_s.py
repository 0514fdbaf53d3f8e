"""Device-busy seconds inside the benchmark's ``offline_opt_services`` span,
per job (busiest chip): the checkpointed joint-state DP forward with its
generation of every service's arrivals, the rents and the N-to-joint
reduction."""
import numpy as np


def read(run):
    v = run.view
    spans = (v.spans_named("bench.offline_opt_services") if v is not None
             else [])
    if not spans:
        return None
    return float(np.mean([v.device_in(s) for s in spans])) / 1e9
