"""Device-busy seconds inside the benchmark's ``offline_opt_fleet`` span,
per job (busiest chip): the checkpointed DP forward with its regeneration
of the scenario."""
import numpy as np


def read(run):
    v = run.view
    spans = v.spans_named("bench.offline_opt_fleet") if v is not None else []
    if not spans:
        return None
    return float(np.mean([v.device_in(s) for s in spans])) / 1e9
