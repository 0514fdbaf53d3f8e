"""Device-busy seconds inside the benchmark's ``run_fleet`` span, per job
(busiest chip): the fan-out scan with fused generation and policy steps."""
import numpy as np


def read(run):
    v = run.view
    spans = v.spans_named("bench.run_fleet") if v is not None else []
    if not spans:
        return None
    return float(np.mean([v.device_in(s) for s in spans])) / 1e9
