"""95th percentile of caller-side admit latency over every admit in the
window: from handing over one slot of telemetry to holding the [B]
decisions on the host."""
import numpy as np


def read(run):
    lat = run.window.get("latency_s")
    if lat is None or len(lat) == 0:
        return None
    return float(np.percentile(np.asarray(lat) * 1e3, 95))
