"""Mean device-busy time inside each traced admit span (busiest chip), in
ms: the compiled step and its transfers on the chip."""
import numpy as np


def read(run):
    v = run.view
    spans = v.spans_named("bench.admit") if v is not None else []
    if not spans:
        return None
    return float(np.mean([v.device_in(s) for s in spans])) / 1e6
