"""eval_p95_ms (ms, host clock): 95th percentile of the client-side latency
of every evaluate request answered in the window."""
import numpy as np


def read(run):
    lat = [r["t1"] - r["t0"] for r in run.answered("evaluate")]
    if not lat:
        return None
    return float(np.percentile(lat, 95)) * 1e3
