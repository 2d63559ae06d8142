"""derive_p50_s (s, host clock): median client-side latency of the fresh
derives answered in the window (tens of samples: a median, not a tail)."""
import numpy as np


def read(run):
    lat = [r["t1"] - r["t0"] for r in run.answered("derive")]
    if not lat:
        return None
    return float(np.median(lat))
