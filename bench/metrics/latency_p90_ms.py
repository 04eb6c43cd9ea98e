"""90th percentile of creation-to-delivery latency, in ms, over every
delivery of the changesets due in the window (linear interpolation)."""
import numpy as np


def read(run):
    lat = run.latencies_s
    return float(np.percentile(lat, 90) * 1e3) if lat else None
