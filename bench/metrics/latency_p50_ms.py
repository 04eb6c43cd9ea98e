"""Median creation-to-delivery latency, in ms, over every delivery of the
changesets due in the window (creation stamp of the newest changeset in the
fire to the moment its outputs and committed replica are ready)."""
import numpy as np


def read(run):
    lat = run.latencies_s
    return float(np.percentile(lat, 50) * 1e3) if lat else None
