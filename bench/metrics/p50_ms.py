"""Median request latency, due time to scores ready, over every request due
in the window (host clock)."""
import numpy as np


def read(run):
    lat = run.latencies_s()
    return None if lat is None else float(np.percentile(lat, 50) * 1e3)
