"""99th percentile of how late the load generator handed a request to the
program's batcher: submit time minus due time (host clock)."""
import numpy as np


def read(run):
    if run.mode != "open":
        return None
    rec = run.record
    return float(np.percentile(rec.submitted - rec.due, 99) * 1e3)
