"""Mean host time per batch of the program's MicroBatcher.next_batch, timed
around the benchmark's own call (host clock)."""
import numpy as np


def read(run):
    if run.mode != "open" or not run.record.batches:
        return None
    return float(np.mean([b - a for a, b, _ in run.record.batches]) * 1e3)
