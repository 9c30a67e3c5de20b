"""99th percentile of one host-to-device hand-over of request data: the
program's ``serve.h2d`` spans that start inside the window (profiler
trace). A stall inside batch assembly's transfers shows here. Silent without
a chip, or where the program has no such spans."""
import numpy as np


def read(run):
    tr = run.trace
    if tr is None or not tr.chips:
        return None
    lo, hi = run.trace_window
    h2d = [e - s for s, e in tr.host.get("serve.h2d", []) if lo <= s < hi]
    if not h2d:
        return None
    return float(np.percentile(h2d, 99) * 1e-6)
