"""Host-to-device hand-overs of request data per batch: the count of the
program's ``serve.h2d`` spans over the count of its ``serve.batch`` spans,
of those that start inside the window (profiler trace). Silent without a
chip, or where the program has no such spans."""


def read(run):
    tr = run.trace
    if tr is None or not tr.chips:
        return None
    lo, hi = run.trace_window
    batches = sum(lo <= s < hi for s, _ in tr.host.get("serve.batch", []))
    h2d = sum(lo <= s < hi for s, _ in tr.host.get("serve.h2d", []))
    if not batches or not h2d:
        return None
    return float(h2d / batches)
