"""Host time per batch that the program's MicroBatcher spends handing
request data to the device: the summed ``serve.h2d`` spans over the count of
``serve.batch`` spans, of those that start inside the window (profiler
trace). Silent without a chip, or where the program has no such spans."""


def read(run):
    tr = run.trace
    if tr is None or not tr.chips:
        return None
    lo, hi = run.trace_window
    batches = sum(lo <= s < hi for s, _ in tr.host.get("serve.batch", []))
    h2d = [e - s for s, e in tr.host.get("serve.h2d", []) if lo <= s < hi]
    if not batches or not h2d:
        return None
    return float(sum(h2d) / batches * 1e-6)
