"""Host time per batch that the program's MicroBatcher spends combining
each key's rows into the batch array: the summed ``serve.stack`` spans over
the count of ``serve.batch`` spans, of those that start inside the window
(profiler trace). Silent without a chip, or where the program has no such
spans."""


def read(run):
    tr = run.trace
    if tr is None or not tr.chips:
        return None
    lo, hi = run.trace_window
    batches = sum(lo <= s < hi for s, _ in tr.host.get("serve.batch", []))
    stack = [e - s for s, e in tr.host.get("serve.stack", [])
             if lo <= s < hi]
    if not batches or not stack:
        return None
    return float(sum(stack) / batches * 1e-6)
