"""The chip's idle share, from the profiler trace. In an open-loop run
(``device_idle.serve``) only while a batch is being assembled or is in
flight: the union of the benchmark's ``assemble`` and ``step`` spans, so that
waiting for arrivals, which at a fixed rate only mirrors the rate, is left
out. In a closed-loop run (``device_idle.bulk``) over the whole window."""


def read(run):
    if run.trace is None or not run.trace.chips:
        return None
    if run.mode == "open":
        spans = run.trace.spans("assemble", "step")
        idle, total = run.trace.idle_within(spans)
    else:
        total = (run.trace_window[1] - run.trace_window[0]) * 1e-9
        idle = total - run.trace.busy_s(run.trace_window)
    return None if total <= 0 else float(idle / total * 100.0)
