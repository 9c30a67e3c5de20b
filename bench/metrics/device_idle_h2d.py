"""The share of the chip's idle time, while a batch is assembled or in
flight, that falls inside the program's host-to-device hand-overs: device
idle inside the ``serve.h2d`` spans over device idle inside the union of the
benchmark's ``assemble`` and ``step`` spans, of spans that start inside the
window (profiler trace; ``device_idle_h2d.serve``). Silent without a chip,
or where the program has no such spans."""


def read(run):
    tr = run.trace
    if tr is None or not tr.chips:
        return None
    lo, hi = run.trace_window
    h2d = [iv for iv in tr.host.get("serve.h2d", []) if lo <= iv[0] < hi]
    busy = [iv for iv in tr.spans("assemble", "step") if lo <= iv[0] < hi]
    if not h2d or not busy:
        return None
    idle_h2d, _ = tr.idle_within(h2d)
    idle, _ = tr.idle_within(busy)
    return None if idle <= 0 else float(idle_h2d / idle * 100.0)
