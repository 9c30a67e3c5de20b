"""Device time per run of the serve step's executable inside the window,
summed from the profiler trace (``step_ms.serve``, ``step_ms.bulk``)."""


def read(run):
    t, n = run.step_device_s()
    return None if not n else float(t / n * 1e3)
