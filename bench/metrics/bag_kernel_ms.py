"""Device time per step of the Pallas bag kernel (``tpu_custom_call``
events inside the serve step's runs), from the profiler trace."""


def read(run):
    t, n_kernels = run.kernel_device_s()
    _, steps = run.step_device_s()
    if not n_kernels or not steps:
        return None
    return float(t / steps * 1e3)
