"""The bag kernel's share of its roofline: the least time the lookup's work
could take (valid rows at their stored width, the ids and the pooled bags at
the HBM peak, or its adds at the FLOP peak) over the kernel's device time
per step."""


def read(run):
    t, n_kernels = run.kernel_device_s()
    _, steps = run.step_device_s()
    if not n_kernels or not steps or t <= 0:
        return None
    t_min, _ = run.min_time(run.lookup_work())
    return float(t_min / (t / steps) * 100.0)
