"""The whole step's share of the chip's peak: the least time its work could
take (FLOPs at the FLOP peak or bytes at the HBM peak, whichever is longer,
counted by ``bench/work.py``) over its measured device time per step."""


def read(run):
    t, n = run.step_device_s()
    if not n or t <= 0:
        return None
    t_min, _ = run.min_time(run.step_work())
    return float(t_min / (t / n) * 100.0)
