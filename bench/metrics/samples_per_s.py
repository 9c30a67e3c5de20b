"""Samples scored in a closed-loop window over the window's length, from
the first dispatch to the last step's scores ready (host clock)."""


def read(run):
    if run.mode != "closed":
        return None
    t0, t1 = run.record.window
    return float(run.record.samples / (t1 - t0))
