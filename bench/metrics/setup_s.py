"""Process start to the window's opening: weights, the program's set-up,
compilation (or the compile cache), warm-up and traffic generation."""


def read(run):
    return float(run.setup_s)
