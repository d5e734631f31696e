"""Engine first touch (core/otcd.py): seconds of the backend compiles that
JAX reported inside the window."""


def read(run):
    return float(sum(end - start for start, end, _ in run["compiles"]))
