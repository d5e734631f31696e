"""Engine first touch (core/otcd.py): backend compiles that JAX reported
inside the window, from its monitoring events."""


def read(run):
    return float(len(run["compiles"]))
