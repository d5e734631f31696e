"""Step layer (core/wave.py, kernels/wave_peel): fixpoint iterations per
device step, Σ ``peel_iters`` / Σ ``device_steps`` over the pools that
record ``peel_iters``.  Nothing to read where the program records none."""


def read(run):
    pools = [p for p in run["pools"] if "peel_iters" in p]
    steps = sum(p["device_steps"] for p in pools)
    if not steps:
        return None
    return sum(p["peel_iters"] for p in pools) / steps
