"""Step layer (core/wave.py, kernels/wave_peel): the vertex rows of the
size class that each step peels over (``pool_log``'s ``step_rows``: edge,
pair and vertex rows), weighted by each pool's steps.  Nothing to read
where the program records no ``step_rows``."""


def read(run):
    pools = [p for p in run["pools"] if p.get("step_rows") is not None]
    steps = sum(p["device_steps"] for p in pools)
    if not steps:
        return None
    return sum(p["step_rows"][2] * p["device_steps"] for p in pools) / steps
