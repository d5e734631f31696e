"""Pipeline layer (core/engine.py): occupied lanes over lanes per device
step, weighted by the steps of each pool, in %."""


def read(run):
    steps = sum(p["device_steps"] for p in run["pools"])
    if not steps:
        return None
    busy = sum(p["occupancy"] * p["device_steps"] / p["wave"]
               for p in run["pools"])
    return 100.0 * busy / steps
