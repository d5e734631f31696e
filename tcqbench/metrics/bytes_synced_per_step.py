"""Pipeline layer (core/engine.py): bytes the host fetches from the device
per device step, over the completed requests' pools."""


def read(run):
    done = [r["stats"] for r in run["tickets"] if r["stats"] is not None]
    steps = sum(s["device_steps"] for s in done)
    if not steps:
        return None
    return sum(s["bytes_synced"] for s in done) / steps
