"""Scheduler layer (core/scheduler.py): cells peeled on the device per
completed request, after pruning and cache hits."""


def read(run):
    done = [r["stats"] for r in run["tickets"] if r["stats"] is not None]
    if not done:
        return None
    return sum(s["cells_evaluated"] for s in done) / len(done)
