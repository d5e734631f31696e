"""Service layer (core/service.py): share of schedule cells the TTI result
cache resolved, over all cells of the completed requests, in %."""


def read(run):
    done = [r["stats"] for r in run["tickets"] if r["stats"] is not None]
    total = sum(s["cells_total"] for s in done)
    if not total:
        return None
    return 100.0 * sum(s["cells_cached"] for s in done) / total
