"""Service layer (core/service.py): 95th percentile of the wait from a
request's due time until the service admits it to a lane pool, in ms."""

import numpy as np


def read(run):
    waits = [(r["admit_s"] - r["due"]) * 1e3 for r in run["tickets"]
             if r["admit_s"] is not None]
    return float(np.quantile(waits, 0.95)) if waits else None
