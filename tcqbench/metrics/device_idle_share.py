"""Device: share of the traced window in which no operation ran on the
device, in %."""

from tcqbench import trace


def read(run):
    tr = run["trace"]
    if tr is None:
        return None
    busy = trace.busy_seconds(tr["events"], tr["window"])
    if busy is None:
        return None
    span = (tr["window"][1] - tr["window"][0]) / 1e9
    return 100.0 * (1.0 - busy / span)
