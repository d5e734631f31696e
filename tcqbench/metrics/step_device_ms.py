"""Step layer (core/wave.py, kernels/wave_peel): device time of one peel
step, from the profiler trace, whichever program ran it, in ms."""

from tcqbench import trace


def read(run):
    tr = run["trace"]
    if tr is None:
        return None
    secs = trace.module_seconds(tr["events"], tr["window"],
                                trace.STEP_PROGRAMS)
    return 1e3 * sum(secs) / len(secs) if secs else None
