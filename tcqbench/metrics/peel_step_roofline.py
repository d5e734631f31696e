"""Step layer (core/wave.py, kernels/wave_peel): the least HBM bytes a peel
step needs (tcqbench/workmodel.py) over the chip's published HBM bandwidth,
as a share of the measured step device time, in %."""

from tcqbench import trace


def read(run):
    tr, pools = run["trace"], run["pools"]
    steps = sum(p["device_steps"] for p in pools)
    if tr is None or not steps or run["peaks"] is None:
        return None
    secs = trace.module_seconds(tr["events"], tr["window"],
                                trace.STEP_PROGRAMS)
    if not secs:
        return None
    least = sum(p["least_bytes_per_step"] * p["device_steps"]
                for p in pools) / steps / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (sum(secs) / len(secs))
