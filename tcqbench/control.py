"""The control of the answer comparison: the plain reference put in the
program's place with each peel cut short after a few rounds, an approximate
answer that breaks the configuration's exactness guarantee.  The comparison
that decides ``correct`` has to count its answers as wrong.

    python3 -m tcqbench.control --workload mathoverflow.adhoc \
        --seconds 50 --seeds 11 12 13 --rounds 2

For each seed it builds the cell's graph and timed requests as a run does,
puts the control's answers where a run puts the program's, and prints what
the run's own comparison (``harness.compare``) makes of them, each number
beside its limit.  The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from tcqbench import harness, reference
from tcqbench.registry import Registry


def control_readings(workload: str, seed: int, seconds: float, rounds: int,
                     registry=None) -> dict:
    reg = registry or Registry()
    cell = reg.workload(workload)
    cfg = reg.config(cell["config"])
    mix = reg.traffic(cell["traffic"], cell["config"])
    (u, v, t, _), _, reqs = harness.inputs(reg, cfg, mix, seed, seconds)
    t0 = time.perf_counter()
    answers = [reference.digest(reference.tcq(
        u, v, t, r["k"], r["h"], r["ts"], r["te"], max_peel_rounds=rounds))
        for r in reqs]
    compared = harness.compare(answers, reqs, u, v, t)
    return {"workload": workload, "seed": seed, "requests": len(reqs),
            "correct": harness.is_correct(compared), "compared": compared,
            "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m tcqbench.control")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rounds", type=int, default=2)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        print(json.dumps(control_readings(args.workload, seed, args.seconds,
                                          args.rounds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
