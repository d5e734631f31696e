"""The one traffic generator: a mix's data file in, a seeded request schedule out.

A mix file (``traffic/<mix>/<config>.json``) gives the Poisson arrival rate,
the window widths in distinct timestamps of the graph, the shares of each
``k`` and ``h``, and the warm-up requests.  Every seed gets the same N
requests, each a (width, k, h, place on the timeline), and the same N
arrival times, one realization of a Poisson process; the seed decides which
request comes at which arrival.  The N windows sit at the centres of N equal
strata of the timeline, so two seeds offer the same work, bursts and queueing
included, and differ in the order of the requests and in the graph the seed
builds.  No window repeats within a run or with a warm-up window.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def _shares(table: Dict[str, float], n: int) -> np.ndarray:
    """``n`` values in the table's proportions (largest remainder)."""
    keys = sorted(table, key=int)
    p = np.array([float(table[k]) for k in keys])
    exact = n * p / p.sum()
    cnt = np.floor(exact).astype(int)
    rest = n - int(cnt.sum())
    cnt[np.argsort(-(exact - cnt), kind="stable")[:rest]] += 1
    return np.repeat(np.array([int(k) for k in keys]), cnt)


def arrivals(mix: dict, n: int, seconds: float) -> np.ndarray:
    """``n`` Poisson arrival times inside the window, the same for every
    seed: the gaps are the exponential distribution's quantiles at
    (i + 1/2)/n, scaled to the mean gap 1/rate (so the last arrival falls
    inside the window), in one fixed random order."""
    if mix["arrivals"]["process"] != "poisson":
        raise ValueError(f"unknown arrival process "
                         f"{mix['arrivals']['process']!r}")
    q = (np.arange(n) + 0.5) / n
    g = -np.log1p(-q)
    g = g * (seconds * (1.0 - 0.5 / n) / g.sum())
    return np.cumsum(_rng(0, 14).permutation(g))


def _window(times: np.ndarray, start: int, width: int) -> Tuple[int, int]:
    return int(times[start]), int(times[start + width - 1])


def schedule(mix: dict, times: np.ndarray, seconds: float, seed: int,
             exclude: Sequence[Tuple[int, int]] = ()) -> List[dict]:
    """The timed requests of one run: dicts with ``arrive_s`` (seconds after
    the window opens), ``k``, ``h``, ``ts`` and ``te``, in arrival order."""
    n = max(1, int(round(float(mix["arrivals"]["rate_per_s"]) * seconds)))
    rng = _rng(seed, 11)
    arrive = arrivals(mix, n, seconds)
    lo, hi = (int(x) for x in mix["windows"]["width_timestamps"])
    if hi > times.size:
        raise ValueError("window wider than the graph's timeline")
    # the requests themselves do not depend on the seed, only their order
    fixed = _rng(0, 10)
    widths = lo + np.floor((np.arange(n) + 0.5) / n
                           * (hi - lo + 1)).astype(int)
    ks = fixed.permutation(_shares(mix["k"], n))
    hs = fixed.permutation(_shares(mix["h"], n))
    strata = fixed.permutation(n)
    taken = set(exclude)
    out = []
    for i in rng.permutation(n):
        room = times.size - int(widths[i]) + 1
        pos = (strata[i] + 0.5) / n
        start = min(room - 1, int(pos * room))
        win = _window(times, start, int(widths[i]))
        while win in taken:
            start = int(rng.integers(0, room))
            win = _window(times, start, int(widths[i]))
        taken.add(win)
        out.append({"arrive_s": float(arrive[len(out)]), "k": int(ks[i]),
                    "h": int(hs[i]), "ts": win[0], "te": win[1]})
    return out


def warmup(mix: dict, times: np.ndarray, seed: int) -> List[List[dict]]:
    """Set-up rounds, served one after another before the window opens.

    One request alone at each warm-up width, then two requests on one
    window together, so that the lane-pool widths a single request and
    co-admitted requests get are both compiled, then one more request
    alone, so the compiler has run a few times before the window opens.
    Their ``k`` is outside the timed mix, so the result cache they fill
    serves no timed request; with ``k = 1`` every cell has a core, so the
    warm-start programs compile."""
    w = mix["warmup"]
    rng = _rng(seed, 12)
    rounds = []
    widths = [int(x) for x in w["widths"]]
    for width in widths + [widths[-1], widths[0]]:
        start = int(rng.integers(0, times.size - width + 1))
        ts, te = _window(times, start, width)
        rounds.append([{"k": int(w["k"]), "h": 1, "ts": ts, "te": te}])
    pair = rounds[-2][0]
    rounds[-2] = [pair, dict(pair, h=2)]
    return rounds


def warmup_windows(rounds: List[List[dict]]) -> List[Tuple[int, int]]:
    return [(r["ts"], r["te"]) for rnd in rounds for r in rnd]
