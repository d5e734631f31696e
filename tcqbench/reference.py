"""Plain reference of the time-range (k, h)-core query, written from its
definition and sharing no code or data with the system under test.

For a temporal edge list and a window ``[Ts, Te]``, let ``T`` be the distinct
edge timestamps inside the window.  For every subinterval ``[a, b]`` with
``a <= b`` in ``T``, the (k, h)-core is the largest vertex set ``S`` in which
every vertex has at least ``k`` neighbours ``u`` in ``S`` such that the pair
``{u, v}`` has at least ``h`` temporal edges inside ``[a, b]``.  The core's
edges are every temporal edge inside ``[a, b]`` with both ends in ``S``; its
tightest time interval (TTI) is the least and greatest timestamp of those
edges.  The answer is every distinct non-empty core, keyed by its TTI, as
``{(lo, hi): (sorted vertex ids, number of edges)}``; :func:`digest` reduces
an answer to ``{(lo, hi): (SHA-1 of the vertex ids, number of edges)}`` so
that answers with cores of 10^5 vertices compare cheaply.

The one shortcut taken is the core's monotonicity in the window: the core of
``[a, b]`` lies inside the core of ``[a, b']`` for ``b <= b'``, so a row of
subintervals sharing ``a`` is peeled from the widest down, each starting from
the previous core, and stops at the first empty core.
"""

from __future__ import annotations

import hashlib
from typing import Dict, Tuple

import numpy as np

Answer = Dict[Tuple[int, int], Tuple[np.ndarray, int]]


def digest(answer) -> Dict[Tuple[int, int], Tuple[str, int]]:
    """``{tti: (SHA-1 of the sorted int64 vertex ids, number of edges)}``."""
    return {(int(lo), int(hi)): (hashlib.sha1(
        np.sort(np.asarray(verts, dtype="<i8")).tobytes()).hexdigest(),
        int(n)) for (lo, hi), (verts, n) in answer.items()}


class Window:
    """The edges of one query window, relabelled to local vertex and pair
    ids so that every peel works on arrays the size of the window."""

    def __init__(self, u, v, t, ts: int, te: int):
        keep = (t >= ts) & (t <= te) & (u != v)
        a, b, self.t = u[keep], v[keep], t[keep].astype(np.int64)
        self.verts, inv = np.unique(np.concatenate([a, b]),
                                    return_inverse=True)
        n = self.t.size
        lo = np.minimum(inv[:n], inv[n:])
        hi = np.maximum(inv[:n], inv[n:])
        pairs, self.pair = np.unique(lo * max(1, self.verts.size) + hi,
                                     return_inverse=True)
        nv = max(1, self.verts.size)
        self.pair_lo, self.pair_hi = pairs // nv, pairs % nv
        self.lo, self.hi = lo, hi
        self.times = np.unique(self.t)

    def core(self, a: int, b: int, k: int, h: int, start=None):
        """(alive vertex mask, core edge mask) of the (k, h)-core of
        ``[a, b]``, peeling from ``start`` (all vertices when None)."""
        nv, np_ = self.verts.size, self.pair_lo.size
        alive = np.ones(nv, bool) if start is None else start.copy()
        inwin = (self.t >= a) & (self.t <= b)
        live = np.flatnonzero(inwin)
        while True:
            # edges inside [a, b] whose ends are both still alive
            live = live[alive[self.lo[live]] & alive[self.hi[live]]]
            strong = np.flatnonzero(
                np.bincount(self.pair[live], minlength=np_) >= h)
            deg = (np.bincount(self.pair_lo[strong], minlength=nv)
                   + np.bincount(self.pair_hi[strong], minlength=nv))
            nxt = alive & (deg >= k)
            if np.array_equal(nxt, alive):
                return alive, inwin & alive[self.lo] & alive[self.hi]
            alive = nxt


def tcq(u, v, t, k: int, h: int, ts: int, te: int,
        max_peel_rounds: int = 0) -> Answer:
    """Every distinct (k, h)-core of every subinterval of ``[ts, te]``.

    ``max_peel_rounds`` > 0 cuts each peel short after that many rounds: an
    approximate answer that breaks the exactness guarantee, kept only as the
    control that the comparison must fail.
    """
    w = Window(np.asarray(u), np.asarray(v), np.asarray(t), ts, te)
    out: Answer = {}
    times = w.times
    for i in range(times.size):
        prev = None
        for j in range(times.size - 1, i - 1, -1):
            if max_peel_rounds:
                alive, ea = _core_cut(w, int(times[i]), int(times[j]), k, h,
                                      max_peel_rounds)
            else:
                alive, ea = w.core(int(times[i]), int(times[j]), k, h, prev)
            if not ea.any():
                break
            prev = alive
            tti = (int(w.t[ea].min()), int(w.t[ea].max()))
            if tti not in out:
                out[tti] = (w.verts[alive], int(ea.sum()))
    return out


def _core_cut(w: Window, a: int, b: int, k: int, h: int, rounds: int):
    """A peel stopped after ``rounds`` rounds from all vertices."""
    nv, np_ = w.verts.size, w.pair_lo.size
    alive = np.ones(nv, bool)
    inwin = (w.t >= a) & (w.t <= b)
    alive &= np.isin(np.arange(nv), np.concatenate([w.lo[inwin],
                                                    w.hi[inwin]]))
    for _ in range(rounds):
        ea = inwin & alive[w.lo] & alive[w.hi]
        strong = np.bincount(w.pair[ea], minlength=np_) >= h
        deg = (np.bincount(w.pair_lo[strong], minlength=nv)
               + np.bincount(w.pair_hi[strong], minlength=nv))
        alive = alive & (deg >= k)
    return alive, inwin & alive[w.lo] & alive[w.hi]
