"""Least HBM bytes one peel step needs, counted from the algorithm.

One step peels ``lanes`` cells of one pool window to their fixpoint.  Whatever
implements it has at least to read the window's temporal edges once (source,
destination, timestamp, pair: four int32 each), its distinct pairs once (two
int32 endpoints) and its distinct vertices once (one int32 id), read every
lane's vertex mask and write it back (one bit per vertex of the deployment's
vertex width), and write each lane's TTI bounds and edge count (three int32).
Fixpoint iterations that stay on chip cost no further HBM bytes, so the count
does not depend on the implementation or on the number of iterations.
"""

from __future__ import annotations

import numpy as np

EDGE_BYTES = 16
PAIR_BYTES = 8
VERTEX_BYTES = 4
LANE_OUT_BYTES = 12


def window_counts(u: np.ndarray, v: np.ndarray, t: np.ndarray,
                  lo: int, hi: int):
    """(edges, distinct pairs, distinct vertices) of the window [lo, hi]."""
    m = (t >= lo) & (t <= hi) & (u != v)
    a = np.minimum(u[m], v[m]).astype(np.int64)
    b = np.maximum(u[m], v[m]).astype(np.int64)
    n = int(max(int(u.max()), int(v.max()))) + 1
    return (int(m.sum()), int(np.unique(a * n + b).size),
            int(np.unique(np.concatenate([a, b])).size))


def step_bytes(edges: int, pairs: int, vertices: int, lanes: int,
               num_vertices: int) -> int:
    """Least HBM bytes of one step over a window with that many edges,
    pairs and vertices, ``lanes`` lanes wide, on a graph of
    ``num_vertices`` vertices."""
    mask = 4 * -(-int(num_vertices) // 32)
    return (EDGE_BYTES * edges + PAIR_BYTES * pairs + VERTEX_BYTES * vertices
            + lanes * (2 * mask + LANE_OUT_BYTES))
