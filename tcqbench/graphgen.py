"""Seeded temporal-graph generator calibrated to a deployment's published counts.

A configuration's ``graph`` block fixes the counts a deployment holds — vertices,
temporal edges, distinct static edges (pairs) and the timeline — and the assumed
shape parameters.  Interactions come in groups: a question thread on a Q&A
site, or a user friending a circle of accounts that know each other.  A group
has an initiator and ``1 + Poisson(group_extra)`` members, all drawn by
popularity ``w_i ~ (i + 1) ** -degree_skew``; every member interacts with the
initiator, and each two members with probability ``closure``.  A group starts
at a time drawn from a timeline whose activity grows as ``exp(growth * x)``,
or, for a ``burst_share`` of groups, near one of ``bursts`` burst centres, and
its interactions follow the start by an exponential delay of mean
``group_days``.  The first group of every vertex is initiated by it, so no
vertex is left without an edge.

:func:`generate` takes groups in order until exactly ``num_pairs`` distinct
pairs have appeared, then adds or drops repeat interactions until there are
exactly ``num_edges`` temporal edges; added repeats fall on a pair with
probability ``~ (w_u * w_v) ** repeat_skew``, ``group_days`` after its first.
Times are quantised to ``time_units`` equal units.  It returns ``(u, v, t)``
as int32 arrays with no self-loop.

The built arrays are cached under ``tcqbench/.cache/graphs`` keyed by the
configuration, the seed and a hash of this file and the graph block, so later
runs in the same checkout load them instead of generating them again.
"""

from __future__ import annotations

import hashlib
import json
import os
import pathlib

import numpy as np

CACHE_DIR = pathlib.Path(__file__).resolve().parent / ".cache" / "graphs"
_MAX_MEMBERS = 6


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def _draw(rng, cdf: np.ndarray, size: int) -> np.ndarray:
    """Categorical draws by inverse CDF."""
    return np.minimum(np.searchsorted(cdf, rng.random(size), side="right"),
                      cdf.size - 1)


def _start_times(rng, count: int, g: dict) -> np.ndarray:
    """Fractional group start times (outside [0, 1) only near the ends)."""
    growth = float(g["growth"])
    u = rng.random(count)
    x = np.log1p(u * np.expm1(growth)) / growth if growth else u
    burst = rng.random(count) < float(g["burst_share"])
    centres = rng.random(int(g["bursts"]))
    width = float(g["burst_days"]) / float(g["days"])
    nb = int(burst.sum())
    x[burst] = (centres[rng.integers(0, centres.size, nb)]
                + rng.normal(0.0, width, nb))
    return x


def _groups(rng, cdf, count: int, first_initiator: int, n: int, g: dict):
    """Edges of ``count`` groups, in group order: (a, b, group index)."""
    idx = np.arange(count, dtype=np.int64)
    init = np.where(first_initiator + idx < n, first_initiator + idx,
                    _draw(rng, cdf, count))
    size = np.minimum(1 + rng.poisson(float(g["group_extra"]), count),
                      _MAX_MEMBERS)
    mem = _draw(rng, cdf, count * _MAX_MEMBERS).reshape(count,
                                                        _MAX_MEMBERS)
    # the first member is never the initiator, so every group has an edge
    mem[:, 0] = np.where(mem[:, 0] == init, (init + 1) % n, mem[:, 0])
    slot = np.arange(_MAX_MEMBERS)[None, :]
    a, b, grp = [], [], []
    ok = slot < size[:, None]
    q, s = np.nonzero(ok)
    a.append(init[q])
    b.append(mem[q, s])
    grp.append(q)
    for i in range(_MAX_MEMBERS):
        for j in range(i + 1, _MAX_MEMBERS):
            link = (j < size) & (rng.random(count) < float(g["closure"]))
            q = np.flatnonzero(link)
            a.append(mem[q, i])
            b.append(mem[q, j])
            grp.append(q)
    a, b, grp = (np.concatenate(x) for x in (a, b, grp))
    order = np.argsort(grp, kind="stable")
    return a[order], b[order], grp[order]


def generate(g: dict, seed: int):
    """The configuration's temporal edge list ``(u, v, t)`` for one seed."""
    n = int(g["num_vertices"])
    e_want = int(g["num_edges"])
    p_want = int(g["num_pairs"])
    if e_want < p_want:
        raise ValueError("num_edges must be at least num_pairs")
    w = np.arange(1, n + 1, dtype=np.float64) ** -float(g["degree_skew"])
    w /= w.sum()
    cdf = np.cumsum(w)
    rng = _rng(seed, 1)
    keys, times, made = [], [], 0
    seen = np.zeros(0, np.int64)
    while seen.size < p_want:
        count = max(n - made, (p_want - seen.size) // 2 + 1024)
        a, b, grp = _groups(rng, cdf, count, made, n, g)
        start = _start_times(rng, count, g)
        keep = a != b
        a, b, grp = a[keep], b[keep], grp[keep]
        k = np.minimum(a, b) * n + np.maximum(a, b)
        keys.append(k)
        times.append(start[grp] + rng.exponential(
            float(g["group_days"]) / float(g["days"]), k.size))
        seen = np.unique(np.concatenate([seen, k]))
        made += count
    keys, x = np.concatenate(keys), np.concatenate(times)
    _, first = np.unique(keys, return_index=True)
    is_first = np.zeros(keys.size, bool)
    is_first[first] = True
    cut = int(np.searchsorted(np.cumsum(is_first), p_want)) + 1
    keys, x, is_first = keys[:cut], x[:cut], is_first[:cut]
    repeats = np.flatnonzero(~is_first)
    if repeats.size > e_want - p_want:
        drop = repeats[e_want - p_want:]
        keep = np.ones(keys.size, bool)
        keep[drop] = False
        keys, x = keys[keep], x[keep]
    elif repeats.size < e_want - p_want:
        rng2 = _rng(seed, 2)
        pk, px = keys[is_first], x[is_first]
        rw = (w[pk // n] * w[pk % n]) ** float(g["repeat_skew"])
        pick = _draw(rng2, np.cumsum(rw / rw.sum()),
                     e_want - p_want - repeats.size)
        keys = np.concatenate([keys, pk[pick]])
        x = np.concatenate([x, px[pick] + rng2.exponential(
            float(g["group_days"]) / float(g["days"]), pick.size)])
    units = int(g["time_units"])
    t = 1 + np.floor(np.clip(x, 0.0, np.nextafter(1.0, 0.0)) * units)
    perm = _rng(seed, 3).permutation(n)
    u = perm[keys // n].astype(np.int32)
    v = perm[keys % n].astype(np.int32)
    return u, v, t.astype(np.int32)


def relabel(u: np.ndarray, v: np.ndarray, t: np.ndarray, n: int,
            seed: int):
    """The same graph under the seed's vertex labels, its edges in the
    seed's order: every window, core and peel keeps its size, and the
    arrays, the pair order and the answers' vertex sets differ."""
    perm = _rng(seed, 4).permutation(n).astype(np.int32)
    order = _rng(seed, 5).permutation(u.size)
    return perm[u[order]], perm[v[order]], t[order]


def counts(u: np.ndarray, v: np.ndarray, t: np.ndarray, units: int,
           days: float) -> dict:
    """The published counts of a generated edge list, to compare with the
    configuration's ``published`` block."""
    lo = np.minimum(u, v).astype(np.int64)
    hi = np.maximum(u, v).astype(np.int64)
    n = int(max(u.max(), v.max())) + 1
    return {
        "vertices": int(np.unique(np.concatenate([u, v])).size),
        "temporal_edges": int(u.size),
        "static_edges": int(np.unique(lo * n + hi).size),
        "days": float(days) * (int(t.max()) - int(t.min()) + 1) / units,
    }


def _key(name: str, g: dict, seed: int) -> str:
    h = hashlib.sha256(pathlib.Path(__file__).read_bytes())
    h.update(json.dumps(g, sort_keys=True).encode())
    return f"{name}-{int(seed)}-{h.hexdigest()[:16]}"


def load_or_generate(name: str, g: dict, seed: int, cache_dir=CACHE_DIR):
    """Edges from the checkout's graph cache, else generated and cached.
    Returns ``(u, v, t, cached)``."""
    path = pathlib.Path(cache_dir) / (_key(name, g, seed) + ".npz")
    if path.exists():
        with np.load(path) as z:
            return z["u"], z["v"], z["t"], True
    u, v, t = generate(g, seed)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(f"{path.stem}.{os.getpid()}.tmp.npz")
    np.savez(tmp, u=u, v=v, t=t)
    os.replace(tmp, path)
    return u, v, t, False
