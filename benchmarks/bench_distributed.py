"""Sharded streaming pipeline benchmark + equivalence gate.

Drains one overlapping-window request batch through the single-device
``TCQService`` and through mesh-backed services on several shapes of an
8-virtual-device mesh (``--xla_force_host_platform_device_count=8`` in a
subprocess: jax locks the device count at first init).  Reports per-shape
aggregate qps and scaling efficiency, asserts every sharded run is
bit-identical to the single-device drain, and enforces the aggregate-qps
floor: the best mesh shape must beat the single-device pipeline by at
least ``REPRO_DIST_FLOOR`` (default 1.5x).

On one physical CPU core the win is host-overhead amortization — a
lane-sharded pool packs ``lane_shards`` times the lanes into each
dispatched step, so per-step dispatch/fetch/bookkeeping is paid once for
L shards' worth of peeling (~6x fewer device steps here) — which is
exactly the term that survives on real multi-chip meshes after per-chip
compute stops shrinking.  The workload is sized so per-step host overhead
is a visible fraction of the drain (small dense graph, many overlapping
windows); timing interleaves single/mesh rounds and takes best-of-N per
engine so background load on the host hits both pipelines alike.

``REPRO_BENCH_SMOKE=1`` times only the widest mesh shape (CI mode); the
floor is enforced in both modes.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from typing import List

from benchmarks.common import SMOKE

FLOOR = float(os.environ.get("REPRO_DIST_FLOOR", "1.5"))

# mesh shapes (lane_shards, model_shards) over the 8 fake devices
SHAPES = [(8, 1), (4, 2), (2, 4)]

# Tuned drain: V/E/span small enough that one peel step is host-overhead
# bound, 64 half-span windows so the lane pools stay saturated.  depth=1
# for both engines — with host and virtual devices sharing one core there
# is no compute to overlap, and a deeper ring only adds in-flight staleness.
CFG = {"V": 64, "E": 192, "span": 128, "requests": 64, "k": 2,
       "depth": 1, "rounds": 3}

_WORKER = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys; sys.path.insert(0, "src")
import json, time
import numpy as np, jax
from repro.core import TCQService
from repro.graphs import powerlaw_temporal
from repro.launch.mesh import make_mesh

cfg = json.loads(sys.argv[1])
g = powerlaw_temporal(cfg["V"], cfg["E"], cfg["span"], seed=9)
lo, hi = g.span
rng = np.random.default_rng(1)
reqs = []
for _ in range(cfg["requests"]):
    a = int(rng.integers(lo, lo + max(1, (hi - lo) // 3)))
    b = a + (hi - lo) // 2 + int(rng.integers(0, max(1, (hi - lo) // 6)))
    reqs.append(dict(k=cfg["k"], ts=a, te=min(b, hi)))


def mk(mesh):
    kw = {} if mesh is None else {"mesh": mesh}
    return TCQService(g, cache=False, retain_snapshots=False,
                      depth=cfg["depth"], **kw)


def drain_round(svc):
    for r in reqs:
        svc.submit(r)
    t0 = time.perf_counter()
    out = svc.run_until_idle()
    dt = time.perf_counter() - t0
    svc.completed.clear()
    return dt, out


def digest(tickets):
    out = []
    for t in sorted(tickets, key=lambda t: t.id):
        out.append(sorted((k, tuple(c.vertices.tolist()), c.n_edges)
                          for k, c in t.result.by_tti().items()))
    return out


entries = [("single", None)]
for L, M in cfg["shapes"]:
    entries.append((f"{L}x{M}", make_mesh((L, M), ("data", "model"))))

svcs, digests = {}, {}
for name, mesh in entries:                 # warm round: compiles + digest
    svcs[name] = mk(mesh)
    _, out = drain_round(svcs[name])
    digests[name] = digest(out)
want = digests["single"]

best = {name: float("inf") for name, _ in entries}
for _ in range(cfg["rounds"]):             # interleave: noise hits all alike
    for name, _ in entries:
        dt, _ = drain_round(svcs[name])
        best[name] = min(best[name], dt)

base_wall = best["single"]
rows = [{"bench": "distributed", "mesh": "single", "devices": 1,
         "combine": "-", "t_s": base_wall,
         "qps": len(reqs) / base_wall, "speedup": 1.0, "efficiency": 1.0,
         "equivalent": True, "collective_bytes": 0,
         "mean_shard_occupancy": 0.0}]
for (L, M), (name, _) in zip(cfg["shapes"], entries[1:]):
    svc, wall = svcs[name], best[name]
    occ = [p["shard_occupancy"] for p in svc.pool_log
           if p.get("shard_occupancy")]
    rows.append({"bench": "distributed", "mesh": name, "devices": L * M,
                 "combine": svc.stats["distributed"]["combine"],
                 "t_s": wall, "qps": len(reqs) / wall,
                 "speedup": base_wall / wall,
                 "efficiency": base_wall / wall / (L * M),
                 "equivalent": digests[name] == want,
                 "collective_bytes":
                     svc.stats["distributed"]["collective_bytes"],
                 "mean_shard_occupancy":
                     (float(np.mean([np.mean(o) for o in occ]))
                      if occ else 0.0)})
print("ROWS::" + json.dumps(rows))
"""


def run() -> List[dict]:
    cfg = dict(CFG)
    cfg["shapes"] = SHAPES[:1] if SMOKE else SHAPES
    out = subprocess.run(
        [sys.executable, "-c", _WORKER, json.dumps(cfg)],
        capture_output=True, text=True, timeout=1800,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    if out.returncode != 0:
        raise RuntimeError("bench_distributed worker failed:\n"
                           + out.stderr[-3000:])
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("ROWS::")][-1]
    rows = json.loads(line[len("ROWS::"):])

    bad = [r["mesh"] for r in rows if not r["equivalent"]]
    if bad:
        raise RuntimeError(
            f"sharded pipeline diverged from single-device on {bad}")
    best = max((r for r in rows if r["mesh"] != "single"),
               key=lambda r: r["speedup"])
    gate_ok = best["speedup"] >= FLOOR
    rows.append({"bench": "distributed_speedup", "best_mesh": best["mesh"],
                 "speedup": best["speedup"], "floor": FLOOR,
                 "gate_ok": gate_ok})
    if not gate_ok:
        raise RuntimeError(
            f"aggregate-qps floor violated: best mesh {best['mesh']} is "
            f"{best['speedup']:.2f}x single-device (floor {FLOOR}x)")
    return rows


if __name__ == "__main__":
    for r in run():
        print(r)
