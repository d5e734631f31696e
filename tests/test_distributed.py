"""Distributed TCQ engine: shard_map semantics on degenerate + subprocess
multi-device meshes, plan invariants, and both degree-combine variants.

``dist_gate``-marked tests are the sharded-pipeline equivalence gate: the
sharded engine/service must be bit-identical to the single-device paths.
CI runs them with ``REPRO_DIST_GATE=1`` for the widened multi-mesh sweep;
they also run (narrower) in plain tier-1."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import TCQEngine, TCQService
from repro.core.distributed import DistributedTCQ, ShardPlan, shard_graph
from repro.core.graph import _I32_MIN
from repro.core.oracle import peel_window
from repro.graphs import planted_cores, powerlaw_temporal
from repro.launch.mesh import make_mesh

_GATE = os.environ.get("REPRO_DIST_GATE") == "1"
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _check_engine(g, mesh, combine, k, cells):
    eng = DistributedTCQ(g, mesh, combine=combine)
    ts = [c[0] for c in cells]
    te = [c[1] for c in cells]
    alive, lo, hi, ne, iters = eng.query_wave(ts, te, k)
    for i, (a, b) in enumerate(cells):
        em = peel_window(g, a, b, k)
        verts = (set(np.unique(np.concatenate(
            [g.src[em], g.dst[em]])).tolist()) if em.any() else set())
        got = set(np.flatnonzero(
            np.asarray(alive[i])[:g.num_vertices]).tolist())
        assert got == verts, (combine, i)
        if em.any():
            assert (int(lo[i]), int(hi[i])) == (int(g.t[em].min()),
                                                int(g.t[em].max()))
            assert int(ne[i]) == int(em.sum())


@pytest.mark.parametrize("combine", ["psum", "rs_ag"])
def test_wave_on_unit_mesh(combine):
    g = planted_cores(seed=3)
    mesh = make_mesh((1, 1), ("data", "model"))
    _check_engine(g, mesh, combine, 3, [(1, 40), (5, 30), (10, 20), (1, 15)])


def test_pair_aligned_sharding_invariants():
    g = powerlaw_temporal(80, 600, 50, seed=1)
    for m in (2, 4, 8):
        plan = shard_graph(g, m)
        assert plan.src.shape[0] == m
        # every real edge appears exactly once; sentinels are inert
        real = plan.t != _I32_MIN
        assert int(real.sum()) == g.num_edges
        # pair-locality: local pair ids within [0, P_s)
        assert int(plan.pair_local[real].max()) < plan.num_pairs_shard
        # padded vertex space divisible by m
        assert plan.num_vertices % m == 0
        # capacity classes are pow2 so appends can land without reshape
        assert plan.e_cap & (plan.e_cap - 1) == 0
        assert plan.p_cap & (plan.p_cap - 1) == 0


def _real_edges(plan):
    """Multiset of real (src, dst, t) triples across all shards."""
    out = []
    for s in range(plan.num_shards):
        mask = plan.t[s] != _I32_MIN
        out.extend(zip(plan.src[s][mask].tolist(),
                       plan.dst[s][mask].tolist(),
                       plan.t[s][mask].tolist()))
    return sorted(out)


@pytest.mark.dist_gate
@pytest.mark.parametrize("seed", range(6 if _GATE else 2))
def test_shard_plan_append_matches_reshard(seed):
    """Epoch-versioned capacity-class TELs: appending edges and refreshing
    the plan in place must carry exactly the new graph's edges — the same
    multiset a from-scratch reshard would — and must keep array shapes
    (no recompile) while capacities suffice."""
    rng = np.random.default_rng(100 + seed)
    g = powerlaw_temporal(60, 400, 64, seed=seed)
    for m in (2, 4):
        plan = shard_graph(g, m)
        bounds0 = plan.bounds.copy()
        g2 = g
        for _ in range(4 if _GATE else 3):
            n = int(rng.integers(10, 80))
            u = rng.integers(0, 60, n)
            v = rng.integers(0, 60, n)
            keep = u != v
            t = rng.integers(1, 128, n)
            g2 = g2.add_edges(u[keep], v[keep], t[keep])
            shapes0 = (plan.src.shape, plan.pair_local.shape,
                       plan.hp_src.shape)
            same = plan.refresh(g2)
            assert plan.epoch == g2.epoch
            # frozen pair-key ownership: refresh never moves the cuts
            assert np.array_equal(plan.bounds, bounds0)
            if same:
                assert (plan.src.shape, plan.pair_local.shape,
                        plan.hp_src.shape) == shapes0
            want = sorted(zip(g2.src.tolist(), g2.dst.tolist(),
                              g2.t.tolist()))
            assert _real_edges(plan) == want
            # a from-scratch reshard carries the same edge multiset
            assert _real_edges(ShardPlan.build(g2, m)) == want
        # windowed extraction agrees with a direct host filter
        lo, hi = int(g2.t.min()), int(g2.t.max())
        ts, te = lo + (hi - lo) // 4, hi - (hi - lo) // 4
        src, dst, t, _ = plan.window_arrays(g2, ts, te)
        wmask = (g2.t >= ts) & (g2.t <= te)
        got = []
        for s in range(m):
            keepm = t[s] != _I32_MIN
            got.extend(zip(src[s][keepm].tolist(), dst[s][keepm].tolist(),
                           t[s][keepm].tolist()))
        assert sorted(got) == sorted(zip(g2.src[wmask].tolist(),
                                         g2.dst[wmask].tolist(),
                                         g2.t[wmask].tolist()))


_REQS = [dict(k=2, ts=5, te=60), dict(k=3, ts=10, te=70, h=2),
         dict(k=2, ts=1, te=40), dict(k=4, ts=20, te=80),
         dict(k=3, ts=30, te=75, h=1)]


def _assert_results_equal(got, want, ctx=""):
    assert len(got) == len(want), ctx
    for a, b in zip(got, want):
        aa, bb = a.by_tti(), b.by_tti()
        assert aa.keys() == bb.keys(), ctx
        for key in aa:
            assert np.array_equal(aa[key].vertices, bb[key].vertices), ctx
            assert aa[key].n_edges == bb[key].n_edges, ctx


@pytest.mark.dist_gate
@pytest.mark.parametrize("combine", ["psum", "rs_ag"])
def test_engine_mesh_unit_equivalence(combine):
    """1x1 mesh TCQEngine == plain TCQEngine: query_batch with mixed
    (k, h, window), plus re-query after an ingest epoch."""
    g = powerlaw_temporal(100, 900, 80, seed=7)
    plain = TCQEngine(g, cache=False)
    mesh = make_mesh((1, 1), ("data", "model"))
    eng = TCQEngine(g, cache=False, mesh=mesh, combine=combine)
    _assert_results_equal(eng.query_batch(_REQS), plain.query_batch(_REQS))
    dist = eng.stats()["distributed"]
    assert dist["combine"] == combine
    assert dist["pool_runs"] >= 1 and dist["device_steps"] >= 1
    # ingest an epoch; the sharded plan refreshes in place
    rng = np.random.default_rng(3)
    u, v = rng.integers(0, 100, 50), rng.integers(0, 100, 50)
    keep = u != v
    g2 = g.add_edges(u[keep], v[keep], rng.integers(1, 90, 50)[keep])
    plain.update_graph(g2)
    eng.update_graph(g2)
    _assert_results_equal(eng.query_batch(_REQS), plain.query_batch(_REQS))


@pytest.mark.dist_gate
def test_engine_mesh_kernel_rung_unit_equivalence():
    """The fused Pallas kernel routes as the per-shard local step on a
    unit mesh; results stay bit-identical to the plain engine whether or
    not the ladder demotes."""
    from repro.core.wave import ResilienceConfig

    g = planted_cores(seed=5)
    plain = TCQEngine(g, cache=False)
    mesh = make_mesh((1, 1), ("data", "model"))
    reqs = _REQS[:3]
    want = plain.query_batch(reqs)
    eng = TCQEngine(g, cache=False, mesh=mesh, use_kernel=True)
    _assert_results_equal(eng.query_batch(reqs), want, "kernel")
    lad = TCQEngine(g, cache=False, mesh=mesh, use_kernel=True,
                    resilience=ResilienceConfig())
    _assert_results_equal(lad.query_batch(reqs), want, "ladder")


@pytest.mark.dist_gate
def test_service_mesh_unit_equivalence():
    """1x1 mesh TCQService == plain TCQService, with per-shard occupancy
    and collective-bytes surfaced in the pool log."""
    g = powerlaw_temporal(60, 400, 40, seed=2)
    mesh = make_mesh((1, 1), ("data", "model"))
    svc_p = TCQService(g, cache=False)
    svc_d = TCQService(g, cache=False, mesh=mesh)
    reqs = [dict(k=2, ts=5, te=30), dict(k=3, ts=10, te=40, h=2),
            dict(k=1, ts=1, te=20), dict(k=2, ts=15, te=45)]
    for svc in (svc_p, svc_d):
        for r in reqs:
            svc.submit(r)
    out_p = {t.id: t for t in svc_p.run_until_idle()}
    out_d = {t.id: t for t in svc_d.run_until_idle()}
    assert out_p.keys() == out_d.keys()
    for tid in out_p:
        _assert_results_equal([out_d[tid].result], [out_p[tid].result])
    rec = svc_d.pool_log[0]
    assert rec["shard_occupancy"] and len(rec["shard_occupancy"]) == 1
    assert rec["collective_bytes"] == 0  # unit mesh: no wire traffic
    assert svc_d.stats["distributed"]["lane_shards"] == 1


_SUBPROCESS = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys; sys.path.insert(0, "src")
import numpy as np, jax
from repro.core.distributed import DistributedTCQ
from repro.core.oracle import peel_window
from repro.graphs import planted_cores
from repro.launch.mesh import make_mesh
g = planted_cores(seed=3)
mesh = make_mesh((2, 4), ("data", "model"))
for combine in ("psum", "rs_ag"):
    eng = DistributedTCQ(g, mesh, combine=combine)
    ts, te, k = [1, 5, 10, 1], [40, 30, 20, 15], 3
    alive, lo, hi, ne, it = eng.query_wave(ts, te, k)
    for i in range(4):
        em = peel_window(g, ts[i], te[i], k)
        verts = set(np.unique(np.concatenate([g.src[em], g.dst[em]])).tolist()) if em.any() else set()
        got = set(np.flatnonzero(np.asarray(alive[i])[:g.num_vertices]).tolist())
        assert got == verts, (combine, i)
print("OK")
"""


def test_wave_on_2x4_mesh_subprocess():
    """Real multi-device shard_map semantics (8 fake CPU devices require a
    fresh process: jax locks the device count at first init)."""
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS],
                         capture_output=True, text=True, cwd=_ROOT,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


_MESH_EQUIV = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys; sys.path.insert(0, "src")
import json
import numpy as np, jax
from repro.core import TCQEngine, TCQService
from repro.graphs import powerlaw_temporal
from repro.launch.mesh import make_mesh

cases = json.loads(sys.argv[1])
g = powerlaw_temporal(100, 900, 80, seed=7)
reqs = [dict(k=2, ts=5, te=60), dict(k=3, ts=10, te=70, h=2),
        dict(k=2, ts=1, te=40), dict(k=4, ts=20, te=80),
        dict(k=3, ts=30, te=75)]

def check(got, want, ctx):
    assert len(got) == len(want), ctx
    for a, b in zip(got, want):
        aa, bb = a.by_tti(), b.by_tti()
        assert aa.keys() == bb.keys(), ctx
        for key in aa:
            assert np.array_equal(aa[key].vertices, bb[key].vertices), ctx
            assert aa[key].n_edges == bb[key].n_edges, ctx

plain = TCQEngine(g, cache=False)
want = plain.query_batch(reqs)
for L, M, combine in cases:
    mesh = make_mesh((L, M), ("data", "model"))
    eng = TCQEngine(g, cache=False, mesh=mesh, combine=combine)
    check(eng.query_batch(reqs), want, (L, M, combine, "batch"))
    d = eng.stats()["distributed"]
    assert (d["lane_shards"], d["model_shards"]) == (L, M)
    assert M == 1 or d["collective_bytes"] > 0, (L, M, combine)

# service: mid-flight admission + ingest across epochs
rng = np.random.default_rng(0)
u, v = rng.integers(0, 100, 40), rng.integers(0, 100, 40)
keep = u != v
extra = (u[keep], v[keep], rng.integers(1, 90, 40)[keep])
sreqs = [dict(k=2, ts=5, te=55), dict(k=3, ts=8, te=60),
         dict(k=2, ts=12, te=64, h=2), dict(k=3, ts=3, te=50)]
late = [dict(k=2, ts=6, te=58), dict(k=4, ts=10, te=62)]

def run_service(mesh):
    kw = {} if mesh is None else {"mesh": mesh}
    svc = TCQService(g, cache=False, **kw)
    for r in sreqs:
        svc.submit(r)
    fired = []
    def poll(s):
        if not fired:
            fired.append(1)
            s.push_edges(*extra)      # new epoch lands mid-serve
            for r in late:            # arrivals while the pool runs
                s.submit(r)
    out = svc.run_until_idle(poll)
    while svc.pending:
        out += svc.run_until_idle()
    assert svc.epoch == 1
    return {t.id: t for t in out}

base = run_service(None)
for L, M, combine in cases:
    mesh = make_mesh((L, M), ("data", "model"))
    got = run_service(mesh)
    assert base.keys() == got.keys(), (L, M)
    for tid in base:
        check([got[tid].result], [base[tid].result], (L, M, "svc", tid))
print("OK")
"""

_DEFAULT_CASES = [[1, 2, "psum"], [2, 2, "rs_ag"]]
_GATE_CASES = [[1, 2, "psum"], [1, 2, "rs_ag"], [2, 2, "psum"],
               [2, 2, "rs_ag"], [1, 8, "rs_ag"], [8, 1, "psum"]]


@pytest.mark.dist_gate
def test_mesh_equivalence_subprocess():
    """Sharded engine + service vs single-device, on real multi-device
    meshes (8 fake CPU devices need a fresh process: jax locks the device
    count at first init).  Mixed (k, h, window) batches, mid-flight
    admission, and ingest across epochs must all be bit-identical.
    REPRO_DIST_GATE=1 widens the mesh/combine sweep."""
    cases = _GATE_CASES if _GATE else _DEFAULT_CASES
    out = subprocess.run(
        [sys.executable, "-c", _MESH_EQUIV, json.dumps(cases)],
        capture_output=True, text=True, cwd=_ROOT, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


_SHARD_FAULT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys; sys.path.insert(0, "src")
import jax
from repro.core import ResilienceConfig, TCQService
from repro.core.faultinject import FaultPlan, FaultyStep
from repro.graphs import powerlaw_temporal
from repro.launch.mesh import make_mesh

g = powerlaw_temporal(64, 192, 128, seed=9)
lo, hi = g.span
third = (hi - lo) // 3
reqs = []                      # two disjoint groups -> two pools/ladders
for base in (lo, lo + 2 * third):
    for i in range(3):
        reqs.append(dict(k=2, ts=int(base + i),
                         te=int(min(base + third - i, hi))))


def digest(tickets):
    return [sorted((k, tuple(c.vertices.tolist()), c.n_edges)
                   for k, c in t.result.by_tti().items())
            for t in sorted(tickets, key=lambda t: t.id)]


mesh = make_mesh((8, 1), ("data", "model"))


def drain(wrapper):
    svc = TCQService(g, mesh=mesh, use_kernel=True, cache=False,
                     retain_snapshots=False,
                     resilience=ResilienceConfig(seed=0,
                                                 rung_wrapper=wrapper))
    for r in reqs:
        svc.submit(dict(r))
    return svc, digest(svc.run_until_idle())


_, want = drain(None)

state = {"armed": True}


def one_shot(name, fn):
    # ladders build per window pool: arm exactly one pool's kernel rung
    if name == "pallas" and state["armed"]:
        state["armed"] = False
        return FaultyStep(fn, FaultPlan(fail_at=(0,)))
    return fn


svc, got = drain(one_shot)
demo = [e for e in svc.engine.resilience_events()
        if e.get("reason") == "error"]
assert not state["armed"], "no pallas rung was ever built"
assert len(demo) == 1, f"expected exactly one demotion: {demo}"
assert got == want, "sharded drain diverged after per-shard rung fault"
backends = [p.get("backend") for p in svc.pool_log]
assert "pallas" in backends, f"healthy pool left the kernel: {backends}"
print("OK")
"""


@pytest.mark.dist_gate
def test_sharded_rung_fault_demotes_one_pool_subprocess():
    """Per-shard kernel fault on an 8-device lane-sharded mesh: only the
    faulted pool's ShardedDegradationLadder demotes (one event, reason
    'error'), the other pool stays on the fused kernel, and the whole
    drain is bit-identical to the fault-free sharded run."""
    out = subprocess.run([sys.executable, "-c", _SHARD_FAULT],
                         capture_output=True, text=True, cwd=_ROOT,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout


def test_dryrun_smoke_subprocess():
    """The dry-run entrypoint itself (reduced configs, real 512-device mesh
    construction) — proves the mesh + lowering pipeline end to end."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--smoke",
         "--arch", "gemma2-2b", "--shape", "train_4k,decode_32k",
         "--mesh", "both"],
        capture_output=True, text=True, cwd=_ROOT, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", _ROOT)})
    assert out.returncode == 0, (out.stdout[-1500:], out.stderr[-1500:])
    assert "0 failed" in out.stdout
