"""Pipelined wave engine equivalence + packed-transfer round trips.

Seeded (non-hypothesis) property matrix: the device-resident pipeline
(mode="wave") must return *exactly* the serial engine's result set —
same TTIs, same vertex sets, same edge counts — across random graphs ×
k × h × span × wave width.  (The seed stepwise engine that used to sit
between them was retired after PR 2; requesting it must fail loudly.)
Plus unit tests for the uint32 bitmask pack/unpack pair and the
distributed engine's packed result transfer.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import TCQEngine, TemporalGraph
from repro.core.engine import (pack_alive_u32, packed_width,
                               unpack_alive_u32)


def random_graph(seed: int, n_v: int = 20, n_e: int = 120, max_t: int = 16):
    rng = np.random.default_rng(seed)
    u = rng.integers(0, n_v, n_e)
    v = rng.integers(0, n_v, n_e)
    t = rng.integers(1, max_t + 1, n_e)
    return TemporalGraph.from_edges(u, v, t, num_vertices=n_v)


def assert_same_results(a, b):
    assert a.by_tti().keys() == b.by_tti().keys()
    for key, ca in a.by_tti().items():
        cb = b.by_tti()[key]
        assert np.array_equal(ca.vertices, cb.vertices), key
        assert ca.n_edges == cb.n_edges, key


@pytest.mark.parametrize("seed,k,h,span,wave", [
    (0, 2, 1, 1.0, 4),
    (1, 3, 1, 1.0, 8),
    (2, 2, 2, 1.0, 5),
    (3, 4, 1, 0.5, 3),
    (4, 2, 1, 0.4, 16),
    (5, 3, 2, 0.6, 2),
    (6, 1, 1, 1.0, 7),
])
def test_wave_modes_equal_serial(seed, k, h, span, wave):
    g = random_graph(seed)
    Ts, Te = g.span
    Te = Ts + max(1, int((Te - Ts) * span))
    eng = TCQEngine(g)
    serial = eng.query(k, Ts, Te, h=h)
    pipelined = eng.query(k, Ts, Te, h=h, mode="wave", wave=wave)
    assert_same_results(serial, pipelined)


def test_retired_stepwise_mode_raises():
    g = random_graph(0)
    Ts, Te = g.span
    with pytest.raises(ValueError, match="wave_stepwise"):
        TCQEngine(g).query(2, Ts, Te, mode="wave_stepwise")


def test_wave_on_dense_planted_graph():
    from repro.graphs import planted_cores

    g = planted_cores(seed=7)
    eng = TCQEngine(g)
    a = eng.query(3, 1, 40)
    b = eng.query(3, 1, 40, mode="wave", wave=6)
    assert_same_results(a, b)
    # pipeline accounting: every evaluated cell ran on some device step,
    # and results moved as packed words + scalar vectors only
    s = b.stats
    assert s.device_steps > 0 and s.host_syncs == s.device_steps
    w32 = packed_width(g.num_vertices)
    per_step = 6 * w32 * 4 + 6 * 4 * 3 + 4   # packed + lo/hi/ne + iters
    assert s.bytes_synced <= s.device_steps * per_step


def test_wave_with_forced_pallas_kernel():
    """Same results when the degree path runs the Pallas kernel
    (interpret mode on CPU)."""
    g = random_graph(11, n_v=16, n_e=80, max_t=8)
    Ts, Te = g.span
    ref = TCQEngine(g, use_kernel=False).query(2, Ts, Te, mode="wave")
    ker = TCQEngine(g, use_kernel=True).query(2, Ts, Te, mode="wave")
    assert_same_results(ref, ker)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_wave_windowed_tel_sub_span(use_kernel):
    """Sub-span queries peel against the truncated (sentinel-padded) TEL;
    results must match serial on the full TEL — on both degree paths
    (the Pallas path rebuilds its pair closure per window)."""
    g = random_graph(12, n_v=18, n_e=150, max_t=20)
    Ts, Te = g.span
    mid_lo, mid_hi = Ts + (Te - Ts) // 4, Ts + (3 * (Te - Ts)) // 4
    eng = TCQEngine(g, use_kernel=use_kernel)
    serial = eng.query(2, mid_lo, mid_hi)
    pipe = eng.query(2, mid_lo, mid_hi, mode="wave", wave=4)
    assert_same_results(serial, pipe)
    # the window cache was populated (the window is a strict edge subset)
    assert eng._win_cache


@pytest.mark.parametrize("num_vertices", [1, 31, 32, 33, 64, 100, 257])
def test_pack_unpack_roundtrip(num_vertices):
    rng = np.random.default_rng(num_vertices)
    masks = rng.random((5, num_vertices)) < 0.3
    packed = np.asarray(pack_alive_u32(jnp.asarray(masks),
                                       num_vertices=num_vertices))
    assert packed.shape == (5, packed_width(num_vertices))
    assert packed.dtype == np.uint32
    assert np.array_equal(unpack_alive_u32(packed, num_vertices), masks)


def test_pack_unpack_single_row():
    v = 70
    mask = np.zeros(v, bool)
    mask[[0, 31, 32, 63, 64, 69]] = True
    packed = np.asarray(pack_alive_u32(jnp.asarray(mask), num_vertices=v))
    assert np.array_equal(unpack_alive_u32(packed, v), mask)


def test_distributed_packed_transfer_matches_bool():
    from repro.core.distributed import DistributedTCQ
    from repro.graphs import planted_cores
    from repro.launch.mesh import make_mesh

    g = planted_cores(seed=3)
    mesh = make_mesh((1, 1), ("data", "model"))
    eng = DistributedTCQ(g, mesh)
    ts, te, k = [1, 5, 10], [40, 30, 20], 3
    alive, lo, hi, ne, _ = eng.query_wave(ts, te, k)
    packed, lo2, hi2, ne2, _ = eng.query_wave(ts, te, k, packed=True)
    v = eng.plan.num_vertices
    assert np.array_equal(unpack_alive_u32(np.asarray(packed), v),
                          np.asarray(alive))
    assert np.array_equal(np.asarray(lo), np.asarray(lo2))
    assert np.array_equal(np.asarray(ne), np.asarray(ne2))


def test_add_edges_empty_batch_is_noop():
    """Regression: empty dynamic batch used to crash on np.max([])."""
    from repro.graphs import paper_style_example

    g = paper_style_example()
    g2 = g.add_edges([], [], [])
    assert g2 is g
    # malformed (mismatched-length) batches must still fail loudly
    with pytest.raises(ValueError):
        g.add_edges([], [5], [7])


def test_all_negative_timestamps_match_oracle():
    """Regression: the tti_hi empty-fill was -1, which clamped TTIs for
    cores whose edges all have t < -1 (wrong keys in EVERY mode, or a
    KeyError on collection).  Now int32 min, like tti_lo's I32_MAX."""
    from repro.core import brute_force_query

    rng = np.random.default_rng(5)
    n_v, n_e = 12, 80
    u = rng.integers(0, n_v, n_e)
    v = rng.integers(0, n_v, n_e)
    t = rng.integers(-50, -2, n_e)
    g = TemporalGraph.from_edges(u, v, t, num_vertices=n_v)
    Ts, Te = g.span
    oracle = brute_force_query(g, 2, Ts, Te)
    eng = TCQEngine(g)
    for mode in ("serial", "wave"):
        kw = {} if mode == "serial" else {"mode": mode}
        res = eng.query(2, Ts, Te, **kw)
        assert set(c.tti for c in res.cores) == set(oracle.keys()), mode
        for c in res.cores:
            assert set(c.vertices.tolist()) == set(
                oracle[c.tti]["vertices"]), (mode, c.tti)


def test_wave_negative_timestamps():
    """Regression: the windowed TEL's sentinel padding must not collide
    with real negative timestamps (pad was t=-1; now int32 min)."""
    rng = np.random.default_rng(3)
    n_v, n_e = 14, 90
    u = rng.integers(0, n_v, n_e)
    v = rng.integers(0, n_v, n_e)
    t = rng.integers(-8, 8, n_e)
    g = TemporalGraph.from_edges(u, v, t, num_vertices=n_v)
    eng = TCQEngine(g)
    for lo, hi in [(-6, 6), (-8, -1), (-3, 7)]:
        serial = eng.query(2, lo, hi)
        wave = eng.query(2, lo, hi, mode="wave", wave=4)
        assert_same_results(serial, wave)
