"""Pallas banded-segsum kernel vs the pure-jnp oracle: shape/dtype sweeps.

The kernel runs in interpret mode on CPU (the TPU is the target; interpret
executes the same kernel body).  Sweeps cover ragged sizes, empty segments,
hub segments (band wider than one tile), padding tails, and dtypes.
"""

import os

import numpy as np
import pytest
import jax.numpy as jnp

from hypothesis import given, settings, strategies as st

from repro.kernels.segdeg.kernel import banded_segsum_pallas, required_k_max
from repro.kernels.segdeg.ops import make_banded_segsum
from repro.kernels.segdeg.ref import banded_segsum_ref


def _run(vals, segs, s):
    k_max = required_k_max(segs, s)
    out = banded_segsum_pallas(jnp.asarray(vals), jnp.asarray(segs),
                               num_segments=s, k_max=k_max, interpret=True)
    ref = banded_segsum_ref(jnp.asarray(vals.astype(np.float32)),
                            jnp.asarray(segs), s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n,s,q", [
    (1, 1, 1),            # degenerate
    (100, 7, 3),          # tiny ragged
    (1000, 300, 17),      # ragged everything
    (512, 128, 128),      # exactly tile-aligned
    (513, 129, 129),      # one past tile boundaries
    (4096, 1024, 64),     # multi-tile
    (2048, 4, 8),         # few fat segments (wide band)
])
def test_shapes_vs_ref(n, s, q):
    rng = np.random.default_rng(n + s + q)
    segs = np.sort(rng.integers(0, s, n)).astype(np.int32)
    vals = rng.normal(0, 1, (n, q)).astype(np.float32)
    _run(vals, segs, s)


@pytest.mark.parametrize("dtype", [np.float32, np.float16, np.int32])
def test_dtypes(dtype):
    rng = np.random.default_rng(3)
    n, s, q = 700, 150, 9
    segs = np.sort(rng.integers(0, s, n)).astype(np.int32)
    if dtype == np.int32:
        vals = rng.integers(0, 3, (n, q)).astype(dtype)
    else:
        vals = rng.normal(0, 1, (n, q)).astype(dtype)
    _run(vals.astype(np.float32), segs, s)


def test_empty_segments_and_gaps():
    segs = np.array([0, 0, 5, 5, 5, 299], dtype=np.int32)
    vals = np.ones((6, 4), dtype=np.float32)
    _run(vals, segs, 300)


def test_hub_segment_band_wider_than_tile():
    """One segment owns most rows => its output tile spans many input
    tiles (the k_max dimension does real work)."""
    n, s, q = 3000, 50, 5
    segs = np.concatenate([np.zeros(2500, np.int32),
                           np.sort(np.random.default_rng(0).integers(
                               1, s, 500)).astype(np.int32)])
    segs = np.sort(segs)
    vals = np.random.default_rng(1).normal(0, 1, (n, q)).astype(np.float32)
    assert required_k_max(segs, s) > 1
    _run(vals, segs, s)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 400), st.integers(1, 90), st.integers(1, 12),
       st.integers(0, 2 ** 31 - 1))
def test_property_random(n, s, q, seed):
    rng = np.random.default_rng(seed)
    segs = np.sort(rng.integers(0, s, n)).astype(np.int32)
    vals = rng.normal(0, 1, (n, q)).astype(np.float32)
    _run(vals, segs, s)


def test_dispatcher_falls_back_on_wide_band():
    segs = np.zeros(100_000, dtype=np.int32)  # one massive hub
    fn = make_banded_segsum(segs, 4, k_cap=4)
    vals = np.ones((100_000, 2), dtype=np.float32)
    out = fn(jnp.asarray(vals), jnp.asarray(segs))
    assert float(out[0, 0]) == 100_000.0


def test_wave_engine_with_kernel_matches_xla():
    """tcd_wave with the Pallas degree path == the XLA segment_sum path."""
    import jax

    from repro.core.wave import make_segsum_fns, tcd_wave
    from repro.graphs import planted_cores

    g = planted_cores(seed=5)
    tel = g.device_tel()
    ts = jnp.asarray([1, 5, 10], jnp.int32)
    te = jnp.asarray([40, 30, 25], jnp.int32)
    alive0 = jnp.ones((3, g.num_vertices), dtype=bool)
    outs = []
    for use_kernel in (False, True):
        sp, sv = make_segsum_fns(g, use_kernel=use_kernel)
        res = tcd_wave(tel, alive0, ts, te, 3, 1,
                       num_vertices=g.num_vertices, seg_pair=sp, seg_vert=sv)
        outs.append(res)
    np.testing.assert_array_equal(np.asarray(outs[0].alive),
                                  np.asarray(outs[1].alive))
    np.testing.assert_array_equal(np.asarray(outs[0].tti_lo),
                                  np.asarray(outs[1].tti_lo))
    np.testing.assert_array_equal(np.asarray(outs[0].n_edges),
                                  np.asarray(outs[1].n_edges))


def test_wave_engine_matches_oracle():
    from repro.core.oracle import peel_window
    from repro.core.wave import make_segsum_fns, tcd_wave
    from repro.graphs import powerlaw_temporal

    g = powerlaw_temporal(50, 300, 40, seed=4)
    tel = g.device_tel()
    sp, sv = make_segsum_fns(g, use_kernel=True)
    ts = [1, 3, 8]
    te = [40, 20, 30]
    res = tcd_wave(tel, jnp.ones((3, g.num_vertices), bool),
                   jnp.asarray(ts, jnp.int32), jnp.asarray(te, jnp.int32),
                   2, 1, num_vertices=g.num_vertices,
                   seg_pair=sp, seg_vert=sv)
    for i in range(3):
        em = peel_window(g, ts[i], te[i], 2)
        verts = (set(np.unique(np.concatenate(
            [g.src[em], g.dst[em]])).tolist()) if em.any() else set())
        got = set(np.flatnonzero(np.asarray(res.alive[i])).tolist())
        assert got == verts


# ------------------------------------------------- fused wave-peel kernel
# Seeded equivalence fuzz: the fused Pallas peel-to-fixpoint kernel
# (interpret mode on CPU — same kernel body as the TPU lowering) must be
# BIT-identical to the XLA composite on every StepResult field.  This is
# the correctness gate behind `BENCH_wave.json`'s kernel section and the
# CI `kernel_gate` job; REPRO_KERNEL_GATE=1 widens the sweep.

_FUZZ_SEEDS = range(24 if os.environ.get("REPRO_KERNEL_GATE") == "1" else 6)


def _random_temporal_graph(rng):
    from repro.core.graph import TemporalGraph

    v = int(rng.integers(3, 60))
    e = int(rng.integers(5, 400))
    tmax = int(rng.integers(4, 60))
    u = rng.integers(0, v, e)
    w = rng.integers(0, v, e)
    keep = u != w
    u, w = u[keep], w[keep]
    if u.size == 0:
        u, w = np.array([0]), np.array([v - 1])
    t = rng.integers(0, tmax, u.size)
    return TemporalGraph.from_edges(u, w, t, num_vertices=v), tmax


def _fuzz_fused_vs_composite(seed, *, capacity_padding, max_wave=12):
    from repro.core.graph import pow2_capacity
    from repro.core.wave import make_wave_step_fn

    rng = np.random.default_rng(seed)
    g, tmax = _random_temporal_graph(rng)
    if capacity_padding:
        # capacity-class TEL: sentinel edges (t=int32 min, pair_id=P_cap)
        # and sentinel half-pairs (hp_src=V_cap) in every table tail
        nv = pow2_capacity(g.num_vertices)
        tel = g.device_tel(edge_capacity=pow2_capacity(g.num_edges),
                           pair_capacity=pow2_capacity(g.num_pairs),
                           vertex_capacity=nv)
    else:
        nv = g.num_vertices
        tel = g.device_tel()
    fused = make_wave_step_fn(tel, nv, use_kernel=True)
    comp = make_wave_step_fn(tel, nv, use_kernel=False)
    assert fused.backend == "pallas" and fused.interpret
    assert comp.backend == "xla"

    W = int(rng.integers(1, max_wave))   # rarely a lane-tile multiple
    ts = rng.integers(0, tmax, W).astype(np.int32)
    te = (ts + rng.integers(0, tmax, W)).astype(np.int32)
    empty = rng.random(W) < 0.25     # pipeline-style idle padding lanes
    ts[empty], te[empty] = 0, -1
    k = rng.integers(1, 5, W).astype(np.int32)
    h = rng.integers(1, 3, W).astype(np.int32)
    if rng.random() < 0.5:
        alive = jnp.asarray(rng.random((W, nv)) < 0.8)   # warm-start rows
    else:
        alive = jnp.ones((W, nv), dtype=bool)

    args = (alive, jnp.asarray(ts), jnp.asarray(te),
            jnp.asarray(k), jnp.asarray(h))
    _assert_matches_composite(fused(*args), comp(*args), nv, f"seed={seed}")


def _assert_matches_composite(rf, rc, nv, what):
    from repro.core.wave import unpack_alive_u32

    for field in ("alive", "packed", "tti_lo", "tti_hi", "n_edges", "iters"):
        np.testing.assert_array_equal(
            np.asarray(getattr(rf, field)), np.asarray(getattr(rc, field)),
            err_msg=f"fused vs composite diverge on {field} ({what})")
    assert np.asarray(rf.packed).dtype == np.uint32
    np.testing.assert_array_equal(
        unpack_alive_u32(np.asarray(rf.packed), nv), np.asarray(rf.alive))


@pytest.mark.kernel_gate
@pytest.mark.parametrize("seed", _FUZZ_SEEDS)
def test_fused_wave_peel_matches_composite(seed):
    _fuzz_fused_vs_composite(1000 + seed, capacity_padding=False)


@pytest.mark.kernel_gate
@pytest.mark.parametrize("seed", _FUZZ_SEEDS)
def test_fused_wave_peel_matches_composite_capacity_padded(seed):
    _fuzz_fused_vs_composite(2000 + seed, capacity_padding=True)


@pytest.mark.kernel_gate
@pytest.mark.parametrize("seed", range(2))
def test_fused_wave_peel_matches_composite_multi_tile(seed):
    """Waves wider than one 128-lane tile: several grid programs, each
    with its own fixpoint loop; max-over-tiles must equal the composite's
    shared iteration count."""
    _fuzz_fused_vs_composite(3000 + seed, capacity_padding=bool(seed),
                             max_wave=300)


@pytest.mark.kernel_gate
def test_fused_step_through_tcd_wave():
    """The step_fn route of tcd_wave == the jitted XLA route, including
    the derived n_verts, on a planted-cores graph."""
    from repro.core.wave import make_segsum_fns, make_wave_step_fn, tcd_wave
    from repro.graphs import planted_cores

    g = planted_cores(seed=11)
    tel = g.device_tel()
    sp, sv = make_segsum_fns(g, use_kernel=False)
    step = make_wave_step_fn(tel, g.num_vertices, use_kernel=True)
    ts = jnp.asarray([1, 5, 0], jnp.int32)
    te = jnp.asarray([40, 30, -1], jnp.int32)
    k = jnp.asarray([3, 2, 1], jnp.int32)
    h = jnp.asarray([1, 1, 1], jnp.int32)
    alive0 = jnp.ones((3, g.num_vertices), dtype=bool)
    ref = tcd_wave(tel, alive0, ts, te, k, h, num_vertices=g.num_vertices,
                   seg_pair=sp, seg_vert=sv)
    got = tcd_wave(tel, alive0, ts, te, k, h, num_vertices=g.num_vertices,
                   step_fn=step)
    for field in ("alive", "tti_lo", "tti_hi", "n_edges", "n_verts", "iters"):
        np.testing.assert_array_equal(np.asarray(getattr(got, field)),
                                      np.asarray(getattr(ref, field)))


CLASS_V = 160          # vertex width of the size-class windows below
CLASS_T = 48           # their timestamps


def _class_window(kind, seed):
    """A window TEL of the fused step's smallest size class (128 edge
    rows) over ``CLASS_V`` vertices: ``matching`` has more local vertices
    than live edges (every pair one edge, no shared endpoint),
    ``pow2_edges`` exactly 128 live edges, ``random`` 40-120."""
    from repro.core.graph import TemporalGraph

    rng = np.random.default_rng(seed)
    if kind == "matching":
        n = int(rng.integers(30, 60))
        ends = rng.permutation(CLASS_V)[:2 * n]
        u, w = ends[:n], ends[n:]
    else:
        n = 128 if kind == "pow2_edges" else int(rng.integers(40, 120))
        u = rng.integers(0, CLASS_V, 4 * n)
        w = (u + rng.integers(1, CLASS_V, 4 * n)) % CLASS_V
    t = rng.integers(0, CLASS_T, u.size)
    # distinct (u, w, t) edges, so that exactly n stay live
    _, first = np.unique(np.stack([u, w, t]), axis=1, return_index=True)
    keep = np.sort(first)[:n]
    g = TemporalGraph.from_edges(u[keep], w[keep], t[keep],
                                 num_vertices=CLASS_V)
    assert g.num_edges == n
    return g.device_tel()


def _lane_args(seed, wave):
    """Host-built step arguments (building them runs no JAX program)."""
    rng = np.random.default_rng(seed)
    ts = rng.integers(0, CLASS_T, wave).astype(np.int32)
    te = (ts + rng.integers(0, CLASS_T, wave)).astype(np.int32)
    k = rng.integers(1, 4, wave).astype(np.int32)
    h = rng.integers(1, 3, wave).astype(np.int32)
    alive = rng.random((wave, CLASS_V)) < 0.9
    return tuple(jnp.asarray(a) for a in (alive, ts, te, k, h))


@pytest.mark.kernel_gate
@pytest.mark.parametrize("kind,wave", [("matching", 8), ("pow2_edges", 8),
                                       ("random", 200)])
def test_fused_step_shares_one_program_per_size_class(kind, wave):
    """Every window of a size class runs the class's one compiled
    program: after a first window of the class, a new window's step
    builds and runs with no backend compile, and still equals the
    composite on every field (W = 200 spans two lane tiles)."""
    import jax
    import jax.monitoring

    from repro.core.wave import make_wave_step_fn
    from repro.kernels.wave_peel.ops import make_fused_wave_step

    first = make_fused_wave_step(_class_window("random", 7), CLASS_V,
                                 interpret=True)
    jax.block_until_ready(first(*_lane_args(8, wave)))
    tel = _class_window(kind, 9)
    args = _lane_args(10, wave)
    compiles = []

    def listen(event, start, end, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(kw.get("fun_name"))

    jax.monitoring.register_event_time_span_listener(listen)
    try:
        fused = make_fused_wave_step(tel, CLASS_V, interpret=True)
        assert fused.program_warm(*args)
        rf = jax.block_until_ready(fused(*args))
        # restarted from its own fixpoint, the peel stops after one
        # iteration: the class's padding rows never move
        again = (rf.alive,) + args[1:]
        rf2 = jax.block_until_ready(fused(*again))
    finally:
        jax.monitoring.unregister_event_time_span_listener(listen)
    assert compiles == []
    assert int(rf2.iters) == 1
    assert fused.operand_shapes == first.operand_shapes
    assert fused.operand_shapes[0] == (128,)
    n_e, _, n_v = fused.local_counts
    if kind == "matching":
        assert n_v > n_e
    if kind == "pow2_edges":
        assert n_e == 128
    comp = make_wave_step_fn(tel, CLASS_V, use_kernel=False)
    _assert_matches_composite(rf, comp(*args), CLASS_V, kind)
    _assert_matches_composite(rf2, comp(*again), CLASS_V, kind + ", again")


def test_fused_step_class_over_smem_budget_takes_exact_sizes(monkeypatch):
    """A window whose size class would exceed the SMEM table budget while
    its exact sizes fit keeps the kernel, on tables of its exact sizes."""
    import repro.kernels.wave_peel.ops as ops
    from repro.core.wave import make_wave_step_fn

    tel = _class_window("random", 11)
    verts, _, n_e, n_p = ops._window_tables(tel)
    exact = ops.fused_step_smem_bytes(n_e, n_p, verts.size)
    assert ops.fused_step_smem_bytes(128, 128, CLASS_V) > exact
    monkeypatch.setattr(ops, "SMEM_TABLE_BUDGET", exact)
    fused = ops.make_fused_wave_step(tel, CLASS_V, interpret=True)
    assert fused.operand_shapes == [(n_e,), (n_p,), (n_p,), (n_p,),
                                    (verts.size,), (2 * n_p,), (2 * n_p,)]
    args = _lane_args(12, 8)
    comp = make_wave_step_fn(tel, CLASS_V, use_kernel=False)
    _assert_matches_composite(fused(*args), comp(*args), CLASS_V, "exact")


def test_fused_step_class_over_vmem_budget_buckets_pairs_and_vertices():
    """A window whose edge-keyed class (2 e vertex rows) would exceed the
    VMEM budget takes power-of-two rows of its own pairs and vertices,
    and still equals the composite."""
    import repro.kernels.wave_peel.ops as ops
    from repro.core.graph import pow2_capacity
    from repro.core.wave import make_wave_step_fn

    tel = _class_window("matching", 13)      # at most 120 vertices
    verts, _, n_e, n_p = ops._window_tables(tel)
    p_cap = pow2_capacity(n_p)
    v_cap = min(pow2_capacity(verts.size), CLASS_V)
    budget = ops.fused_step_vmem_bytes(p_cap, v_cap)
    assert ops.fused_step_vmem_bytes(128, CLASS_V) > budget
    assert ops.fused_step_class(n_e, n_p, verts.size, CLASS_V, budget) == \
        (128, p_cap, v_cap)
    fused = ops.make_fused_wave_step(tel, CLASS_V, interpret=True,
                                     vmem_budget_bytes=budget)
    assert fused.operand_shapes[1] == (p_cap,)
    assert fused.operand_shapes[4] == (v_cap,)
    args = _lane_args(14, 8)
    comp = make_wave_step_fn(tel, CLASS_V, use_kernel=False)
    _assert_matches_composite(fused(*args), comp(*args), CLASS_V, "tier 2")


def test_fused_program_warm_follows_jax_cache():
    """``program_warm`` turns true once the class's program has run and
    false again when JAX's caches are cleared, so a recompile is never
    counted as a reuse."""
    import jax

    from repro.kernels.wave_peel.ops import make_fused_wave_step

    args = _lane_args(15, 8)
    first = make_fused_wave_step(_class_window("random", 16), CLASS_V,
                                 interpret=True)
    jax.block_until_ready(first(*args))
    second = make_fused_wave_step(_class_window("random", 17), CLASS_V,
                                  interpret=True)
    assert second.program_warm(*args)
    # another lane count is another program
    assert not second.program_warm(*_lane_args(15, 16))
    jax.clear_caches()
    # a recompile of another program after the clear leaves this one cold
    jax.block_until_ready(second(*_lane_args(15, 16)))
    assert not second.program_warm(*args)
    jax.block_until_ready(second(*args))
    assert second.program_warm(*args)


def test_fused_vmem_budget_falls_back_to_composite():
    """A TEL whose working set exceeds the VMEM budget must yield the
    composite from the dispatcher (never a kernel that can't fit)."""
    from repro.core.wave import make_wave_step_fn
    from repro.graphs import planted_cores

    g = planted_cores(seed=3)
    tel = g.device_tel()
    step = make_wave_step_fn(tel, g.num_vertices, use_kernel=True,
                             interpret=False, vmem_budget_bytes=1024)
    assert step.backend == "xla"


def test_on_tpu_propagates_backend_errors(monkeypatch):
    """A backend that fails to initialize must surface, not read as
    "not a TPU" (which would silently route the chip to CPU paths)."""
    import jax

    from repro.kernels.segdeg.ops import on_tpu

    def broken():
        raise RuntimeError("backend failed to initialize")

    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="failed to initialize"):
        on_tpu()


def test_segsum_fns_cached_per_epoch():
    """make_segsum_fns: same (graph, epoch, path) => same closures; a
    streaming append (new epoch) refreshes them."""
    from repro.core.wave import make_segsum_fns
    from repro.graphs import planted_cores

    g = planted_cores(seed=9)
    a = make_segsum_fns(g, use_kernel=False)
    b = make_segsum_fns(g, use_kernel=False)
    assert a == b
    assert make_segsum_fns(g, use_kernel=True) != a
    g2 = g.add_edges([0], [1], [99])
    assert g2.epoch != g.epoch
    assert make_segsum_fns(g2, use_kernel=False) != a


# ---------------------------------------------------------------- ssm scan
def test_ssm_scan_kernel_matches_ref():
    """Pallas diagonal-SSM scan (VMEM-resident state) vs the lax.scan
    oracle — the register-residency fix identified in EXPERIMENTS §Perf B."""
    from repro.kernels.ssm_scan.kernel import ssm_scan_pallas
    from repro.kernels.ssm_scan.ref import ssm_scan_ref

    rng = np.random.default_rng(7)
    for b, s, f, sc, ft in [(1, 5, 3, 4, 128), (2, 300, 700, 64, 256),
                            (3, 128, 512, 128, 512)]:
        la = jnp.asarray(-np.abs(rng.normal(0.3, 0.5, (b, s, f))),
                         jnp.float32)
        bx = jnp.asarray(rng.normal(0, 1, (b, s, f)), jnp.float32)
        s0 = jnp.asarray(rng.normal(0, 1, (b, f)), jnp.float32)
        out = ssm_scan_pallas(la, bx, s0, s_chunk=sc, f_tile=ft,
                              interpret=True)
        ref = ssm_scan_ref(la, bx, s0)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
