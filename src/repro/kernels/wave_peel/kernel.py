"""Pallas TPU kernel: the whole peel-to-fixpoint wave step, fused.

The XLA composite (`core/wave.py`'s ``peel_to_fixpoint`` chain) runs the
fixpoint loop at HBM bandwidth: every iteration re-materializes the
[W, E] edge-activity mask, the [P, W] pair counts and the [2P, W] pair
contributions as separate fusion outputs.  This kernel runs the *entire*
fixpoint loop per lane tile with every intermediate resident in VMEM.

Layout (what Mosaic can lower: no vector gathers, no cumsum, no bool
carries).  Query lanes ride the 128-wide lane axis and the window's
*local* vertices / pairs ride the sublane axis, so every gather or
scatter is a dynamic single-row load or store driven by a scalar index
read from SMEM:

  grid = (W_pad / 128,)   one program owns a [V_loc, 128] lane slab

  * the wrapper (``ops.make_fused_wave_step``) compacts the window once
    on the host: live edges grouped by pair (the canonical sort), the
    window's local pairs, and their endpoints relabelled 0..V_loc-1;
    the index tables ride in SMEM, padded to the window's size class,
    with the live pair and vertex counts beside them as the loop bounds;
    slab rows past the live vertices enter as 0 and stay 0;
  * prologue: for every local pair, count its edges inside each lane's
    window and store ``ok[p] = count >= h`` (one row per pair);
  * fixpoint: a pair is active iff both endpoints are alive and
    ``ok[p]`` — so the degree of an alive vertex v is the sum over its
    half-pairs (v, o, p) of ``ok[p] * cur[o]``.  Degrees are computed
    Jacobi-style from the previous iteration's mask, then one dense pass
    applies ``cur & (deg >= k)`` and reports whether any lane changed;
  * epilogue: edge activity of the fixpoint (window & both endpoints
    alive) gives the per-lane edge count and TTI lo/hi.

Everything counts 0/1 values in int32, so the kernel is bit-identical to
the f32 composite (small integers are exact in f32).  The per-tile
iteration count is the composite's count restricted to that tile's lanes;
a converged lane is invariant under extra iterations, so max-over-tiles
equals the composite's ``iters`` exactly (seeded fuzz gate in
tests/test_kernels.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128          # query lanes per grid program (the vreg lane width)
STAT_ROWS = 8        # stats block rows: n_edges, tti_lo, tti_hi, iters, pad

_I32_MAX = jnp.iinfo(jnp.int32).max
_I32_MIN = jnp.iinfo(jnp.int32).min


def _kernel(t_ref, pend_ref, pu_ref, pv_ref, vend_ref, hpo_ref, hpp_ref,
            cnt_ref, prm_ref, alive_ref, cur_ref, st_ref, ok_ref, deg_ref):
    # the window's live pair and vertex counts, read at run time: one
    # compiled program serves every window of a size class
    n_pairs, n_verts = cnt_ref[0], cnt_ref[1]
    ts, te = prm_ref[0:1, :], prm_ref[1:2, :]
    kk, hh = prm_ref[2:3, :], prm_ref[3:4, :]
    zero = jnp.zeros((1, LANES), jnp.int32)

    def in_window(tv):
        return (tv >= ts) & (tv <= te)

    # prologue: ok[p] = (#edges of pair p inside the lane's window) >= h
    def pair_ok(p, e0):
        e1 = pend_ref[p]
        cnt = jax.lax.fori_loop(
            e0, e1,
            lambda e, c: c + in_window(t_ref[e]).astype(jnp.int32), zero)
        ok_ref[pl.ds(p, 1), :] = (cnt >= hh).astype(jnp.int32)
        return e1

    jax.lax.fori_loop(0, n_pairs, pair_ok, jnp.int32(0))
    cur_ref[...] = alive_ref[...]

    def vertex_degree(v, j0):
        j1 = vend_ref[v]

        def half_pair(j, acc):
            return acc + (ok_ref[pl.ds(hpp_ref[j], 1), :]
                          * cur_ref[pl.ds(hpo_ref[j], 1), :])

        deg_ref[pl.ds(v, 1), :] = jax.lax.fori_loop(j0, j1, half_pair, zero)
        return j1

    def body(state):
        it, _ = state
        jax.lax.fori_loop(0, n_verts, vertex_degree, jnp.int32(0))
        cur = cur_ref[...]
        new = jnp.where(deg_ref[...] >= kk, cur, 0)
        cur_ref[...] = new
        return it + 1, jnp.max(cur - new)

    iters, _ = jax.lax.while_loop(lambda s: s[1] > 0, body,
                                  (jnp.int32(0), jnp.int32(1)))

    # epilogue: edge activity of the fixpoint -> edge count + TTI lo/hi
    def pair_stats(p, carry):
        e0, ne, lo, hi = carry
        e1 = pend_ref[p]
        both = (cur_ref[pl.ds(pu_ref[p], 1), :]
                * cur_ref[pl.ds(pv_ref[p], 1), :]) > 0

        def edge(e, c):
            ne, lo, hi = c
            tv = t_ref[e]
            ea = in_window(tv) & both
            return (ne + ea.astype(jnp.int32),
                    jnp.where(ea, jnp.minimum(lo, tv), lo),
                    jnp.where(ea, jnp.maximum(hi, tv), hi))

        ne, lo, hi = jax.lax.fori_loop(e0, e1, edge, (ne, lo, hi))
        return e1, ne, lo, hi

    _, ne, lo, hi = jax.lax.fori_loop(
        0, n_pairs, pair_stats,
        (jnp.int32(0), zero, jnp.full((1, LANES), _I32_MAX, jnp.int32),
         jnp.full((1, LANES), _I32_MIN, jnp.int32)))
    st_ref[0:1, :] = ne
    st_ref[1:2, :] = lo
    st_ref[2:3, :] = hi
    st_ref[3:4, :] = jnp.broadcast_to(iters, (1, LANES))
    st_ref[4:STAT_ROWS, :] = jnp.zeros((STAT_ROWS - 4, LANES), jnp.int32)


def wave_peel_pallas(tables, counts, prm, alive, *, pair_rows: int,
                     interpret: bool):
    """Raw fused call over pre-padded arrays.

    tables: seven 1-D int32 SMEM tables (t, pair_end, pair_u, pair_v,
    vertex_end, hp_other, hp_pair) — see ``ops.make_fused_wave_step``;
    counts: [2] int32 SMEM (live local pairs, live local vertices), the
    loop bounds; prm: [8, W_pad] int32 rows ts, te, k, h; alive:
    [V_pad, W_pad] int32 0/1 lane slab (V_pad a multiple of 8, W_pad of
    ``LANES``), whose rows past the live vertices are 0.  ``pair_rows``
    (a multiple of 8, at least the live pairs) sizes the pair-ok scratch.

    Returns (alive [V_pad, W_pad] int32, stats [8, W_pad] int32 with rows
    n_edges, tti_lo, tti_hi, iters).
    """
    v_pad, w_pad = alive.shape
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    slab = pl.BlockSpec((v_pad, LANES), lambda q: (0, q))
    stat = pl.BlockSpec((STAT_ROWS, LANES), lambda q: (0, q))
    return pl.pallas_call(
        _kernel,
        grid=(w_pad // LANES,),
        in_specs=[smem] * (len(tables) + 1) + [stat, slab],
        out_specs=[slab, stat],
        out_shape=[jax.ShapeDtypeStruct((v_pad, w_pad), jnp.int32),
                   jax.ShapeDtypeStruct((STAT_ROWS, w_pad), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((pair_rows, LANES), jnp.int32),
                        pltpu.VMEM((v_pad, LANES), jnp.int32)],
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="wave_peel",
    )(*tables, counts, prm, alive)
