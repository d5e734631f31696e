"""Pallas TPU kernel: banded segment-sum as MXU one-hot matmuls.

The TCQ engine's hot spot is the two-level degree reduction
(edges -> pairs -> vertices) over a wave of Q query cells.  Segment ids are
SORTED (the ArrayTEL canonical order), so each input tile of N_TILE rows
touches a contiguous band of output segments.  The kernel exploits this:

  grid = (Q_tiles, S_tiles, K)      K = max input tiles per output band
  out[o] accumulates over the K consecutive grid steps (standard matmul
  k-loop pattern: same output block revisited consecutively), each step
  contracting a (S_TILE x N_TILE) one-hot "segment membership" matrix with a
  (N_TILE x Q_TILE) value tile on the MXU.

Per-output-tile input ranges (in_lo / in_hi, in block units) are computed
with two searchsorteds and passed via scalar prefetch so BlockSpec index
maps can chase the band.  K is data-dependent (hub vertices widen the
band); the ops.py wrapper derives it from the graph once at engine build
and falls back to XLA segment_sum above a cap.

Validated on CPU with interpret=True against ref.banded_segsum_ref.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(in_lo_ref, in_hi_ref, seg_ref, val_ref, out_ref, *,
            s_tile: int, n_tile: int):
    q, o, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # contribution is void when this k-step is past the band's end
    valid = (in_lo_ref[o] + j) <= in_hi_ref[o]
    rows = o * s_tile + jax.lax.broadcasted_iota(
        jnp.int32, (s_tile, n_tile), 0)
    segs = seg_ref[0, :]                         # [n_tile]
    onehot = (rows == segs[None, :]).astype(jnp.float32)
    contrib = jnp.dot(onehot, val_ref[...].astype(jnp.float32),
                      preferred_element_type=jnp.float32)
    out_ref[...] += jnp.where(valid, 1.0, 0.0) * contrib


@functools.partial(jax.jit, static_argnames=(
    "num_segments", "k_max", "s_tile", "n_tile", "q_tile", "interpret"))
def banded_segsum_pallas(values: jnp.ndarray, seg_ids: jnp.ndarray,
                         *, num_segments: int, k_max: int,
                         s_tile: int = 128, n_tile: int = 512,
                         q_tile: int = 128,
                         interpret: bool = True) -> jnp.ndarray:
    """values: [N, Q] (any float dtype); seg_ids: [N] int32 sorted; returns
    [num_segments, Q] f32.  k_max: max input tiles overlapping one output
    tile (host-derived from the static graph)."""
    n, qdim = values.shape
    n_pad = -(-n // n_tile) * n_tile
    q_pad = -(-qdim // q_tile) * q_tile
    s_pad = -(-num_segments // s_tile) * s_tile
    vals = jnp.pad(values.astype(jnp.float32),
                   ((0, n_pad - n), (0, q_pad - qdim)))
    # pad segment ids with an out-of-range id => zero one-hot rows
    segs = jnp.pad(seg_ids.astype(jnp.int32), (0, n_pad - n),
                   constant_values=jnp.int32(s_pad))
    segs2 = segs[None, :]                        # 2-D for TPU vmem tiling

    n_s_tiles = s_pad // s_tile
    starts = jnp.arange(n_s_tiles, dtype=jnp.int32) * s_tile
    in_lo = jnp.searchsorted(segs, starts, side="left") // n_tile
    last = jnp.searchsorted(segs, starts + s_tile, side="left") - 1
    in_hi = jnp.maximum(last, 0) // n_tile
    in_hi = jnp.maximum(in_hi, in_lo)
    in_lo = in_lo.astype(jnp.int32)
    in_hi = in_hi.astype(jnp.int32)

    grid = (q_pad // q_tile, n_s_tiles, k_max)
    n_in_tiles = n_pad // n_tile

    def seg_index(q, o, j, lo, hi):
        blk = jnp.minimum(lo[o] + j, n_in_tiles - 1)
        return (0, blk)

    def val_index(q, o, j, lo, hi):
        blk = jnp.minimum(lo[o] + j, n_in_tiles - 1)
        return (blk, q)

    def out_index(q, o, j, lo, hi):
        return (o, q)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n_tile), seg_index),
            pl.BlockSpec((n_tile, q_tile), val_index),
        ],
        out_specs=pl.BlockSpec((s_tile, q_tile), out_index),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, s_tile=s_tile, n_tile=n_tile),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_pad, q_pad), jnp.float32),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
    )(in_lo, in_hi, segs2, vals)
    return out[:num_segments, :qdim]


def required_k_max(seg_ids, num_segments: int, s_tile: int = 128,
                   n_tile: int = 512) -> int:
    """Host-side: max input tiles overlapping any output tile (static per
    graph, used to size the kernel grid)."""
    import numpy as np

    segs = np.asarray(seg_ids)
    n_s_tiles = -(-max(num_segments, 1) // s_tile)
    starts = np.arange(n_s_tiles) * s_tile
    lo = np.searchsorted(segs, starts, side="left") // n_tile
    last = np.maximum(np.searchsorted(segs, starts + s_tile, "left") - 1, 0)
    hi = np.maximum(last // n_tile, lo)
    return int(np.max(hi - lo + 1)) if n_s_tiles else 1
