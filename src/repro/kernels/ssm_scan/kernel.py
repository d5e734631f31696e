"""Pallas TPU kernel: diagonal SSM scan with VMEM-resident state.

EXPERIMENTS §Perf (jamba hillclimb, iteration 1) showed that neither
`associative_scan` (2·log2(c) full-array HBM passes) nor an unrolled chunk
(per-step carry round-trips at XLA op granularity) reaches the intrinsic
traffic of the Mamba recurrence.  This kernel does: the running state lives
in a VMEM scratch across the sequential grid dimension, so HBM traffic is
exactly read(log_a) + read(bx) + write(states) — 3 passes instead of ~24.

Grid: (B, F_tiles, S_chunks) with S innermost/sequential; the scratch
carries (1, F_TILE) state between consecutive chunks of the same (b, f)
lane.  Validated in interpret mode against ref.ssm_scan_ref.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(la_ref, bx_ref, s0_ref, out_ref, state, *, s_chunk: int):
    sc = pl.program_id(2)

    @pl.when(sc == 0)
    def _init():
        state[0, :] = s0_ref[0, :]

    def step(i, _):
        new = jnp.exp(la_ref[0, i, :]) * state[0, :] + bx_ref[0, i, :]
        state[0, :] = new
        out_ref[0, i, :] = new
        return 0

    jax.lax.fori_loop(0, s_chunk, step, 0)


@functools.partial(jax.jit, static_argnames=("s_chunk", "f_tile",
                                             "interpret"))
def ssm_scan_pallas(log_a: jnp.ndarray, bx: jnp.ndarray, s0: jnp.ndarray,
                    *, s_chunk: int = 128, f_tile: int = 512,
                    interpret: bool = True) -> jnp.ndarray:
    """log_a/bx: [B, S, F]; s0: [B, F] -> all states [B, S, F] (f32)."""
    b, s, f = log_a.shape
    s_pad = -(-s // s_chunk) * s_chunk
    f_pad = -(-f // f_tile) * f_tile
    la = jnp.pad(log_a.astype(jnp.float32),
                 ((0, 0), (0, s_pad - s), (0, f_pad - f)))
    bxp = jnp.pad(bx.astype(jnp.float32),
                  ((0, 0), (0, s_pad - s), (0, f_pad - f)))
    s0p = jnp.pad(s0.astype(jnp.float32), ((0, 0), (0, f_pad - f)))
    grid = (b, f_pad // f_tile, s_pad // s_chunk)

    def in_idx(bi, fi, si):
        return (bi, si, fi)

    def s0_idx(bi, fi, si):
        return (bi, fi)

    out = pl.pallas_call(
        functools.partial(_kernel, s_chunk=s_chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, s_chunk, f_tile), in_idx),
            pl.BlockSpec((1, s_chunk, f_tile), in_idx),
            pl.BlockSpec((1, f_tile), s0_idx),
        ],
        out_specs=pl.BlockSpec((1, s_chunk, f_tile), in_idx),
        out_shape=jax.ShapeDtypeStruct((b, s_pad, f_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, f_tile), jnp.float32)]
        if pltpu else None,
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(la, bxp, s0p)
    return out[:, :s, :f]
