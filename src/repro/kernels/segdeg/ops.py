"""Dispatching wrapper: Pallas banded segment-sum with XLA fallback.

On TPU the Pallas kernel runs compiled; on CPU it runs interpret=True
(used by tests); graphs whose band width exceeds ``k_cap`` (extreme hub
vertices) fall back to ``jax.ops.segment_sum``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.segdeg.kernel import banded_segsum_pallas, required_k_max
from repro.kernels.segdeg.ref import banded_segsum_ref


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU.  Backend errors
    propagate: a broken accelerator must not pass for "not a TPU"."""
    return jax.default_backend() == "tpu"


def make_banded_segsum(seg_ids_host, num_segments: int, *, k_cap: int = 16,
                       s_tile: int = 128, n_tile: int = 512,
                       use_kernel: bool = True, interpret=None):
    """Build a segsum closure for one static graph (segment ids fixed).

    Returns fn(values [N, Q], seg_ids [N]) -> [num_segments, Q] f32.
    """
    if not use_kernel:
        return functools.partial(banded_segsum_ref,
                                 num_segments=num_segments)
    k_max = required_k_max(seg_ids_host, num_segments, s_tile, n_tile)
    if k_max > k_cap:
        # hub-dominated band too wide: XLA scatter path wins
        return functools.partial(banded_segsum_ref,
                                 num_segments=num_segments)
    interp = (not on_tpu()) if interpret is None else interpret

    def fn(values, seg_ids):
        return banded_segsum_pallas(
            values, seg_ids, num_segments=num_segments, k_max=k_max,
            s_tile=s_tile, n_tile=n_tile, interpret=interp)

    return fn
