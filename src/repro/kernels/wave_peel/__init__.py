"""Fused Pallas peel-to-fixpoint wave step (kernel + dispatch + cost
model).  See kernel.py for the design; ``core.wave.make_wave_step_fn``
is the routing entry point used by the engines."""

from repro.kernels.wave_peel.kernel import wave_peel_pallas  # noqa: F401
from repro.kernels.wave_peel.ops import (fused_step_cost,  # noqa: F401
                                         fused_step_vmem_bytes,
                                         make_fused_wave_step)
