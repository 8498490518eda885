"""Jitted public wrapper for the fused LIF kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.lif_dynamics import LIFResult
from repro.kernels.common import pad_dim, use_interpret
from repro.kernels.lif.kernel import ROWS, lif_fused_kernel


@functools.partial(jax.jit, static_argnames=("leak_shift",))
def lif_fused(currents: jnp.ndarray, thresholds: jnp.ndarray,
              leak_shift: int) -> LIFResult:
    """currents (T, B, N_pad) int32 (scan layout) -> LIFResult over (B, N_pad).

    Accepts the same layout core.lif_dynamics.lif_scan uses so the
    accelerator can swap implementations freely; the batch is padded to the
    kernel's 8-row tile and cut back."""
    B = currents.shape[1]
    first, v = lif_fused_kernel(pad_dim(currents, 1, ROWS), thresholds,
                                leak_shift, interpret=use_interpret())
    return LIFResult(first_spike=first[:B], v_final=v[:B])
