"""Jitted public wrapper for event accumulation."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.common import use_interpret
from repro.kernels.event_accum.kernel import event_accum_kernel


@jax.jit
def event_accum(ids: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """ids (B, T, E_max) int32, w (N_in, N_pad) int8 -> (B, T, N_pad) int32."""
    return event_accum_kernel(ids, w, interpret=use_interpret())
