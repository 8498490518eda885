"""Jitted public wrapper for the grouped TTFS decode kernel: pads the batch
to the kernel's 8-row tile."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.common import pad_dim, use_interpret
from repro.kernels.ttfs_decode.kernel import ROWS, ttfs_decode_kernel


@functools.partial(jax.jit, static_argnames=("n_groups", "per_group",
                                             "sentinel", "fallback"))
def ttfs_decode(first_spike: jnp.ndarray, v_final: jnp.ndarray, *,
                n_groups: int, per_group: int, sentinel: int,
                fallback: str = "membrane") -> jnp.ndarray:
    B = first_spike.shape[0]
    labels = ttfs_decode_kernel(
        pad_dim(first_spike, 0, ROWS), pad_dim(v_final, 0, ROWS),
        n_groups=n_groups, per_group=per_group, sentinel=sentinel,
        fallback=fallback, interpret=use_interpret())
    return labels[:B]
