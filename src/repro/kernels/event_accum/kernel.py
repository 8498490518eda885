"""Event-driven synaptic accumulation — the router/gather path.

This is the latency-oriented sibling of spike_matmul: work scales with the
number of ACTIVE events, not with N_in. Each grid step processes one batch
row's whole time window against one 128-lane neuron block; event ids index
weight ROWS held in VMEM (the BRAM-resident packed-synapse analogue), and
masked rows (PAD = -1) contribute exactly zero, preserving integer
determinism.

    grid = (B, N_pad // bn)
    ids block   (1, T, E_max)    int32  SMEM   per-event scalars
    w block     (N_in, bn)       int8   VMEM   (784 x 128 int8 = 98 KiB)
    w32         (N_in, bn)       int32  VMEM   scratch: w widened per step
    out block   (1, T, bn)       int32

The E-loop is a fori_loop of dynamic single-row loads. A one-row load at a
dynamic offset is legal on TPU for a 32-bit VMEM buffer but not for a packed
int8 one, hence the widened scratch. Cost ~ O(E_active * bn) instead of
O(N_in * bn): the event-sparse structure the FPGA's router provides.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _event_accum_kernel(ids_ref, w_ref, o_ref, w32_ref, *, T: int, e_max: int):
    w32_ref[...] = w_ref[...].astype(jnp.int32)
    bn = o_ref.shape[2]

    def step(t, carry):
        def body(e, acc):
            nid = ids_ref[0, t, e]
            row = w32_ref[pl.ds(jnp.maximum(nid, 0), 1), :]
            return acc + jnp.where(nid >= 0, row, 0)

        o_ref[0, pl.ds(t, 1), :] = jax.lax.fori_loop(
            0, e_max, body, jnp.zeros((1, bn), jnp.int32))
        return carry

    jax.lax.fori_loop(0, T, step, 0)


def event_accum_kernel(ids: jnp.ndarray, w: jnp.ndarray, *,
                       block_n: int = 128,
                       interpret: bool = True) -> jnp.ndarray:
    """ids (B, T, E_max) int32 (PAD=-1), w (N_in, N_pad) int8
    -> currents (B, T, N_pad) int32."""
    B, T, E = ids.shape
    N_in, N = w.shape
    assert N % block_n == 0
    kernel = functools.partial(_event_accum_kernel, T=T, e_max=E)
    return pl.pallas_call(
        kernel,
        grid=(B, N // block_n),
        in_specs=[
            pl.BlockSpec((1, T, E), lambda b, n: (b, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((N_in, block_n), lambda b, n: (0, n)),
        ],
        out_specs=pl.BlockSpec((1, T, block_n), lambda b, n: (b, 0, n)),
        out_shape=jax.ShapeDtypeStruct((B, T, N), jnp.int32),
        scratch_shapes=[pltpu.VMEM((N_in, block_n), jnp.int32)],
        interpret=interpret,
    )(ids, w)
