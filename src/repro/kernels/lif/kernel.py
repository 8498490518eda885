"""Fused LIF kernel — membrane update + threshold compare + first-spike latch.

The FPGA evaluates one neuron group (128 neurons) per cycle against BRAM-held
state. The TPU-native tiling is the same co-design sweet spot: one 128-lane
neuron block of 8 batch rows per grid step — one int32 vreg of membrane
state — with the whole time window resident in VMEM and the T-loop fused
inside the kernel, so membrane state never round-trips to HBM.

    grid  = (B_pad // 8, N_pad // bn)
    currents block (T, 8, bn) int32   VMEM   (T=32, bn=128 -> 128 KiB)
    thresholds     (1, bn)    int32   VMEM
    out: first_spike (8, bn) int32, v_final (8, bn) int32

Integer semantics identical to core.lif_dynamics.lif_scan:
    v <- v - (v >> leak_shift) + I_t ; fire at v >= thr ; latch first time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS = 8     # batch rows per grid step: the int32 sublane tile


def _lif_kernel(cur_ref, thr_ref, first_ref, v_ref, *, T: int, leak_shift: int):
    thr = thr_ref[...]
    shape = first_ref.shape

    def step(t, carry):
        v, first = carry
        v = v - jnp.right_shift(v, leak_shift) + cur_ref[t]
        fired = (v >= thr) & (first == T)
        first = jnp.where(fired, t, first)
        return (v, first)

    v0 = jnp.zeros(shape, jnp.int32)
    f0 = jnp.full(shape, T, jnp.int32)
    v, first = jax.lax.fori_loop(0, T, step, (v0, f0))
    first_ref[...] = first
    v_ref[...] = v


def lif_fused_kernel(currents: jnp.ndarray, thresholds: jnp.ndarray,
                     leak_shift: int, *, block_n: int = 128,
                     interpret: bool = True) -> tuple[jnp.ndarray, jnp.ndarray]:
    """currents (T, B, N_pad) int32 with B a multiple of 8, thresholds
    (N_pad,) int32 -> (first_spike (B, N_pad) int32, v_final (B, N_pad))."""
    T, B, N = currents.shape
    assert N % block_n == 0, f"N_pad {N} must be a multiple of {block_n}"
    assert B % ROWS == 0, f"batch {B} must be a multiple of {ROWS}"
    kernel = functools.partial(_lif_kernel, T=T, leak_shift=leak_shift)
    out = pl.BlockSpec((ROWS, block_n), lambda b, n: (b, n))
    return pl.pallas_call(
        kernel,
        grid=(B // ROWS, N // block_n),
        in_specs=[
            pl.BlockSpec((T, ROWS, block_n), lambda b, n: (0, b, n)),
            pl.BlockSpec((1, block_n), lambda b, n: (0, n)),
        ],
        out_specs=[out, out],
        out_shape=[
            jax.ShapeDtypeStruct((B, N), jnp.int32),
            jax.ShapeDtypeStruct((B, N), jnp.int32),
        ],
        interpret=interpret,
    )(currents, thresholds.reshape(1, N))
