"""Grouped TTFS decode kernel — the RTL comparator tree, lane-parallel.

The FPGA decodes the label with a comparator tree over class-group first-spike
registers. The TPU version evaluates the same deterministic rule over a tile
of 8 batch rows per grid step: pack (time, neuron_index) into a single
monotone int32 key so that one min-reduction implements both the earliest-
time rule AND the lowest-index tie-break exactly:

    key(n) = first_spike[n] * n_out + n       (fits int32 for T*n_out < 2^31)

Group min over keys (a masked lane reduction per group), then arg-min over
groups (first-index tie-break), with the artifact's membrane fallback when
nothing fired. Bit-identical to core.ttfs.decode_labels by construction;
tests assert it.

    grid = (B_pad // 8,)
    first, v blocks (8, n)  int32  VMEM    (n = the full lane count)
    out             (8, 1)  int32
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS = 8     # batch rows per grid step: the int32 sublane tile


def _argbest(values, better):
    """First-index arg-best over a list of (R, 1) arrays -> (R, 1) int32."""
    best, idx = values[0], jnp.zeros(values[0].shape, jnp.int32)
    for g, val in enumerate(values[1:], start=1):
        take = better(val, best)
        best = jnp.where(take, val, best)
        idx = jnp.where(take, g, idx)
    return idx


def group_decode(first, v, *, n_groups: int, per_group: int, sentinel: int,
                 fallback: str):
    """first, v (R, L) int32 whose first n_groups*per_group lanes are the
    logical outputs (lanes past them are ignored) -> labels (R, 1) int32."""
    n_out = n_groups * per_group
    lane = jax.lax.broadcasted_iota(jnp.int32, first.shape, 1)
    key = first * n_out + lane
    big = jnp.iinfo(jnp.int32).max
    groups = [(lane >= g * per_group) & (lane < (g + 1) * per_group)
              for g in range(n_groups)]
    gkey = [jnp.min(jnp.where(m, key, big), axis=1, keepdims=True)
            for m in groups]
    ttfs_label = _argbest(gkey, jnp.less)
    earliest = jnp.min(jnp.where(lane < n_out, first, big), axis=1,
                       keepdims=True)
    if fallback == "membrane":
        small = jnp.iinfo(jnp.int32).min
        gv = [jnp.max(jnp.where(m, v, small), axis=1, keepdims=True)
              for m in groups]
        fb_label = _argbest(gv, jnp.greater)
    else:
        fb_label = jnp.zeros_like(ttfs_label)
    return jnp.where(earliest < sentinel, ttfs_label, fb_label)


def _decode_kernel(first_ref, v_ref, out_ref, **kw):
    out_ref[...] = group_decode(first_ref[...], v_ref[...], **kw)


def ttfs_decode_kernel(first_spike: jnp.ndarray, v_final: jnp.ndarray, *,
                       n_groups: int, per_group: int, sentinel: int,
                       fallback: str = "membrane",
                       interpret: bool = True) -> jnp.ndarray:
    """first_spike/v_final (B, G*P) int32, B a multiple of 8
    -> labels (B,) int32."""
    B, N = first_spike.shape
    assert N == n_groups * per_group and B % ROWS == 0
    kernel = functools.partial(_decode_kernel, n_groups=n_groups,
                               per_group=per_group, sentinel=sentinel,
                               fallback=fallback)
    rows = pl.BlockSpec((ROWS, N), lambda i: (i, 0))
    out = pl.pallas_call(
        kernel,
        grid=(B // ROWS,),
        in_specs=[rows, rows],
        out_specs=pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.int32),
        interpret=interpret,
    )(first_spike, v_final)
    return out[:, 0]
