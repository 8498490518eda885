"""Fused event→LIF→decode megakernel — the whole event pipeline in one pass.

The staged TPU event path launches three kernels and round-trips the full
(T, N_pad) int32 currents tensor through HBM between them:

    event_accum  -> HBM currents -> lif_fused -> HBM first/v -> ttfs_decode

The FPGA does none of that: event routing, membrane update, and the TTFS
decision happen in ONE pass with all state resident on-chip. This kernel is
the TPU-native equivalent: grid ``(B,)``, one batch row per grid step, the
packed event frames stream through the fused T-loop, weight rows are gathered
straight out of the VMEM-resident synapse block (the BRAM analogue), the
membrane updates and the first-spike latch happen in registers, and the
(T, N_pad) currents tensor is NEVER materialized. Per grid step:

    ids block  (1, T, E_max)  int32  SMEM   event frames for one batch row
    count      (1, 1, T)      int32  SMEM   active events per step (bounds
                                            the gather loop — work scales
                                            with ACTIVE events)
    w          (N_in, N_pad)  int8   VMEM   synapse block, resident across
                                            the whole grid
    w32        (N_in, N_pad)  int32  VMEM   scratch: the synapse block
                                            widened once, at grid step 0
    thr        (1, N_pad)     int32  VMEM
    out        first (1, 1, N_pad), v_final (1, 1, N_pad) int32

TPU tiling notes: every block's last two dims equal the array's own (the
unit axis on the outputs makes a one-row block legal); the per-event ids are
scalars, so they are read from SMEM; a one-row load at a dynamic offset is
legal for a 32-bit VMEM buffer but not for a packed int8 one, hence the
widened copy. The grid runs in order (``arbitrary``) because steps after the
first read the scratch that step 0 filled.

Integer semantics are identical to ``core.lif_dynamics.lif_scan`` fed by
``event_accum``: integer addition is associative, so summing gathered rows
event-by-event inside the T-loop is bit-exact with the staged path.

Three variants share that body:

* ``fused_event_lif_kernel`` — full-T, emits (first_spike, v_final).
* ``fused_event_lif_decode_kernel`` — appends the grouped-TTFS comparator
  tree so the kernel emits the LABEL directly (the paper's on-chip decision
  point).
* ``fused_event_lif_early_exit_kernel`` — latency mode: a while-loop T-loop
  that stops integrating at the first output spike, returning the step count
  (the paper's TTFS decision latency).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ttfs_decode.kernel import group_decode


def _widen_weights(w_ref, w32_ref):
    """Fill the int32 synapse scratch once; later grid steps reuse it."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        w32_ref[...] = w_ref[...].astype(jnp.int32)


def _gather_step(ids_ref, count_ref, w32_ref, t):
    """Accumulate the weight rows of step ``t``'s active events: (1, N)."""
    n = w32_ref.shape[1]

    def body(e, acc):
        nid = ids_ref[0, t, e]
        row = w32_ref[pl.ds(jnp.maximum(nid, 0), 1), :]
        return acc + jnp.where(nid >= 0, row, 0)

    return jax.lax.fori_loop(0, count_ref[0, 0, t], body,
                             jnp.zeros((1, n), jnp.int32))


def _lif_update(v, first, i_t, thr, t, T, leak_shift):
    v = v - jnp.right_shift(v, leak_shift) + i_t
    fired = (v >= thr) & (first == T)
    first = jnp.where(fired, t, first)
    return v, first


def _full_t(ids_ref, count_ref, w32_ref, thr, T, leak_shift):
    n = w32_ref.shape[1]

    def step(t, carry):
        v, first = carry
        i_t = _gather_step(ids_ref, count_ref, w32_ref, t)
        return _lif_update(v, first, i_t, thr, t, T, leak_shift)

    v0 = jnp.zeros((1, n), jnp.int32)
    f0 = jnp.full((1, n), T, jnp.int32)
    v, first = jax.lax.fori_loop(0, T, step, (v0, f0))
    return v, first


def _fused_kernel(ids_ref, count_ref, w_ref, thr_ref, first_ref, v_ref,
                  w32_ref, *, T: int, leak_shift: int):
    _widen_weights(w_ref, w32_ref)
    v, first = _full_t(ids_ref, count_ref, w32_ref, thr_ref[...], T,
                       leak_shift)
    first_ref[0] = first
    v_ref[0] = v


def _fused_decode_kernel(ids_ref, count_ref, w_ref, thr_ref,
                         first_ref, v_ref, label_ref, w32_ref, *,
                         T: int, leak_shift: int, n_groups: int,
                         per_group: int, fallback: str):
    _widen_weights(w_ref, w32_ref)
    v, first = _full_t(ids_ref, count_ref, w32_ref, thr_ref[...], T,
                       leak_shift)
    first_ref[0] = first
    v_ref[0] = v
    label_ref[0] = group_decode(first, v, n_groups=n_groups,
                                per_group=per_group, sentinel=T,
                                fallback=fallback)


def _fused_early_exit_kernel(ids_ref, count_ref, w_ref, thr_ref,
                             first_ref, v_ref, steps_ref, w32_ref, *,
                             T: int, leak_shift: int):
    """Latency mode: stop integrating once ANY neuron fired (TTFS decision
    point). Single neuron block per row so the exit condition is global —
    semantics identical to ``core.lif_dynamics.lif_scan_early_exit``."""
    _widen_weights(w_ref, w32_ref)
    n = w32_ref.shape[1]
    thr = thr_ref[...]

    def cond(state):
        t, v, first = state
        return (t < T) & jnp.all(first == T)

    def body(state):
        t, v, first = state
        i_t = _gather_step(ids_ref, count_ref, w32_ref, t)
        v, first = _lif_update(v, first, i_t, thr, t, T, leak_shift)
        return (t + 1, v, first)

    t0 = jnp.int32(0)
    v0 = jnp.zeros((1, n), jnp.int32)
    f0 = jnp.full((1, n), T, jnp.int32)
    t, v, first = jax.lax.while_loop(cond, body, (t0, v0, f0))
    first_ref[0] = first
    v_ref[0] = v
    steps_ref[0] = jnp.full((1, 1), t, jnp.int32)


def _call(kernel, name: str, ids, count, w, thresholds, n_scalar_outs: int,
          interpret: bool):
    """Shared pallas_call over grid (B,): (first (B, N), v (B, N)) plus
    ``n_scalar_outs`` per-row int32 scalars (B,). ``name`` names the
    kernel's custom call in the compiled program, whatever jitted function
    calls it; profiler traces find the kernel by it (``fused_event_lif``
    prefix)."""
    B, T, E = ids.shape
    N_in, N = w.shape
    row = pl.BlockSpec((1, 1, N), lambda b: (b, 0, 0))
    one = pl.BlockSpec((1, 1, 1), lambda b: (b, 0, 0))
    outs = pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, T, E), lambda b: (b, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, T), lambda b: (b, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((N_in, N), lambda b: (0, 0)),
            pl.BlockSpec((1, N), lambda b: (0, 0)),
        ],
        out_specs=[row, row] + [one] * n_scalar_outs,
        out_shape=([jax.ShapeDtypeStruct((B, 1, N), jnp.int32)] * 2
                   + [jax.ShapeDtypeStruct((B, 1, 1), jnp.int32)]
                   * n_scalar_outs),
        scratch_shapes=[pltpu.VMEM((N_in, N), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=name,
    )(ids, count.reshape(B, 1, T), w, thresholds.reshape(1, N))
    return ([outs[0][:, 0], outs[1][:, 0]]
            + [o[:, 0, 0] for o in outs[2:]])


def fused_event_lif_kernel(ids: jnp.ndarray, count: jnp.ndarray,
                           w: jnp.ndarray, thresholds: jnp.ndarray,
                           leak_shift: int, *, interpret: bool = True
                           ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """ids (B, T, E_max) int32 (PAD=-1), count (B, T) int32,
    w (N_in, N_pad) int8, thresholds (N_pad,) int32
    -> (first_spike (B, N_pad), v_final (B, N_pad)) int32."""
    kernel = functools.partial(_fused_kernel, T=ids.shape[1],
                               leak_shift=leak_shift)
    first, v = _call(kernel, "fused_event_lif", ids, count, w, thresholds,
                     0, interpret)
    return first, v


def fused_event_lif_decode_kernel(ids: jnp.ndarray, count: jnp.ndarray,
                                  w: jnp.ndarray, thresholds: jnp.ndarray,
                                  leak_shift: int, *, n_out: int,
                                  n_groups: int, per_group: int,
                                  fallback: str = "membrane",
                                  interpret: bool = True
                                  ) -> tuple[jnp.ndarray, jnp.ndarray,
                                             jnp.ndarray]:
    """Megakernel with the grouped TTFS decode fused after the T-loop.
    Emits (first_spike (B, N_pad), v_final (B, N_pad), labels (B,))."""
    assert n_out <= w.shape[1] and n_out == n_groups * per_group
    kernel = functools.partial(
        _fused_decode_kernel, T=ids.shape[1], leak_shift=leak_shift,
        n_groups=n_groups, per_group=per_group, fallback=fallback)
    first, v, labels = _call(kernel, "fused_event_lif_decode", ids, count, w,
                             thresholds, 1, interpret)
    return first, v, labels


def fused_event_lif_early_exit_kernel(ids: jnp.ndarray, count: jnp.ndarray,
                                      w: jnp.ndarray, thresholds: jnp.ndarray,
                                      leak_shift: int, *,
                                      interpret: bool = True
                                      ) -> tuple[jnp.ndarray, jnp.ndarray,
                                                 jnp.ndarray]:
    """ids (B, T, E_max), count (B, T) -> (first (B, N_pad), v_final
    (B, N_pad), steps (B,)). ``v_final`` is the membrane AT EXIT TIME, same
    contract as ``lif_scan_early_exit``."""
    kernel = functools.partial(_fused_early_exit_kernel, T=ids.shape[1],
                               leak_shift=leak_shift)
    first, v, steps = _call(kernel, "fused_event_lif_early_exit", ids, count,
                            w, thresholds, 1, interpret)
    return first, v, steps
