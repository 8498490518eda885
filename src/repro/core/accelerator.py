"""Accelerator runtime — the TPU-native "board" path.

Consumes the SAME deployment artifact as the software reference (no
conversion stage) and executes the padded block layout the planner emitted:

  * ``mode="batch"``  — time-batched execution: the (T, N_in) spike raster is
    a 0/1 int8 matrix fed to the MXU as one matmul, then the fused LIF scan
    runs over the (T, N_pad) currents. This is the TPU-native re-thinking of
    the FPGA's event pipeline: instead of serializing events through a router
    (which a systolic machine cannot do efficiently), we batch a whole time
    window into one hardware-shaped matrix product. Throughput-oriented.

  * ``mode="event"`` — event-frame execution: packed (T, E_max) event-id
    buffers drive per-step gathers of weight rows (HBM->VMEM in the kernel),
    accumulated into the membrane block. Work scales with ACTIVE events, the
    paper's event-driven property, and an early-exit loop stops at the first
    output spike (the TTFS decision point) for latency mode. Given images,
    the TTFS encode and the packing (``events.pack_events_device``) run in
    the same jitted program as the kernel: one upload, one dispatch.

  * ``kernel="jnp" | "pallas" | "fused"`` — the jnp path mirrors the kernel's
    block structure op-for-op (and is fast on this CPU-only container); the
    pallas path calls the actual TPU kernels (interpret mode on CPU); the
    fused path runs the event→LIF→decode megakernel (event mode only): one
    pass, state resident on-chip, the (T, N_pad) currents tensor never
    materialized. All are bit-exact against the reference; tests assert they
    agree.

Execution parameters come from the lowered program (``core.lowering``); the
jitted callables live in the process-wide program cache keyed by
(program fingerprint, mode, kernel), so every serving lane over the same
artifact shares one compiled pipeline.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ttfs
from repro.core.artifact import Artifact
from repro.core.events import EventFrames, PAD, pack_events_device
from repro.core.lif_dynamics import lif_scan, lif_scan_early_exit
from repro.core.lowering import (LoweredProgram, get_cache, lower,
                                 program_nbytes)
from repro.core.types import SNNOutput, decode_output
from repro.telemetry import trace as ttrace


def _build_bundle(prog: LoweredProgram, mode: str, kernel: str) -> dict:
    """Jitted pipelines for one (program, mode, kernel) config. Module-level
    closures over program fields — never methods — so two runtime instances
    with the same config share the compiled executables."""
    T, x_min, leak_shift = prog.T, prog.x_min, prog.leak_shift
    n_out = prog.n_out
    w_padded, thr_padded = prog.w_padded, prog.thr_padded
    plan = prog.decode

    # ------------------------------------------------------------ batch mode
    def currents_batch(raster: jnp.ndarray) -> jnp.ndarray:
        """(B, T, N_in) int8 raster -> (T, B, N_pad) int32 currents."""
        if kernel == "pallas":
            from repro.kernels.spike_matmul import ops as smm
            cur = smm.spike_matmul(raster, w_padded)           # (B, T, N_pad)
        else:
            cur = jax.lax.dot_general(raster, w_padded,
                                      (((2,), (0,)), ((), ())),
                                      preferred_element_type=jnp.int32)
        return jnp.moveaxis(cur, 1, 0)

    def lif(currents: jnp.ndarray):
        """(T, ..., N_pad) -> LIFResult via fused kernel or its jnp mirror."""
        if kernel == "pallas":
            from repro.kernels.lif import ops as lif_ops
            return lif_ops.lif_fused(currents, thr_padded, leak_shift)
        return lif_scan(currents, thr_padded, leak_shift, T)

    def decode_padded(first, v_final):
        first_l, v_l = first[..., :n_out], v_final[..., :n_out]
        if kernel == "pallas":
            from repro.kernels.ttfs_decode import ops as dec_ops
            labels = dec_ops.ttfs_decode(
                first_l, v_l,
                n_groups=plan.n_groups, per_group=plan.per_group,
                sentinel=plan.sentinel, fallback=plan.fallback)
        else:
            labels = decode_output(first_l, v_l, plan)
        return labels, first_l, v_l

    def forward_batch(images: jnp.ndarray) -> SNNOutput:
        times = ttfs.encode_ttfs(images, T, x_min)
        raster = ttfs.frames_from_times(times, T)
        currents = currents_batch(raster)
        res = lif(currents)
        labels, first_l, v_l = decode_padded(res.first_spike, res.v_final)
        steps = jnp.full(labels.shape, T, jnp.int32)
        return SNNOutput(labels, first_l, v_l, steps)

    # ------------------------------------------------------------ event mode
    def event_currents(ids: jnp.ndarray) -> jnp.ndarray:
        """(B, T, E_max) event ids -> (B, T, N_pad) int32 currents via row
        gather."""
        if kernel == "pallas":
            from repro.kernels.event_accum import ops as ea
            return ea.event_accum(ids, w_padded)
        safe = jnp.maximum(ids, 0)
        rows = w_padded[safe].astype(jnp.int32)              # (B, T, E, N_pad)
        mask = (ids != PAD)[..., None]
        return jnp.sum(jnp.where(mask, rows, 0), axis=-2)

    def forward_event(ids: jnp.ndarray, count: jnp.ndarray) -> SNNOutput:
        """ids: (B, T, E_max), count: (B, T).
        Full-T evaluation (throughput/accuracy mode)."""
        if kernel == "fused":
            from repro.kernels.fused_event_lif import ops as fused
            res, labels = fused.fused_event_lif_decode(
                ids, count, w_padded, thr_padded, leak_shift,
                n_out=n_out, n_groups=plan.n_groups,
                per_group=plan.per_group, fallback=plan.fallback)
            first_l = res.first_spike[..., :n_out]
            v_l = res.v_final[..., :n_out]
            steps = jnp.full(labels.shape, T, jnp.int32)
            return SNNOutput(labels, first_l, v_l, steps)
        currents = event_currents(ids)                          # (B, T, N_pad)
        res = lif(jnp.moveaxis(currents, 1, 0))
        labels, first_l, v_l = decode_padded(res.first_spike, res.v_final)
        steps = jnp.full(labels.shape, T, jnp.int32)
        return SNNOutput(labels, first_l, v_l, steps)

    def forward_event_latency(ids: jnp.ndarray,
                              count: jnp.ndarray) -> SNNOutput:
        """(B, T, E_max) frames, stop each row at its first output spike."""
        if kernel == "fused":
            from repro.kernels.fused_event_lif import ops as fused
            res, steps = fused.fused_event_lif_early_exit(
                ids, count, w_padded, thr_padded, leak_shift)
        else:
            res, steps = jax.vmap(
                lambda cur: lif_scan_early_exit(cur, thr_padded, leak_shift,
                                                T))(event_currents(ids))
        labels, first_l, v_l = decode_padded(res.first_spike, res.v_final)
        return SNNOutput(labels, first_l, v_l, steps)

    def with_device_pack(forward):
        """(B, N_in) float32 images -> (SNNOutput, overflow, events_per_row):
        TTFS encode and event packing run in the same program as ``forward``,
        so a batch crosses to the device once each way."""
        def run(images: jnp.ndarray):
            times = ttfs.encode_ttfs(images, T, x_min)
            ids, count, overflow, events = pack_events_device(
                times, T, prog.e_max)
            return forward(ids, count), overflow, events
        return run

    if mode == "batch":
        return {"batch": jax.jit(forward_batch)}
    return {"event": jax.jit(forward_event),
            "event_latency": jax.jit(forward_event_latency),
            "event_images": jax.jit(with_device_pack(forward_event)),
            "event_images_latency": jax.jit(
                with_device_pack(forward_event_latency))}


class SNNAccelerator:
    def __init__(self, artifact: Artifact | LoweredProgram,
                 mode: str = "batch", kernel: str = "jnp"):
        if mode not in ("batch", "event"):
            raise ValueError(mode)
        if kernel not in ("jnp", "pallas", "fused"):
            raise ValueError(kernel)
        if kernel == "fused" and mode != "event":
            raise ValueError(
                "the fused megakernel consumes packed event frames; "
                "use mode='event' (batch mode has its own matmul pipeline)")
        prog = lower(artifact)
        self.program = prog
        self.art = prog.artifact
        self.mode, self.kernel = mode, kernel
        self.T = prog.T
        self.x_min = prog.x_min
        self.leak_shift = prog.leak_shift
        self.e_max = prog.e_max
        self.n_out = prog.n_out
        self.w_padded = prog.w_padded          # (N_in, N_pad) int8
        self.thr_padded = prog.thr_padded      # (N_pad,) int32
        bundle, self.cache_hit = get_cache().bundle(
            ("accelerator", prog.fingerprint, mode, kernel),
            lambda: _build_bundle(prog, mode, kernel),
            nbytes=program_nbytes(prog))
        if mode == "batch":
            self._fwd_batch = bundle["batch"]
        else:
            self._fwd_event = bundle["event"]
            self._fwd_event_latency = bundle["event_latency"]
            self._fwd_images = bundle["event_images"]
            self._fwd_images_latency = bundle["event_images_latency"]

    # -------------------------------------------------------------- frontend
    def _span(self, rec, batch, latency_mode: bool):
        """The ``accel.forward`` span of one call over the array ``batch``
        (a no-op, reading nothing, when disabled)."""
        attrs = meta = None
        if rec.enabled:
            shape = np.shape(batch)
            attrs = {"mode": self.mode,
                     "batch": int(shape[0]) if len(shape) > 1 else 1,
                     "T": self.T, "latency": bool(latency_mode)}
            meta = {"kernel": self.kernel}
        return rec.span("accel.forward", "system", attrs=attrs, meta=meta)

    def forward_images(self, images, latency_mode: bool = False):
        """Event mode, one device call: TTFS encode, event packing and the
        kernel on a (B, N_in) float32 batch. Returns (SNNOutput, overflow
        (B,) bool, events_per_row (B,) int32), still on the device — the
        caller decides what to read back. A row flagged in ``overflow`` ran
        on frames truncated to E_max."""
        rec = ttrace.get()
        with self._span(rec, images, latency_mode):
            # the jitted call: transfer and enqueue, not the device work
            with rec.span("accel.dispatch", "accel"):
                run = (self._fwd_images_latency if latency_mode
                       else self._fwd_images)
                return run(jnp.asarray(images, jnp.float32))

    def forward(self, images=None, frames: EventFrames | None = None,
                latency_mode: bool = False,
                check_overflow: bool = True) -> SNNOutput:
        """Images take the device-packed event program (event mode) or the
        dense one (batch mode); pre-packed ``frames`` run the event kernel
        alone. ``check_overflow`` reads the overflow flags back after the
        call and raises if a row exceeded E_max; ``False`` skips that
        device round trip for callers that read the flags themselves."""
        # telemetry spans (accel.forward -> dispatch) are no-ops on the
        # shared NullRecorder — nothing below allocates when disabled
        rec = ttrace.get()
        if self.mode == "batch":
            assert images is not None, "batch mode consumes dense images"
            with self._span(rec, images, latency_mode), \
                    rec.span("accel.dispatch", "accel"):
                return self._fwd_batch(jnp.asarray(images, jnp.float32))
        if frames is None:
            out, overflow, _ = self.forward_images(images, latency_mode)
        else:
            with self._span(rec, frames.ids, latency_mode), \
                    rec.span("accel.dispatch", "accel"):
                run = (self._fwd_event_latency if latency_mode
                       else self._fwd_event)
                out = run(frames.ids, frames.count)
            overflow = frames.overflow
        if check_overflow and bool(np.any(np.asarray(overflow))):
            raise OverflowError(
                "event frames exceed artifact E_max; re-export with "
                "larger headroom or use the dense batch path")
        return out

    __call__ = forward
