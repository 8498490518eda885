"""Packed event buffers — the TPU-native replacement for AER packets.

The FPGA routes (time, neuron) events through an event router into neuron
groups. TPUs have no dynamic dataflow, so we keep the *event-driven* property
(only active spikes cause work) in a shape-static form XLA/Pallas can compile:

    EventFrames:  ids   (T, E_max) int32   neuron ids spiking at step t,
                                            padded with PAD (= -1)
                  count (T,)       int32   number of valid events per step

E_max is part of the deployment artifact (the co-design analogue of the event
router's FIFO depth): the exporter calibrates it from data and rounds up to a
lane multiple, and the runtime asserts the input respects it. Overflow policy
is deterministic drop-with-flag (the hardware would backpressure; we surface
the flag so the caller can fall back to the dense path).

The served path packs on the device (``pack_events_device``, inside the
accelerator's jitted program); the numpy packers are its host reference.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

PAD = -1


@dataclasses.dataclass
class EventFrames:
    ids: jnp.ndarray     # (B, T, E_max) int32, PAD-padded
    count: jnp.ndarray   # (B, T) int32
    overflow: jnp.ndarray  # (B,) bool — any step dropped events

    @property
    def e_max(self) -> int:
        return self.ids.shape[-1]


def pack_events(times: np.ndarray, T: int, e_max: int) -> EventFrames:
    """times (B, N_in) int spike times (T = never) -> packed frames.

    Host-side packing (numpy): this is the "spike packing" stage the paper
    measures separately in the system-path breakdown (Fig 2)."""
    times = np.asarray(times)
    B, N = times.shape
    ids = np.full((B, T, e_max), PAD, dtype=np.int32)
    count = np.zeros((B, T), dtype=np.int32)
    overflow = np.zeros((B,), dtype=bool)
    for b in range(B):
        for t in range(T):
            (idx,) = np.nonzero(times[b] == t)
            k = len(idx)
            if k > e_max:
                overflow[b] = True
                idx = idx[:e_max]
                k = e_max
            ids[b, t, :k] = idx
            count[b, t] = k
    return EventFrames(jnp.asarray(ids), jnp.asarray(count), jnp.asarray(overflow))


def step_counts(times: np.ndarray, T: int) -> np.ndarray:
    """(B, N) int spike times -> (B, T+1) events per step (bin T absorbs the
    never-spikes sentinel). One flat bincount: O(B*N), no python loop over T."""
    B, N = times.shape
    clipped = np.minimum(times, T).astype(np.int64)
    flat = np.arange(B, dtype=np.int64)[:, None] * (T + 1) + clipped
    return np.bincount(flat.ravel(), minlength=B * (T + 1)).reshape(B, T + 1)


def pack_events_batched(times: np.ndarray, T: int, e_max: int) -> EventFrames:
    """Vectorized packing (no python loop over batch OR time) — the optimized
    host path: O(B*N log N) from the argsort, everything else O(B*N).

    Uses an argsort by (time, id): stable ordering makes packing deterministic."""
    times = np.asarray(times)
    B, N = times.shape
    order = np.argsort(times, axis=1, kind="stable")          # (B, N) ids sorted by time
    sorted_t = np.take_along_axis(times, order, axis=1)       # (B, N)
    # position of each event within its timestep: exclusive cumsum of per-step
    # counts gives step_start[:, t] = #events with time < t
    counts = step_counts(times, T)
    step_start = np.zeros((B, T + 1), dtype=np.int64)
    np.cumsum(counts[:, :T], axis=1, out=step_start[:, 1:])
    ids = np.full((B, T, e_max), PAD, dtype=np.int32)
    count = np.minimum(counts[:, :T], e_max).astype(np.int32)
    overflow = np.any(counts[:, :T] > e_max, axis=1)
    pos_in_step = np.arange(N)[None, :] - np.take_along_axis(
        step_start, np.minimum(sorted_t, T).astype(np.int64), axis=1)
    valid = (sorted_t < T) & (pos_in_step < e_max)
    b_idx, n_idx = np.nonzero(valid)
    t_idx = sorted_t[b_idx, n_idx]
    e_idx = pos_in_step[b_idx, n_idx]
    ids[b_idx, t_idx, e_idx] = order[b_idx, n_idx].astype(np.int32)
    return EventFrames(jnp.asarray(ids), jnp.asarray(count), jnp.asarray(overflow))


def pack_events_device(times: jnp.ndarray, T: int, e_max: int):
    """jnp packing, jit-able, for the device: (B, N) int32 spike times ->
    (ids (B, T, E_max), count (B, T), overflow (B,), events_per_row (B,)),
    element for element ``pack_events_batched``'s frames, plus each row's
    unclipped count of spikes before T.

    Dense int8 matmuls on the MXU, no sort, gather or scatter (a TPU gathers
    scalar indices one at a time): a pixel's slot in its step is the count
    of lower ids spiking in the same step, a prefix count taken as a matmul
    against a strictly upper triangular matrix; the frames are then the
    matmul of the (step, pixel) and (pixel, slot) one-hots with each pixel's
    id, one base-128 digit at a time so every operand fits int8."""
    B, N = times.shape
    pixel = jnp.arange(N, dtype=jnp.int32)
    spikes = (times[:, None, :] == jnp.arange(T, dtype=jnp.int32)[:, None]
              ).astype(jnp.int8)                             # (B, T, N)
    per_step = jnp.sum(spikes, axis=-1, dtype=jnp.int32)     # (B, T)
    count = jnp.minimum(per_step, e_max)
    overflow = jnp.any(per_step > e_max, axis=1)
    lower = (pixel[:, None] < pixel[None, :]).astype(jnp.int8)
    before = jnp.einsum("btm,mn->btn", spikes, lower,
                        preferred_element_type=jnp.int32)
    rank = jnp.sum(spikes * before, axis=1)                  # (B, N)
    slot = (rank[:, :, None] == jnp.arange(e_max, dtype=jnp.int32)
            ).astype(jnp.int8)                               # (B, N, E_max)
    ids = jnp.zeros((B, T, e_max), jnp.int32)
    place = 1
    while place < N:
        digit = ((pixel // place) % 128).astype(jnp.int8)
        ids += place * jnp.einsum("btn,bne->bte", spikes,
                                  slot * digit[:, None],
                                  preferred_element_type=jnp.int32)
        place *= 128
    ids = jnp.where(jnp.arange(e_max) < count[:, :, None], ids, PAD)
    events_per_row = jnp.sum(times < T, axis=1, dtype=jnp.int32)
    return ids, count, overflow, events_per_row


def calibrate_e_max(times: np.ndarray, T: int, lane: int = 128,
                    headroom: float = 1.0) -> int:
    """Pick E_max from calibration data: max simultaneous events per step,
    scaled by headroom, rounded up to a lane multiple. Stored in the artifact."""
    times = np.asarray(times)
    peak = int(step_counts(times, T)[:, :T].max()) if T > 0 else 0
    e = int(np.ceil(peak * headroom))
    return max(lane, ((e + lane - 1) // lane) * lane)


def unpack_to_raster(frames: EventFrames, n_in: int) -> jnp.ndarray:
    """Inverse of packing: frames -> (B, T, N_in) int8 raster (for testing)."""
    B, T, E = frames.ids.shape
    raster = jnp.zeros((B, T, n_in + 1), dtype=jnp.int8)  # +1 slot absorbs PAD
    ids = jnp.where(frames.ids == PAD, n_in, frames.ids)
    raster = raster.at[
        jnp.arange(B)[:, None, None], jnp.arange(T)[None, :, None], ids
    ].set(1)
    return raster[..., :n_in]
