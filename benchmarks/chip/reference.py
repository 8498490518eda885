"""Plain reference of the deployed TTFS classifier, independent of the
program: it imports nothing from ``repro`` and takes only the integers the
benchmark itself made (``model.Deployment``).

Semantics, all integer after the encode:
  * encode: pixel x in [0, 1] spikes at t = floor((1 - x) * (T - 1)) when
    x >= x_min, never (t = T) otherwise;
  * layer: at step t each neuron adds the int8 weights of the inputs that
    spike at t to an int32 membrane, after the leak v -= v >> leak_shift;
    a neuron's first spike is the first t with v >= its threshold;
  * decode: the label is the group holding the earliest first spike (lowest
    group on ties); with no spike, the group holding the largest final
    membrane;
  * latency mode stops at the first spike of any neuron: the step count is
    that step + 1, or T when nothing fires. A request whose events overflow
    the deployment's e_max in some step is served by the dense path, which
    always runs T steps.

The currents are an int8 x int8 -> int32 matrix product per step, run in
blocks of images on the default device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 512


def encode(images: np.ndarray, T: int, x_min: float) -> np.ndarray:
    """(B, n_in) float32 images -> (B, n_in) int32 spike times (T: none)."""
    x = np.clip(np.asarray(images, np.float32), np.float32(0), np.float32(1))
    t = np.floor((np.float32(1) - x) * np.float32(T - 1)).astype(np.int32)
    return np.where(x >= np.float32(x_min), t, np.int32(T))


def step_counts(times: np.ndarray, T: int) -> np.ndarray:
    """(B, n_in) spike times -> (B, T) events in each step."""
    return np.stack([(times == t).sum(axis=1) for t in range(T)], axis=1)


@functools.partial(jax.jit, static_argnames=("T", "leak_shift"))
def _membrane(times, w, T: int, leak_shift: int):
    """(b, n_in) times, (n_in, n) int8 weights -> (T, b, n) int32 membrane
    after each step."""
    steps = jnp.arange(T, dtype=jnp.int32)
    raster = (times[:, None, :] == steps[None, :, None]).astype(jnp.int8)
    cur = jax.lax.dot_general(raster, w, (((2,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)

    def step(v, i_t):
        v = v - jnp.right_shift(v, leak_shift) + i_t
        return v, v

    _, vs = jax.lax.scan(step, jnp.zeros(cur[:, 0].shape, jnp.int32),
                         jnp.moveaxis(cur, 1, 0))
    return vs


@functools.partial(jax.jit, static_argnames=("T",))
def _first_and_peak(vs, thresholds, T: int):
    """(T, b, n) membrane, (c, n) thresholds -> first spike (c, b, n) for
    each threshold row, peak membrane (b, n), final membrane (b, n)."""
    above = vs[None] >= thresholds[:, None, None, :]
    first = jnp.where(above.any(axis=1), jnp.argmax(above, axis=1), T)
    return first.astype(jnp.int32), vs.max(axis=0), vs[-1]


def layer(times: np.ndarray, w: np.ndarray, thresholds: np.ndarray,
          T: int, leak_shift: int):
    """Run the layer over ``times`` in blocks.

    ``thresholds`` is (n,) or (c, n); returns first spikes (B, n) or
    (c, B, n), peak membranes (B, n) and final membranes (B, n)."""
    thr = np.atleast_2d(np.asarray(thresholds, np.int32))
    w = jnp.asarray(w, jnp.int8)
    firsts, peaks, finals = [], [], []
    for i in range(0, len(times), BLOCK):
        vs = _membrane(jnp.asarray(times[i:i + BLOCK]), w, T, leak_shift)
        f, p, v = _first_and_peak(vs, jnp.asarray(thr), T)
        firsts.append(np.asarray(f))
        peaks.append(np.asarray(p))
        finals.append(np.asarray(v))
    first = np.concatenate(firsts, axis=1)
    if np.ndim(thresholds) == 1:
        first = first[0]
    return first, np.concatenate(peaks), np.concatenate(finals)


def decode(first: np.ndarray, v_final: np.ndarray, n_groups: int,
           per_group: int, T: int, fallback: str) -> np.ndarray:
    """Grouped first-spike readout over (..., n) arrays -> (...,) labels."""
    shape = first.shape[:-1] + (n_groups, per_group)
    gmin = first.reshape(shape).min(axis=-1)
    label = gmin.argmin(axis=-1)
    if fallback == "membrane":
        backup = v_final.reshape(shape).max(axis=-1).argmax(axis=-1)
    elif fallback == "zero":
        backup = np.zeros_like(label)
    else:
        raise ValueError(f"unknown fallback {fallback!r}")
    return np.where(gmin.min(axis=-1) < T, label, backup).astype(np.int64)


def answers(dep, images: np.ndarray, latency_mode: bool,
            weights: np.ndarray | None = None
            ) -> tuple[np.ndarray, np.ndarray]:
    """The deployment's (label, step count) for every image.

    ``weights`` replaces the deployment's int8 weights (the control)."""
    c = dep.cfg
    T = c["T"]
    times = encode(images, T, c["x_min"])
    w = dep.w_int8 if weights is None else weights
    first, _, v_final = layer(times, w, dep.thresholds, T, c["leak_shift"])
    labels = decode(first, v_final, c["n_groups"], c["per_group"], T,
                    c["fallback"])
    steps = np.full(len(images), T, np.int64)
    if latency_mode:
        t_star = first.min(axis=1)
        steps = np.where(t_star < T, t_star + 1, T)
        overflow = (step_counts(times, T) > c["e_max"]).any(axis=1)
        steps[overflow] = T
    return labels, steps


def int4_weights(w_int8: np.ndarray) -> np.ndarray:
    """The control: the int8 weights held at 4 bits (16 levels of 16), in
    the same units so that the thresholds still apply."""
    w4 = np.clip(np.round(w_int8.astype(np.float32) / 16), -8, 7)
    return (w4 * 16).astype(np.int8)
