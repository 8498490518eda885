"""The work the algorithm requires, counted the same whatever implements it,
and the chip's peaks (``peaks.json``, keyed by JAX's ``device_kind``).

Per served image, full T, summed over the layers ``widths`` lists, each
``(n_in, n_out)``:
  * operations: one int32 add per (event into the layer, output neuron),
    plus ``LIF_OPS`` per (step, output neuron): leak shift, subtract, add
    the step's current, compare with the threshold, latch the first spike;
  * bytes per call of every layer: each layer's int8 weight block once
    (n_in x n_out, real neurons only), 4 bytes per real event id, 4 bytes
    per label out.
Padded event slots, padded lanes and a dense layer's T x n_in x n_out
multiply-adds do not count.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

from benchmarks.chip import reference

LIF_OPS = 5
PEAKS = Path(__file__).resolve().parent / "peaks.json"


def load_peaks() -> dict:
    with open(PEAKS) as f:
        return json.load(f)["devices"]


def peak_for(kind: str, peaks: dict | None = None) -> dict:
    """The peaks of one chip; an unknown ``device_kind`` is an error."""
    peaks = load_peaks() if peaks is None else peaks
    if kind not in peaks:
        raise KeyError(f"device_kind {kind!r} is not in {PEAKS.name} "
                       f"(known: {sorted(peaks)})")
    return peaks[kind]


def events(times: np.ndarray, T: int, e_max: int) -> np.ndarray:
    """(B, n_in) spike times -> (B,) events the event path takes in: at
    most ``e_max`` per step."""
    return np.minimum(reference.step_counts(times, T), e_max).sum(axis=1)


def ops_per_image(n_events, widths, T: int):
    """(..., L) events into each layer -> (...) operations."""
    return sum(n_events[..., i] * n_out + LIF_OPS * T * n_out
               for i, (_, n_out) in enumerate(widths))


def bytes_per_call(widths, n_events: int, rows: int) -> int:
    return (sum(n_in * n_out for n_in, n_out in widths)
            + 4 * n_events + 4 * rows)


def roofline(kernel_s: float, ops: float, nbytes: float, peak: dict
             ) -> tuple[float, str]:
    """(share of the least time in %, which bound binds)."""
    t_ops = ops / peak["int8_ops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_ops >= t_bytes else "memory"
    return 100.0 * max(t_ops, t_bytes) / kernel_s, bound
