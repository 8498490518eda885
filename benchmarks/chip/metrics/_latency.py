"""Latency of every request due in the window, from when it was due to its
completion, in ms; a request that never completed counts as infinitely
late."""

import numpy as np


def percentile(run, q: float) -> float | None:
    r = run.records
    due = run.due_in_window()
    if not due.any():
        return None
    lat = np.where(r.error[due], np.inf, r.done[due] - r.due[due])
    return float(1e3 * np.percentile(lat, q, method="higher"))
