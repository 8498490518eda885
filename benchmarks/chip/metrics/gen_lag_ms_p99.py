"""How late the open-loop generator submitted: the 99th percentile of
submit time minus due time, in ms. Nothing to read in a closed loop."""

import numpy as np


def read(run):
    if run.cell.traffic["load"]["kind"] != "poisson":
        return None
    r = run.records
    lag = (r.submit - r.due)[run.due_in_window() & ~np.isnan(r.submit)]
    if not len(lag):
        return None
    return float(1e3 * np.percentile(lag, 99, method="higher"))
