"""99th percentile of the program's ``admission`` span, submit to batch
formation, in ms. Nothing to read when spans were dropped."""

import numpy as np


def read(run):
    if run.spans is None or run.spans_dropped:
        return None
    waits = [s.wall_ns_end - s.wall_ns_start for s in run.spans
             if s.name == "admission"]
    if not waits:
        return None
    return float(np.percentile(waits, 99, method="higher") / 1e6)
