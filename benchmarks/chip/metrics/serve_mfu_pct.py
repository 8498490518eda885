"""The whole serving step's share of the chip's peak, in %: the operations
required by the images correctly served inside the window, per second of
window, over the chip's int8 peak."""

import numpy as np

from benchmarks.chip import work


def read(run):
    rows = run.in_window() & run.correct_rows
    if not rows.any():
        return None
    ops = float(np.sum(work.ops_per_image(run.events[run.records.image[rows]],
                                          run.widths, run.cell.cfg["T"])))
    return 100.0 * ops / run.seconds / run.peak["int8_ops_per_s"]
