"""The fused event kernel's share of its roofline, in %: the least time the
chip needs for the work of every request served while traced (operations
and bytes from ``work``), over the summed device time of the kernel's
events. The kernel runs once per layer, so each of its events reads one
layer's weight block, and the events of one call of every layer read each
block once. Nothing to read without kernel events."""

import numpy as np

from benchmarks.chip import devtrace, work


def read(run):
    if run.trace is None:
        return None
    evs = devtrace.kernel_events(run.trace, "fused_event_lif")
    kernel_s = sum(e - s for _, s, e in evs) / 1e9
    if not evs or kernel_s <= 0:
        return None
    served = ~run.records.error
    n_events = run.events[run.records.image[served]]
    ops = float(np.sum(work.ops_per_image(n_events, run.widths,
                                          run.cell.cfg["T"])))
    nbytes = (len(evs) * work.bytes_per_call(run.widths, 0, 0)
              // len(run.widths)
              + work.bytes_per_call([], int(n_events.sum()),
                                    int(served.sum())))
    share, _ = work.roofline(kernel_s, ops, nbytes, run.peak)
    return share
