"""The fused event kernel's share of its roofline, in %: the least time the
chip needs for the work of every request served while traced (operations
and bytes from ``work``), over the summed device time of the kernel's
events. Nothing to read without kernel events."""

import numpy as np

from benchmarks.chip import devtrace, work


def read(run):
    if run.trace is None:
        return None
    evs = devtrace.kernel_events(run.trace, "fused_event_lif")
    kernel_s = sum(e - s for _, s, e in evs) / 1e9
    if not evs or kernel_s <= 0:
        return None
    c = run.cell.cfg
    served = ~run.records.error
    n_events = run.events[run.records.image[served]]
    ops = float(np.sum(work.ops_per_image(n_events, c["n_out"], c["T"])))
    nbytes = (len(evs) * work.bytes_per_call(c["n_in"], c["n_out"], 0, 0)
              + work.bytes_per_call(0, 0, int(n_events.sum()),
                                    int(served.sum())))
    share, _ = work.roofline(kernel_s, ops, nbytes, run.peak)
    return share
