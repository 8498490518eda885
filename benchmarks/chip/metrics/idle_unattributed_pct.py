"""Share of the device's idle time in the window that no lane span covers
(the lane's wait, the batch's formation and its phases), in %: what the
program's spans cannot explain. The spans are moved onto the trace's clock
by the run's anchor."""

from benchmarks.chip import devtrace
from benchmarks.chip.metrics._spans import LANE


def read(run):
    if run.trace is None or run.spans is None or run.spans_dropped:
        return None
    lane = [(s.wall_ns_start + run.offset_ns, s.wall_ns_end + run.offset_ns)
            for s in run.spans if s.name in LANE]
    if not lane:
        return None
    lo, hi = run.window_ns()
    idle = devtrace.gaps(devtrace.merge((s, e) for _, s, e in run.trace.ops),
                         lo, hi)
    idle_ns = devtrace.total(idle)
    if idle_ns <= 0:
        return None
    covered = devtrace.overlap(idle, devtrace.merge(lane))
    return 100.0 * (idle_ns - covered) / idle_ns
