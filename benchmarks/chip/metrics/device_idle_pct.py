"""Share of the window in which no operation ran on the device, from the
profiler trace, in %."""

from benchmarks.chip import devtrace


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.window_ns()
    busy = devtrace.busy_share(run.trace, lo, hi)
    return 100.0 * (1.0 - busy * 1e9 / (hi - lo))
