"""Median latency over all requests due in the window, from due time to
completion."""

from benchmarks.chip.metrics._latency import percentile


def read(run):
    return percentile(run, 50)
