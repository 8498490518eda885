"""99th percentile latency over all requests due in the window, from due
time to completion. A per-layer reading, not an end-to-end metric: host
stalls of ~140 ms inside the served path's encode and pack, several in a
40-s window, move it by several times between runs."""

from benchmarks.chip.metrics._latency import percentile


def read(run):
    return percentile(run, 99)
