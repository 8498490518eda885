"""Summed wall time of the lane's batch phases that each took over 50 ms,
in the window's batches, in s."""

from benchmarks.chip.metrics._spans import PHASES, STALL_NS, named, wall_ns


def read(run):
    found = named(run, PHASES)
    if found is None:
        return None
    _, spans = found
    return sum(wall_ns(s) for s in spans if wall_ns(s) > STALL_NS) / 1e9
