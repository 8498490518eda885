"""Wall time per batch of the program's ``lane.readback`` span, reading the
labels and step counts back to the host, and of ``lane.reroute``, the dense
path for rows whose events overflow E_max, where it fires; in ms."""

from benchmarks.chip.metrics._spans import ms_per_batch


def read(run):
    return ms_per_batch(run, "lane.readback", "lane.reroute")
