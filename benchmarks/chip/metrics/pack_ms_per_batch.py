"""Wall time per batch of the program's ``lane.pack`` span, in ms: packing
the spike times into event frames, with the host read of the overflow
flags."""

from benchmarks.chip.metrics._spans import ms_per_batch


def read(run):
    return ms_per_batch(run, "lane.pack")
