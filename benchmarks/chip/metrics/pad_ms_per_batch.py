"""Wall time per batch of the program's ``lane.pad`` span, in ms: copying
the batch's real rows into the zeroed (max_batch, n_in) buffer."""

from benchmarks.chip.metrics._spans import ms_per_batch


def read(run):
    return ms_per_batch(run, "lane.pad")
