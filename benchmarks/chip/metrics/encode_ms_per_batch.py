"""Wall time per batch of the program's ``lane.encode`` span, in ms: TTFS
encode of the padded buffer, through its read back to the host."""

from benchmarks.chip.metrics._spans import ms_per_batch


def read(run):
    return ms_per_batch(run, "lane.encode")
