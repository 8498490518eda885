"""Wall time per batch of the program's ``batch.complete`` span, in ms:
taking the scheduler's lock and completing the batch's requests under it."""

from benchmarks.chip.metrics._spans import ms_per_batch


def read(run):
    return ms_per_batch(run, "batch.complete")
