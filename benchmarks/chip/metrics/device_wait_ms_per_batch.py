"""Wall time per batch of the program's ``lane.device_wait`` span, in ms:
the lane's wait for the event program's labels (block_until_ready)."""

from benchmarks.chip.metrics._spans import ms_per_batch


def read(run):
    return ms_per_batch(run, "lane.device_wait")
