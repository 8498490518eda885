"""Wall time per batch of the program's ``accel.dispatch`` span, in ms: the
event program's jitted call, until it returns (the enqueue: the pack already
put the frames on the device)."""

from benchmarks.chip.metrics._spans import ms_per_batch


def read(run):
    return ms_per_batch(run, "accel.dispatch")
