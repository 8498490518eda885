"""Wall time per batch of the program's ``lane.encode`` span, in ms: the
``jax.device_put`` of the padded image buffer to the chip. The TTFS encode
itself runs on the device, inside the event program's call."""

from benchmarks.chip.metrics._spans import ms_per_batch


def read(run):
    return ms_per_batch(run, "lane.encode")
