"""Mean wall time of the program's ``batch.form`` span, from the oldest
request popped to the batch closed at max_batch or max_wait_us, in ms."""

from benchmarks.chip.metrics._spans import named, wall_ns


def read(run):
    found = named(run, ("batch.form",))
    if found is None:
        return None
    _, spans = found
    return sum(wall_ns(s) for s in spans) / len(spans) / 1e6
