"""The lane thread's CPU time over the wall time of its batch phases, in %:
the rest it waited, for the interpreter lock, for the host's CPU or for
the device. Reads the phase spans' ``cpu_ns``, summed over the window:
where the thread CPU clock steps in ticks, a single span reads whole
ticks, and only the sum over thousands of spans is meaningful."""

from benchmarks.chip.metrics._spans import PHASES, named, wall_ns


def read(run):
    found = named(run, PHASES)
    if found is None:
        return None
    _, spans = found
    if any(getattr(s, "cpu_ns", None) is None for s in spans):
        return None
    wall = sum(wall_ns(s) for s in spans)
    return 100.0 * sum(s.cpu_ns for s in spans) / wall if wall else None
