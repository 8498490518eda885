"""Seconds from process start to the first timed request: imports, the
deployment built from the seed, the scheduler's lanes and their compiled
programs, and the warm-up."""


def read(run):
    return run.setup_s
