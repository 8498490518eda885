"""Correctly served images completed inside the window, per second."""


def read(run):
    return float((run.in_window() & run.correct_rows).sum()) / run.seconds
