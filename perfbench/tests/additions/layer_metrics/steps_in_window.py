"""Whole steps inside the window (a reader added as a file)."""


def read(run):
    return len(run.step_ends) - 1
