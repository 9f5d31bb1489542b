"""Median time of a training step on the host's clock, each step ended
by the fetched loss, over the window's steps."""

import statistics


def read(run):
    ends = run.step_ends
    if len(ends) < 2:
        return None
    return 1e3 * statistics.median(b - a for a, b in zip(ends, ends[1:]))
