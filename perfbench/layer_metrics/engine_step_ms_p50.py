"""Median of the benchmark's span around ``InferenceEngine.step`` over
the window's steps that decoded."""

import statistics


def read(run):
    steps = [r.end - r.start for r in run.engine_steps if r.decodes]
    return 1e3 * statistics.median(steps) if steps else None
