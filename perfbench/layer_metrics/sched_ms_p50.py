"""Median time in ``Scheduler.schedule()`` over the window's engine steps:
the program's ``infer.schedule`` phase, from ``InferenceEngine.step_log()``."""

LAYER = "scheduler"
UNIT = "ms"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def read(run):
    from perfbench import steplog

    return steplog.phase_ms_p50(run, "infer.schedule")
