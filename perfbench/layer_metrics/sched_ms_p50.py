"""Median time in ``Scheduler.schedule()`` over the window's engine steps:
the program's ``infer.schedule`` phase, from ``InferenceEngine.step_log()``."""


def read(run):
    from perfbench import steplog

    return steplog.phase_ms_p50(run, "infer.schedule")
