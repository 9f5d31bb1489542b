"""Share of the traced window in which chip 0 ran nothing and no
``infer.step`` was open, or only the replica loop's ``serve.llm.*``
phases: publishing, the hand-over of the lock, the wait for it."""


def read(run):
    from perfbench import steplog

    return steplog.idle_pct(run, "between_steps")
