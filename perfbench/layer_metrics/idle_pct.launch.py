"""Share of the traced window in which chip 0 ran nothing while the host was
on its way to a launch: ``infer.schedule``, a prefill phase or
``infer.decode.launch`` innermost (``steplog.idle_bucket``)."""


def read(run):
    from perfbench import steplog

    return steplog.idle_pct(run, "launch")
