"""Share of the traced window in which chip 0 ran nothing while the host
was inside ``infer.decode.sample``: sampling and emitting on the host
before the next step can be launched."""


def read(run):
    from perfbench import steplog

    return steplog.idle_pct(run, "sample")
