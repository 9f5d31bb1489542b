"""Share of the traced window in which chip 0 ran nothing while the host
was inside ``infer.decode.wait``: the chip idle while the host waits on
it, which is launch latency and the copy back, not host work."""


def read(run):
    from perfbench import steplog

    return steplog.idle_pct(run, "wait")
