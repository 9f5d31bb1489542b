"""Share of the traced window, averaged over the chips, in which a
collective was in flight and no other instruction ran on that chip
(``trace_reduce.collective_exposed_seconds``)."""


def read(run):
    from perfbench import trace_reduce

    if run.trace is None or not run.trace.device:
        return None
    lo, hi = trace_reduce.window_of(run.trace)
    return 100.0 * trace_reduce.collective_exposed_seconds(run.trace) \
        / (hi - lo)
