"""Share of the window in which the cycle collector held the serving
process's interpreter: the seconds of the program's ``host.gc`` entries
(``raytpu.util.tracing.host_pauses``: every full collection and any that
took over a millisecond, on the step log's clock) that lie inside
``run.window``, over the window. It needs no trace. Times a cell's
``tpot_mean_ms`` it is what a collector that never ran in the window
(``gc.freeze()`` once a deployment is ready) would give back. ``None``
for a program that keeps no pauses (the parent of the PR that brought
them)."""


def read(run):
    from perfbench import flightlog

    return flightlog.gc_pause_pct(run)
