"""Full (generation-2) collections of the serving process that ended
inside the window, counted from the program's ``host.gc`` entries: the
one kind of collection that holds the interpreter for a tenth of a second
or more here. It needs no trace. ``None`` for a program that keeps no
pauses."""


def read(run):
    from perfbench import flightlog

    pauses = flightlog.host_pauses(run)
    if pauses is None:
        return None
    return flightlog.gc_full_collections(pauses, tuple(run.window))
