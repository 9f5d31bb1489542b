"""Share of the traced window in which chip 0 ran nothing while the
cycle collector held the serving process's interpreter: chip 0's idle
gaps under the program's ``host.gc`` entries, put on the trace's clock by
``steplog.clock_offset``. An overlay on the four ``idle_pct.*``, which go
on summing to the idle share: ``idle_long_gaps_pct`` less this is the
long idle that is still unnamed. ``None`` without a trace, for a program
that keeps no pauses, and where the clocks' pairs do not agree."""


def read(run):
    from perfbench import flightlog

    return flightlog.idle_in_gc_pct(run)
