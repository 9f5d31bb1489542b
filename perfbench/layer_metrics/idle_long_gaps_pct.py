"""Share of the traced window that chip 0 spent idle in gaps longer than
10 ms: what one decode in flight does not ride out (a plain step's host
path is 1-3 ms), so a pause of the host or a drained chip. From the
trace alone."""


def read(run):
    from perfbench import flightlog

    return flightlog.idle_long_gaps_pct(run)
