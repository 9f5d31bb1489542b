"""The device's own decode step: median, over the traced decodes behind
which a plain step dispatched the next, of the time from the decode
program's event on chip 0's ``XLA Modules`` line to the next decode's.
Paired with the step records by their ordinals (``flightlog.pair``), and
``None`` where the pairing does not hold or the records carry none."""


def read(run):
    from perfbench import flightlog

    decodes = flightlog.paired(run)
    return flightlog.device_step_ms_p50(decodes) if decodes else None
