"""From the device's last instruction to the host's next: median of the
end of the ``infer.decode.wait`` phase of the record whose ``fetched`` is
``n`` less the end of the last program of decode ``n`` on chip 0 (its
sampler's; a drafting model's draft program's): the copy back of the ids
and the stepping thread's way to the interpreter, the part of
``decode_wait_ms_p50`` that is not the device's. ``None`` where the
pairing by ordinals does not hold or the records carry none."""


def read(run):
    from perfbench import flightlog

    decodes = flightlog.paired(run)
    return flightlog.fetch_lag_ms_p50(decodes) if decodes else None
