"""How far the host is ahead of the chip: median, over the traced
decodes a plain step dispatched, of the start of decode ``n``'s program
on chip 0 less the end of the ``infer.decode.launch`` phase of the record
whose ``dispatched`` is ``n``; 0 where the program began before its
launch phase ended. At 0 the chip waits for the host (a drafting
engine's drained step, whose programs start as they are enqueued); what
it reads above 0 is the margin a longer host path would eat before it
moved a token's time. ``None`` where the pairing by ordinals does not
hold or the records carry none."""


def read(run):
    from perfbench import flightlog

    decodes = flightlog.paired(run)
    return flightlog.host_lead_ms_p50(decodes) if decodes else None
