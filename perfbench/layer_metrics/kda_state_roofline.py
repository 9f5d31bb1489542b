"""The delta-rule state kernel's share of its roofline in the traced plain
decode steps (``perfbench/kda.py``). Time: the device trace's
``_kda_state_pallas*`` events in those steps. Least time: the larger of
the bytes the recurrence of those steps' decode rows must move in every
KDA layer (each sequence's float32 matrices read once and written once
and the row's vectors: the family file's ``kda_state_bytes``; not the
convolutions' tails, which the convolution's own gather and scatter move
and the timed events never touch) over the peak bandwidth, and its FLOPs
(``kda_state_flops``) over the peak rate; both from the configuration's
shapes alone, the same work whatever implements it. The bytes are the
larger by two hundred."""


def read(run):
    from perfbench import kda, roofline

    count_bytes = getattr(run.family, "kda_state_bytes", None)
    count_flops = getattr(run.family, "kda_state_flops", None)
    if run.peaks is None or count_bytes is None or count_flops is None:
        return None
    got = kda.traced(run)
    if got is None:
        return None
    seconds, _, _, rows = got
    return roofline.roofline_share_pct(
        count_flops(run.cfg, rows), count_bytes(run.cfg, rows), seconds,
        run.peaks)
