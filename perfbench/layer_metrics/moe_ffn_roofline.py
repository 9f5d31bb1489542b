"""The routed-expert layer's share of its roofline in the traced part of
the window. Time: the device trace's events of the expert matrices
(``perfbench/moe.py``: ``_moe_grouped_pallas*``, or ``ragged-dot-*``) inside the traced
``pb.engine.step`` spans. Least time: the larger of the weight bytes the
experts touched in those same steps hold (three matrices each, from the
step records' ``moe_experts_touched``) over the peak bandwidth, and the
FLOPs of the (token, expert) pairs computed (``moe_assignments``) over
the peak rate; the count functions are the family file's
(``expert_ffn_bytes``, ``expert_ffn_flops``). A decode step at 128 pairs
a layer is bound by the bytes."""


def read(run):
    from perfbench import moe, roofline

    if run.peaks is None or moe.shape(run) is None:
        return None
    seconds = moe.traced_seconds(run)
    counts = moe.traced_counts(run)
    if seconds is None or counts is None or seconds[0] <= 0:
        return None
    return roofline.roofline_share_pct(
        run.family.expert_ffn_flops(run.cfg, counts["assignments"]),
        run.family.expert_ffn_bytes(run.cfg, counts["experts_touched"]),
        seconds[0], run.peaks)
