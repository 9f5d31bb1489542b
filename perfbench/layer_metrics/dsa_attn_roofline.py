"""The share of its roofline of the attention over the rows the indexer
chose, in the traced plain decode steps (``perfbench/dsa.py``). Time: the
device trace's ``_dsa_attend_pallas*`` events and the gather that hands
them the chosen rows (XLA's fusion whose result is ``[n x index_topk, the
latent row's lanes]``), which together are what the count's work takes
here. Least time: the larger of the chosen latent rows
(every layer x the rows the steps' queries read, each once at its
published width: the family file's ``dsa_attn_bytes``) over the peak
bandwidth, and the absorbed form's FLOPs over them (``dsa_attn_flops``)
over the peak rate: the same work whatever implements it."""


def read(run):
    from perfbench import dsa, roofline

    count_bytes = getattr(run.family, "dsa_attn_bytes", None)
    count_flops = getattr(run.family, "dsa_attn_flops", None)
    if run.peaks is None or count_bytes is None or count_flops is None:
        return None
    got = dsa.traced(run)
    if got is None:
        return None
    return roofline.roofline_share_pct(
        count_flops(run.cfg, got["selected"]),
        count_bytes(run.cfg, got["selected"]),
        got["attend_s"] + got["gather_s"], run.peaks)
