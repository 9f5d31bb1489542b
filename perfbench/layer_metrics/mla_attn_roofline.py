"""The absorbed latent-attention kernel's share of its roofline in the
traced plain decode steps (``perfbench/latent.py``). Time: the device
trace's ``_mla_paged_pallas*`` events in those steps. Least time: the
larger of the pool bytes their decodes had to read (every layer x the
steps' live pages, a row once at its published width: the family file's
``latent_attn_bytes``) over the peak bandwidth, and the absorbed form's
FLOPs over those pages' slots (``latent_attn_flops``) over the peak rate.
At one query token a sequence the bytes are the larger by four."""


def read(run):
    from perfbench import latent, roofline

    count_bytes = getattr(run.family, "latent_attn_bytes", None)
    count_flops = getattr(run.family, "latent_attn_flops", None)
    if run.peaks is None or count_bytes is None or count_flops is None:
        return None
    got = latent.traced(run)
    if got is None or got[0] <= 0:
        return None
    seconds, _, pages = got
    page_size = run.mix["engine_options"]["page_size"]
    return roofline.roofline_share_pct(
        count_flops(run.cfg, pages * page_size),
        count_bytes(run.cfg, page_size, pages), seconds, run.peaks)
