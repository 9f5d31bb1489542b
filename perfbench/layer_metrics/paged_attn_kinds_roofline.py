"""The paged-attention kernel's share of its roofline over a cache of two
kinds of pool, in the traced plain decode steps (``perfbench/
paged_kinds.py``). Time: the device trace's ``_paged_pallas*`` events in
those steps. Least time: the pool bytes their decodes had to read, counted
by kind (``full layers x live_pages_full + window layers x
live_pages_window`` pages of K and V, the page counts from the program's
step records, the bytes from the family file's
``paged_attn_bytes_by_kind``) over the peak bandwidth; decode at one query
token a sequence is bound by those bytes. ``paged_attn_roofline`` counts
every layer as reading the whole context and would read several hundred
per cent here."""


def read(run):
    from perfbench import paged_kinds, roofline

    count = getattr(run.family, "paged_attn_bytes_by_kind", None)
    if run.peaks is None or count is None:
        return None
    seconds = paged_kinds.traced_seconds(run)
    pages = paged_kinds.traced_pages(run)
    if seconds is None or pages is None or seconds[0] <= 0:
        return None
    bytes_ = count(run.cfg, run.mix["engine_options"]["page_size"],
                   pages["full"], pages["window"])
    return roofline.roofline_share_pct(0.0, bytes_, seconds[0], run.peaks)
