"""The paged-attention kernel's share of its roofline in the traced
*plain* decode steps (steps that decode and prefill nothing:
``perfbench/paged_kinds.py``), for a model of one kind of pool in only
some of its layers. Time: the device trace's ``_paged_pallas*`` events in
those steps. Least time: the K and V rows of the pages those same steps'
decodes had to read (the probe's ``live_pages``, every sequence's whole
context) in each layer that has a pool (``kv_shape``: the attention layers
alone) over the peak bandwidth; decode at one query token a sequence is
bound by those bytes. ``paged_attn_roofline`` also counts the steps that
hold a prompt's chunk, whose kernel time is no decode's."""


def read(run):
    from perfbench import paged_kinds, roofline

    if run.peaks is None:
        return None
    seconds = paged_kinds.traced_seconds(run)
    if seconds is None or seconds[0] <= 0:
        return None
    layers, heads, head_dim, itemsize = run.family.kv_shape(run.cfg)
    pages = sum(r.live_pages for _, r in paged_kinds._plain_traced(run))
    bytes_ = layers * roofline.paged_attn_bytes(
        pages, run.mix["engine_options"]["page_size"], heads, head_dim,
        itemsize)
    return roofline.roofline_share_pct(0.0, bytes_, seconds[0], run.peaks)
