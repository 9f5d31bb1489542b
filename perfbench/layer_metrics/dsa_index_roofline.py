"""The indexer's score kernel's share of its roofline in the traced plain
decode steps (``perfbench/dsa.py``). Time: the device trace's
``_dsa_index_pallas*`` events in those steps. Least time: the larger of
the index keys their decodes had to read (every layer x the cached
positions the steps' queries scored, one key each at the itemsize held:
the family file's ``dsa_index_bytes``) over the peak bandwidth, and the
index heads' products (``dsa_index_flops``) over the peak rate."""


def read(run):
    from perfbench import dsa, roofline

    count_bytes = getattr(run.family, "dsa_index_bytes", None)
    count_flops = getattr(run.family, "dsa_index_flops", None)
    if run.peaks is None or count_bytes is None or count_flops is None:
        return None
    got = dsa.traced(run)
    if got is None:
        return None
    return roofline.roofline_share_pct(
        count_flops(run.cfg, got["scored"]),
        count_bytes(run.cfg, got["scored"]), got["index_s"], run.peaks)
