"""The sparse-attention mechanism's share of the chip's busy time in the
traced plain decode steps: device time of everything it adds that the
trace can name over the time in which any operation ran on chip 0, both
inside those steps' ``pb.engine.step`` spans (``perfbench/dsa.py``). The
events: ``_dsa_index_pallas*`` (the indexer's scores), the gather of the
chosen rows out of the latent pool (XLA's fusion whose result is ``[n x
index_topk, the latent row's lanes]``) and ``_dsa_attend_pallas*`` (the
attention over them). **What has no name of its own and is left out:**
the exact choice of the ``index_topk`` between the two kernels (fusions
with tuple results, numbered like any other) and the small arithmetic
around them, so this under-reads the mechanism's cost by those, about
three tenths of the busy time where PR 55 read them by hand (``PERF.md``
section 6). Whether the mechanism does the share of a step's work the
cell was built for."""


def read(run):
    from perfbench import dsa

    got = dsa.traced(run)
    if got is None or got["busy_s"] <= 0:
        return None
    return 100.0 * (got["index_s"] + got["gather_s"] + got["attend_s"]) \
        / got["busy_s"]
