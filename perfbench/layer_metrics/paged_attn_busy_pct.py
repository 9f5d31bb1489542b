"""The paged-attention kernel's share of the chip's busy time in the
traced plain decode steps: device time of ``_paged_pallas*`` over the
time in which any operation ran on chip 0, both inside those steps'
``pb.engine.step`` spans (``perfbench/paged_kinds.py``). Whether the
cache does the share of a step's work the cell was built for."""


def read(run):
    from perfbench import paged_kinds

    if paged_kinds.traced_pages(run) is None:
        return None  # not a cache of two kinds of pool
    seconds = paged_kinds.traced_seconds(run)
    if seconds is None or seconds[0] <= 0 or seconds[1] <= 0:
        return None
    return 100.0 * seconds[0] / seconds[1]
