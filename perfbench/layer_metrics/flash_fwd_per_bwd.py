"""Forward flash-attention calls per backward in the traced steps: the
device trace's Pallas custom calls of the train step that
``flash_attn_roofline.classify`` tells apart as forward over those it
tells apart as dq, every chip's together. A step's backward runs dq once
a layer, so the ratio is how often a layer's forward kernel ran: 2 where
the backward of a block under remat runs the kernel again to get its
residuals back, 1 where the block keeps them. Nothing where the trace
holds no dq call (a cell that takes no gradient, a program without the
kernels)."""

import collections
import os


def read(run):
    from perfbench import byname, trace_reduce

    if run.trace is None:
        return None
    classify = byname.load_module(
        [os.path.dirname(byname.__file__)], "layer_metrics",
        "flash_attn_roofline").classify
    calls = collections.Counter(
        (classify(e.name) or (None,))[0]
        for events in trace_reduce.kernel_events(
            run.trace, trace_reduce.is_pallas).values()
        for e in events)
    if not calls["dq"]:
        return None
    return calls["fwd"] / calls["dq"]
