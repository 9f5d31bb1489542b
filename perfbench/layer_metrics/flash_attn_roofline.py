"""The flash-attention kernels' share of their roofline in the traced
steps: forward, dq and dk/dv together. Time: the device trace's Pallas
custom calls of the train step, told apart by their results (forward: the
output and a float32 log-sum-exp; dk/dv: two outputs; dq: one). Least
time: each call's FLOPs from its shapes (causal half) over the bf16 peak;
at T = 1024 and d = 64 every one of them is FLOP-bound. A call the remat
policy repeats is counted as often as it ran."""

import re


SHAPE = re.compile(r"(bf16|f32)\[(\d+),(\d+),(\d+)\]")


def classify(name):
    """(kind, batch*heads, T, d) of a flash custom call, else None."""
    result = name.split(" custom-call(", 1)[0].split(" = ", 1)[-1]
    outs = SHAPE.findall(result)
    if not outs:
        return None
    kinds = [o[0] for o in outs]
    bh, t, d = (int(x) for x in outs[0][1:])
    if kinds == ["bf16", "f32"]:
        return "fwd", bh, t, d
    if kinds == ["bf16", "bf16"]:
        return "dkv", bh, t, d
    if kinds == ["bf16"]:
        return "dq", bh, t, d
    return None


def read(run):
    from perfbench import roofline, trace_reduce

    if run.trace is None or run.peaks is None:
        return None
    events = trace_reduce.kernel_events(run.trace, trace_reduce.is_pallas)
    seconds = flops = 0.0
    for evs in events.values():
        for e in evs:
            found = classify(e.name)
            if found is None:
                continue
            kind, bh, t, d = found
            seconds += e.seconds
            flops += roofline.flash_flops(kind, bh, t, d, causal=True)
    if seconds <= 0:
        return None
    return roofline.roofline_share_pct(flops, 0.0, seconds, run.peaks)
