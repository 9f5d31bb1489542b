"""What the latent-attention kernel's two readers share: its device time
in the traced plain decode steps, and the pages those steps read.

A *plain* decode step decodes and prefills nothing (a step that holds a
prompt's chunk runs the kernel for the chunk's rows too, whose time is
no decode's). Device time: the trace's events named ``_mla_paged_pallas*``
(the kernel is a custom call named after its JAX function,
``raytpu/ops/mla_attention.py``) inside those steps' ``pb.engine.step``
spans, on chip 0; steps and spans are paired in order, as
``paged_kinds.py`` pairs them. Pages: the probe's ``live_pages`` of each
of those steps, every sequence's whole context in whole pages, which is
what one latent layer's call reads. A program without the kernel (any
other family, or the parent of the PR that brought it) has no such event
and gives ``None`` to both readers.
"""

from __future__ import annotations

from typing import Optional, Tuple

KERNEL = "_mla_paged_pallas"


def traced(run) -> Optional[Tuple[float, float, int]]:
    """``(the latent kernel's device seconds, chip 0's busy seconds, live
    pages read in one layer)`` over the traced plain decode steps."""
    from perfbench import paged_kinds, trace_reduce

    pairs = paged_kinds._plain_traced(run)  # (span, probe's step)
    if pairs is None:
        return None
    within = [(m.start, m.end) for m, _ in pairs]
    chip = min(run.trace.device)
    events = trace_reduce.kernel_events(
        run.trace, lambda e: trace_reduce.op_head(e.name).startswith(KERNEL),
        within=within)[chip]
    if not events:
        return None
    busy = sum(trace_reduce.measure(trace_reduce.clip(
        trace_reduce.busy_intervals(run.trace, chip), w)) for w in within)
    return (sum(e.seconds for e in events), busy,
            sum(r.live_pages for _, r in pairs))
