"""What the readers of a cache of two kinds of pool share: the paged
kernel's device time in the traced plain decode steps, and the pages
those steps read in one layer of each kind.

A *plain* decode step decodes and prefills nothing: a step that also
holds a prompt's chunk runs the paged kernel for the chunk's rows too,
whose time is no decode's. Device time: the trace's events named
``_paged_pallas*`` (the kernel is a custom call named after its JAX
function) inside those steps' ``pb.engine.step`` spans, on chip 0. Steps
and spans are paired in order, as ``paged_attn_roofline`` pairs them.

Pages: a program that serves window layers puts ``live_pages_full`` and
``live_pages_window`` into each step's record (pages its decode read in
one full layer: every sequence's whole context; and in one window layer:
the windows' spans). A program without them (a model of one kind of
layer, or the parent of the PR that brought them) gives ``None`` to every
reader here.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple


def _plain_traced(run) -> Optional[List[Tuple]]:
    """``(span, probe's step)`` of the traced plain decode steps."""
    from perfbench import trace_reduce

    if run.trace is None or not run.trace.device:
        return None
    marks = trace_reduce.spans(run.trace, "pb.engine.step")
    pairs = [(m, r) for m, r in zip(marks, run.traced_steps)
             if r.decodes and not r.prefills]
    return pairs or None


def traced_seconds(run) -> Optional[Tuple[float, float]]:
    """``(the paged kernel's device seconds, chip 0's busy seconds)``
    inside the traced plain decode steps."""
    from perfbench import trace_reduce

    pairs = _plain_traced(run)
    if pairs is None:
        return None
    within = [(m.start, m.end) for m, _ in pairs]
    chip = min(run.trace.device)
    events = trace_reduce.kernel_events(
        run.trace, lambda e: trace_reduce.op_head(e.name).startswith(
            "_paged_pallas"), within=within)[chip]
    busy = sum(trace_reduce.measure(trace_reduce.clip(
        trace_reduce.busy_intervals(run.trace, chip), w)) for w in within)
    return sum(e.seconds for e in events), busy


def traced_pages(run) -> Optional[Dict[str, int]]:
    """``full`` and ``window``: pages read in one layer of the kind,
    summed over the traced plain decode steps."""
    pairs = _plain_traced(run)
    if pairs is None:
        return None
    fields = [getattr(r.program, "fields", None) or {} for _, r in pairs]
    if any("live_pages_window" not in f for f in fields):
        return None
    return {"full": sum(f["live_pages_full"] for f in fields),
            "window": sum(f["live_pages_window"] for f in fields)}
