"""What the routed-expert layer's four readers share: its device time in
the traced steps, and the routing counts the program's step log carries.

Device time: since PR 40 the expert matrices are multiplied by the
grouped-matmul Pallas kernel (``raytpu/ops/grouped_matmul.py``), whose
trace events are ``_moe_grouped_pallas*``, two a layer. Where an expert's
matrices do not fit the kernel's buffers the program falls back to
``jax.lax.ragged_dot``, which the TPU compiler turns into kernels of its
own called ``ragged-dot-*`` (seen on the chip, PR 26: three
``ragged-dot-none*`` a layer and one ``ragged-dot-metadata``). A kernel
of the routed layer is named after its function and has to start with
one of ``EXPERT_OPS``. The router, the sort of the assignments, the
gather and the weighted sum run under ``jax.named_scope("moe.router")``
and ``("moe.experts")``, but a scope is HLO metadata and ``Trace`` keeps
an event's name only: they are a few microseconds each and are not in
this time (``PERF.md``, open questions).

Counts: a routed model's engine puts ``moe_assignments``,
``moe_experts_touched`` and ``moe_expert_max`` into each record of
``step_log()``. A program without them (a dense family, or the parent of
the PR that brought them) gives ``None`` to every reader.
"""

from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Tuple

EXPERT_OPS = ("ragged-dot", "_moe_")


def is_expert_op(name: str) -> bool:
    from perfbench import trace_reduce

    return trace_reduce.op_head(name).startswith(EXPERT_OPS)


def shape(run) -> Optional[Tuple]:
    """The family's ``moe_shape`` of the run's configuration."""
    fn = getattr(run.family, "moe_shape", None)
    return fn(run.cfg) if fn else None


def _traced_spans(run) -> Optional[List]:
    from perfbench import trace_reduce

    if run.trace is None or not run.trace.device:
        return None
    marks = trace_reduce.spans(run.trace, "pb.engine.step")
    return marks[:len(run.traced_steps)]


def traced_seconds(run) -> Optional[Tuple[float, float]]:
    """``(expert layer's device seconds, chip 0's busy seconds)`` inside
    the traced ``pb.engine.step`` spans."""
    from perfbench import trace_reduce

    marks = _traced_spans(run)
    if not marks:
        return None
    within = [(m.start, m.end) for m in marks]
    chip = min(run.trace.device)
    events = trace_reduce.kernel_events(
        run.trace, lambda e: is_expert_op(e.name), within=within)[chip]
    busy = sum(trace_reduce.measure(trace_reduce.clip(
        trace_reduce.busy_intervals(run.trace, chip), w)) for w in within)
    return sum(e.seconds for e in events), busy


def traced_counts(run) -> Optional[Dict[str, int]]:
    """``assignments`` and ``experts_touched`` summed over the engine's
    records of the traced steps: a record belongs to the probe's step
    that encloses it."""
    from perfbench import steplog

    marks = _traced_spans(run)
    log = steplog.engine_log(run)
    if not marks or log is None:
        return None
    probes = run.traced_steps[:len(marks)]
    lo, hi = probes[0].start, probes[-1].end
    inside = [s for s in log["steps"] if lo <= s["start"] and s["end"] <= hi
              and "moe_assignments" in s]
    if not inside:
        return None
    return {"assignments": sum(s["moe_assignments"] for s in inside),
            "experts_touched": sum(s["moe_experts_touched"]
                                   for s in inside)}


def plain_decode_steps(run) -> Optional[List[Dict]]:
    """The window's records that decoded, prefilled nothing and carry the
    routing counts."""
    from perfbench import steplog

    steps = steplog.window_steps(run)
    if steps is None:
        return None
    return [s for s in steps if s.get("decodes") and not s.get("prefills")
            and s.get("moe_assignments")]


def median_over_decode_steps(run, value) -> Optional[float]:
    steps = plain_decode_steps(run)
    if not steps or shape(run) is None:
        return None
    return statistics.median(value(s, shape(run)) for s in steps)
