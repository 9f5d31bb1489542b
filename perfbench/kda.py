"""What the readers of the delta-rule linear-attention layers (``kda_*``)
share: their device time in the traced plain decode steps.

A *plain* decode step decodes and prefills nothing (``paged_kinds.
_plain_traced``; steps and ``pb.engine.step`` spans are paired in order).
Two sets of events, on chip 0, inside those steps' spans:

- the state kernel: the events named ``_kda_state_pallas*`` (a kernel is a
  custom call named after its JAX function, ``raytpu/ops/kda.py``): each
  sequence's float32 matrices read once and written once, a call a layer;
- every operation of the layer that the trace can tell from the rest of
  the step: the state kernel, and any instruction whose text names a
  parameter of a KDA operator (the program's parameter tree has them under
  ``.../kda/...``, which an HLO operand spells ``__kda__``: the seven
  projections' products, the gate's and the head norm's vectors, the
  convolution's taps). **What has no name of its own and is left out:**
  the fusions that read no parameter of the layer (the l2-norms, the
  sigmoids between two products, the gather and scatter of the
  convolutions' tails), numbered like any other; ``kda_busy_pct``
  under-reads the layer by them.

Rows: the decode rows of the same steps (the probe's ``decodes``), a
sequence a row. A program without the kernel (any other family, the parent
of the PR that brought it, a backend on which the layer runs in
``jax.numpy``) has no such event and gives ``None`` to every reader here.
"""

from __future__ import annotations

from typing import Optional, Tuple

KERNEL = "_kda_state_pallas"
PARAMETER = "__kda__"


def traced(run) -> Optional[Tuple[float, float, float, int]]:
    """``(the state kernel's device seconds, the layer's named operations'
    seconds, chip 0's busy seconds, decode rows)`` over the traced plain
    decode steps."""
    from perfbench import paged_kinds, trace_reduce

    pairs = paged_kinds._plain_traced(run)  # (span, probe's step)
    if pairs is None:
        return None
    within = [(m.start, m.end) for m, _ in pairs]
    chip = min(run.trace.device)

    def is_kernel(e) -> bool:
        return trace_reduce.op_head(e.name).startswith(KERNEL)

    def seconds(match) -> float:
        events = trace_reduce.kernel_events(run.trace, match,
                                            within=within)[chip]
        # A loop encloses its body's instructions: leaves alone.
        return sum(e.seconds for e in trace_reduce.leaves(events))

    kernel = seconds(is_kernel)
    if kernel <= 0:
        return None
    named = seconds(lambda e: is_kernel(e) or PARAMETER in e.name)
    busy = sum(trace_reduce.measure(trace_reduce.clip(
        trace_reduce.busy_intervals(run.trace, chip), w)) for w in within)
    return kernel, named, busy, sum(r.decodes for _, r in pairs)
