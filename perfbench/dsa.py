"""What the readers of the sparse-attention mechanism (``dsa_*``) share:
its device time in the traced plain decode steps, by part, and the rows
those steps scored and read.

A *plain* decode step decodes and prefills nothing (``paged_kinds.
_plain_traced``; steps and ``pb.engine.step`` spans are paired in order).
The mechanism is three parts (``raytpu/ops/dsa_attention.py``):

- the indexer's scores: the events named ``_dsa_index_pallas*`` (a
  kernel is a custom call named after its JAX function);
- the attention over the chosen rows: ``_dsa_attend_pallas*``;
- the gather of the chosen rows out of the latent pool, XLA's: a fusion
  numbered like any other, known by its result, ``[n x index_topk, the
  latent row's lanes as held]`` (``family.latent_row_held``), which
  nothing else in a step has;
- the exact choice of the ``index_topk`` (``select_rows``: the k-th
  largest from the bits, the running counts, the one-hot products) and
  the small arithmetic around the kernels (the scores' transpose, the
  chosen positions' pages and offsets, ``W_uk`` on the query and ``W_uv``
  on the result, the indexer's three projections): fusions with tuple
  results and numbers for names, which **no reader can tell from the
  rest of the step**. ``dsa_busy_pct`` leaves them out and under-reads
  the mechanism by them: in the traced plain steps of PR 55's runs the
  choice's fusions were about three tenths of the busy time
  (``perfbench/tests/aot_v5e_dsa.py --ops`` lists the compiled decode
  program's instructions under each ``attn.dsa.*`` scope; ``PERF.md``
  section 6, PR 55, has the times).

Rows: the step records' ``dsa_rows_scored`` and ``dsa_rows_selected``, one
layer's, summed over the same steps. A program without the kernels or the
fields (any other family, or the parent of the PR that brought them) gives
``None`` to every reader here.
"""

from __future__ import annotations

import re
from typing import Dict, Optional

INDEX_KERNEL = "_dsa_index_pallas"
ATTEND_KERNEL = "_dsa_attend_pallas"
RESULT = re.compile(r"^(fusion|gather) \w+\[(\d+),(\d+)\]$")


def traced(run) -> Optional[Dict[str, float]]:
    """``index_s``, ``attend_s``, ``gather_s`` (device seconds on chip 0),
    ``busy_s`` (chip 0's busy seconds), ``scored`` and ``selected`` (rows
    of one layer) over the traced plain decode steps."""
    from perfbench import paged_kinds, trace_reduce

    pairs = paged_kinds._plain_traced(run)  # (span, probe's step)
    if pairs is None:
        return None
    fields = [getattr(r.program, "fields", None) or {} for _, r in pairs]
    if any("dsa_rows_scored" not in f for f in fields):
        return None
    within = [(m.start, m.end) for m, _ in pairs]
    chip = min(run.trace.device)

    held = getattr(run.family, "latent_row_held", None)
    if held is None:
        return None
    topk, lanes = int(run.cfg["index_topk"]), held(run.cfg)

    def gathered(label: str) -> bool:
        m = RESULT.match(label)
        return bool(m) and int(m.group(3)) == lanes \
            and int(m.group(2)) % topk == 0

    def seconds(match) -> float:
        events = trace_reduce.kernel_events(run.trace, match,
                                            within=within)[chip]
        # A loop encloses its body's instructions: leaves alone.
        return sum(e.seconds for e in trace_reduce.leaves(events))

    def named(kernel: str):
        return lambda e: trace_reduce.op_head(e.name).startswith(kernel)

    out = {"index_s": seconds(named(INDEX_KERNEL)),
           "attend_s": seconds(named(ATTEND_KERNEL)),
           "gather_s": seconds(
               lambda e: gathered(trace_reduce.op_label(e.name)))}
    if out["index_s"] <= 0 or out["attend_s"] <= 0:
        return None
    out["busy_s"] = sum(trace_reduce.measure(trace_reduce.clip(
        trace_reduce.busy_intervals(run.trace, chip), w)) for w in within)
    out["scored"] = sum(f["dsa_rows_scored"] for f in fields)
    out["selected"] = sum(f["dsa_rows_selected"] for f in fields)
    return out
