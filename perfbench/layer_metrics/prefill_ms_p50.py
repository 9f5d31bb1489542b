"""Median, over the window's steps that held a prefill, of the step's total
time in ``infer.prefill`` and ``infer.prefill_chunk``: input build, the
jitted call, the logits on the host and the first token emitted, i.e. the
stall a turnover puts on the whole batch. ``xl-batch-decode`` has 4 or 5
such steps in a 40 s window (156-157 decode steps, one turnover every 32),
so this is the median of 4 or 5 readings."""


def read(run):
    from perfbench import steplog

    return steplog.phase_ms_p50(run, *steplog.PREFILL_PHASES)
