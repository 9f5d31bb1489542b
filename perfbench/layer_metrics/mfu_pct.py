"""Model-FLOPs utilization: tokens per second per chip times the FLOPs
one token needs (6N + 12·L·E·T, ``roofline.train_flops_per_token`` with
the family file's N, L and E; recomputation not counted) over one chip's bf16 peak. The rate is a
step's tokens over the window's median step time, because this reader
runs in the traced run, where the one step that stops the profiler takes
seconds and the window's mean is not the untraced run's."""

import statistics


def read(run):
    ends = run.step_ends
    if len(ends) < 2 or run.peaks is None:
        return None
    step_s = statistics.median(b - a for a, b in zip(ends, ends[1:]))
    rate = run.tokens_per_step / step_s / run.chips
    per_token = run.family.train_flops_per_token(run.cfg,
                                                 run.mix["seq_len"])
    return 100.0 * rate * per_token / run.peaks.bf16_flops_per_s
