"""Median, over the window's engine steps, of the next ``infer.step``'s
start less this one's end: the replica's loop publishing the tokens
(``serve.llm.publish``), handing the lock to the consumers it woke and
waiting for it again (``serve.llm.lock_wait``). What the serve path adds
to a token's gap, timed from inside (``serve_overhead_ms`` times it from
outside)."""


def read(run):
    from perfbench import steplog

    return steplog.step_gap_ms_p50(run)
