"""Median of the program's ``infer.decode.sample`` phase over the window's
decode steps: advancing each sequence and emitting the token ids the
device sampled (since PR 35; one ``sample_token`` a sequence on the host
before)."""


def read(run):
    from perfbench import steplog

    return steplog.phase_ms_p50(run, "infer.decode.sample")
