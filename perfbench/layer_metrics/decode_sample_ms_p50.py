"""Median of the program's ``infer.decode.sample`` phase over the window's
decode steps: one ``sample_token`` per sequence on the host, and the
emitting of each token."""

LAYER = "engine step"
UNIT = "ms"
MOVES = "itl_p95_ms"
SOURCE = "program_span"


def read(run):
    from perfbench import steplog

    return steplog.phase_ms_p50(run, "infer.decode.sample")
