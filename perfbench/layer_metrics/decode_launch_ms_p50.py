"""Median of the program's ``infer.decode.launch`` phase over the window's
decode steps: the numpy inputs, the block table, the five host-to-device
puts and the call of the jitted decode returning (the enqueue)."""


def read(run):
    from perfbench import steplog

    return steplog.phase_ms_p50(run, "infer.decode.launch")
