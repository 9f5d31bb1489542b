"""Median of the program's ``infer.decode.draft`` phase over the window's
decode steps: the host's time to send out the prediction module's program
behind the verification (its inputs are on the device already; the enqueue
and what the launch waits for). The module's device time is inside the
step's ``infer.decode.wait``, with the model's. Nothing is read where no
step holds the phase."""


def read(run):
    from perfbench import steplog

    return steplog.phase_ms_p50(run, "infer.decode.draft")
