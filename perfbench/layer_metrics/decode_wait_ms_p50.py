"""Median of the program's ``infer.decode.wait`` phase over the window's
decode steps: the host blocked in ``np.asarray(logits)``, on the device's
step and on the copy of the logits back."""


def read(run):
    from perfbench import steplog

    return steplog.phase_ms_p50(run, "infer.decode.wait")
