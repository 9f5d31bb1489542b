"""The delta-rule linear-attention layers' share of the chip's busy time in
the traced plain decode steps: device time of the state kernel
(``_kda_state_pallas*``) and of every instruction that names a parameter of
a KDA operator, over the time in which any operation ran on chip 0, both
inside those steps' ``pb.engine.step`` spans (``perfbench/kda.py``, which
also says what of the layer has no name and is left out). Whether the
mechanism does the share of a step's work the cell was built for."""


def read(run):
    from perfbench import kda

    got = kda.traced(run)
    if got is None or got[2] <= 0:
        return None
    return 100.0 * got[1] / got[2]
