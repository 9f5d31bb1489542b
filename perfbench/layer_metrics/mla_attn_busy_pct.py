"""The latent-attention kernel's share of the chip's busy time in the
traced plain decode steps: device time of ``_mla_paged_pallas*`` over the
time in which any operation ran on chip 0, both inside those steps'
``pb.engine.step`` spans (``perfbench/latent.py``). Whether the mechanism
does the share of a step's work the cell was built for."""


def read(run):
    from perfbench import latent

    got = latent.traced(run)
    if got is None or got[0] <= 0 or got[1] <= 0:
        return None
    return 100.0 * got[0] / got[1]
