"""Share of the traced window in which no operation ran on the device,
averaged over the chips: 1 - union of the device-op intervals over the
window (training cells)."""


def read(run):
    return run.device_idle_pct()
