"""Share of the traced window in which no operation ran on the device,
averaged over the chips: 1 - union of the device-op intervals over the
window (serving cells)."""

LAYER = "device"
UNIT = "%"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def read(run):
    return run.device_idle_pct()
