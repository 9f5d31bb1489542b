"""The routed-expert layer's share of the chip's busy time in the traced
steps: device time of the expert matrices' events (``perfbench/moe.py``)
over the time in which any operation ran on chip 0, both inside the
traced ``pb.engine.step`` spans. Whether the new mechanism does most of
the work."""


def read(run):
    from perfbench import moe

    seconds = moe.traced_seconds(run)
    if seconds is None or seconds[0] <= 0 or seconds[1] <= 0:
        return None
    return 100.0 * seconds[0] / seconds[1]
