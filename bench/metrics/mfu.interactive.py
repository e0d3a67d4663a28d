"""Whole-step share of the chip's peak while the device is busy (%):
2 x MACs of the images served, over the device's busy time, over the
int8 peak.  Busy time and not the window, because in an open loop
below capacity the idle time is set by the offered rate."""
import readers


def read(ctx):
    if not ctx.images or ctx.trace.busy_s <= 0:
        return None
    return 100.0 * readers.served_ops(ctx) / ctx.trace.busy_s / \
        readers.peak_ops(ctx)
