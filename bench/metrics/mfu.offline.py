"""Whole-step share of the chip's peak over the traced window (%):
2 x MACs of the images served, over the window, over the int8 peak."""
import readers


def read(ctx):
    if not ctx.images:
        return None
    return 100.0 * readers.served_ops(ctx) / ctx.trace.window_s / \
        readers.peak_ops(ctx)
