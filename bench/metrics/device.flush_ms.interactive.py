"""Device busy time per flush: the union of device operation intervals
in the traced window over the flushes in it (ms)."""


def read(ctx):
    if not ctx.flushes:
        return None
    return ctx.trace.busy_s / len(ctx.flushes) * 1e3
