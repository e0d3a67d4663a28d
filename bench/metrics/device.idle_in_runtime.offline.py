"""Share of the traced window in which the device idled for the
runtime (%): the profiled flushes' runtime wait (launch + completion
notice) and readback, over the trace's window.  The rest of
``device.idle_share.offline`` is the host's work (``phases.runtime``)."""
import phases


def read(ctx):
    r = phases.runtime(ctx)
    return None if r is None else r.idle_pct
