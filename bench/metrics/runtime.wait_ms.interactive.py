"""Runtime wait of a flush (ms): from its forward call's return to its
result's being ready, less the device's mean time per flush; that is
the launch plus the completion notice.  Median over the profiled half's
flushes, from the server's phase stamps and the trace's device time
(``phases.runtime``)."""
import phases


def read(ctx):
    r = phases.runtime(ctx)
    return None if r is None else r.wait_ms
