"""Readback of a flush (ms): from its result's being ready on the
device to its logits' being on the host.  Median over the profiled
half's flushes, from the server's phase stamps (``phases.runtime``)."""
import phases


def read(ctx):
    r = phases.runtime(ctx)
    return None if r is None else r.readback_ms
