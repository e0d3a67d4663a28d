"""Share of the rows the device computed that were padding (%): over
the traced flushes, the bucket rows beyond each flush's requests, over
all bucket rows.  Device work spent on pad rows when cohorts fall
between buckets.  Moves latency_p95_ms."""


def read(ctx):
    slots = sum(f["bucket"] for f in ctx.flushes)
    if not slots:
        return None
    return 100.0 * (slots - sum(f["batch"] for f in ctx.flushes)) / slots
