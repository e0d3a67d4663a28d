"""Arithmetic shared by the per-layer metric readers in
``bench/metrics/``.  Each reader takes a ``run.LayerContext`` and
returns a number, or None where the run holds nothing to read."""
from __future__ import annotations

import peaks


def span_ms(ctx, name: str) -> list[float]:
    return [e["dur"] * 1e-3 for e in ctx.spans if e["name"] == name]


def mean(xs: list[float]) -> float | None:
    return sum(xs) / len(xs) if xs else None


HOST_PHASES = ("serve.bucket_pad", "serve.pack", "serve.dispatch",
               "serve.complete")


def host_flush_ms(ctx) -> float | None:
    """Mean over flushes of the host phases of a flush, each its own
    span inside ``serve.flush``: queue pop, pack, dispatch, complete.
    Not ``serve.compute`` (the wait for the device), and not the
    tracer's own per-request queue-wait records between them."""
    flushes = [e for e in ctx.spans if e["name"] == "serve.flush"]
    phases = [e for e in ctx.spans if e["name"] in HOST_PHASES]
    out = []
    for f in flushes:
        a, b = f["ts"], f["ts"] + f["dur"]
        out.append(sum(p["dur"] for p in phases
                       if a <= p["ts"] and p["ts"] + p["dur"] <= b) * 1e-3)
    return mean(out)


def roofline(ctx, family: str) -> float | None:
    """Percent: the least time of the family's layers over all flushes
    of the trace, at their bucket rows, over the device time of the
    family's layers: its kernels and the XLA operations around them
    (``trace_reduce`` says which)."""
    if family not in ctx.cfg["kernel_families"] or not ctx.flushes:
        return None
    layer_s = ctx.trace.family_s.get(family, 0.0)
    if layer_s <= 0:
        return None
    least = 0.0
    for f in ctx.flushes:
        w = ctx.reference.work(ctx.cfg, f["bucket"])
        if family not in w:
            return None
        least += peaks.least_time(*w[family], ctx.device_kind)
    return 100.0 * least / layer_s


def served_ops(ctx) -> float:
    """2 x the float network's MACs of every image served in the window."""
    return 2.0 * ctx.images * ctx.reference.macs_per_image(ctx.cfg)


def peak_ops(ctx) -> float:
    return peaks.peak(ctx.device_kind)["ops_per_s"]
