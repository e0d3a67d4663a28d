"""Telemetry: the repo's one observability layer (numpy its only dependency).

Three pieces (see ``docs/observability.md`` for the full taxonomy):

* :mod:`repro.telemetry.metrics` — counters / gauges / histograms with
  snapshot, reset, and merge (``MetricsRegistry``).
* :mod:`repro.telemetry.trace` — nestable spans with Chrome
  ``trace_event`` export, near-zero cost when disabled (``Tracer``).
* :mod:`repro.telemetry.probes` — STATIC cost probes (kernel-launch
  counts, grids, collective bytes) gated against
  ``experiments/PROBES_baseline.json``.  Imported explicitly (it pulls
  in jax + the models); never imported from here.

:class:`Telemetry` bundles a registry + tracer; the serving layer owns
one per ``PackedInferenceServer`` (isolated, testable), while
module-level hot seams that have no object to hang telemetry on
(``kernels.ops.dispatch_batch``, the sharded-forward gathers) write to
the process-wide :func:`default` instance.
"""
from __future__ import annotations

from repro.telemetry.metrics import (LATENCY_BUCKETS_S, Counter, Gauge,
                                     Histogram, MetricsRegistry,
                                     log_spaced_buckets)
from repro.telemetry.trace import Tracer

__all__ = ["Counter", "Gauge", "Histogram", "LATENCY_BUCKETS_S",
           "MetricsRegistry", "Telemetry", "Tracer", "default",
           "log_spaced_buckets", "set_default"]


class Telemetry:
    """One metrics registry + one tracer, wired together.

    The registry is always live (a counter bump is a few dict/int ops);
    the tracer starts disabled and costs one attribute check per span
    until :meth:`enable_tracing` is called.
    """

    def __init__(self, *, metrics: MetricsRegistry | None = None,
                 tracer: Tracer | None = None):
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else Tracer()

    def span(self, name: str, **args):
        return self.tracer.span(name, **args)

    def enable_tracing(self) -> "Telemetry":
        self.tracer.enable()
        return self


_default = Telemetry()


def default() -> Telemetry:
    """The process-wide instance used by module-level seams (kernel
    dispatch counters, sharded gather counters)."""
    return _default


def set_default(tel: Telemetry) -> Telemetry:
    """Swap the process-wide instance (tests); returns the previous one."""
    global _default
    prev, _default = _default, tel
    return prev
