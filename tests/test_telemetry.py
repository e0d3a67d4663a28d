"""Telemetry subsystem: metrics registry, span tracer, instrumented
serving lifecycle, and the shared jaxpr traversal (taxonomy in
docs/observability.md)."""
import json

import numpy as np
import pytest

from repro import telemetry
from repro.kernels import ops as kops
from repro.models import cnn
from repro.telemetry import (LATENCY_BUCKETS_S, MetricsRegistry, Telemetry,
                             Tracer, log_spaced_buckets)
from repro.telemetry.trace import _NOOP
from repro.train import serve as SV
from repro.analysis import (count_pallas_calls, max_intermediate_bytes,
                            pallas_grids, pallas_launches)


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_log_spaced_buckets():
    edges = log_spaced_buckets(1e-6, 100.0, 4)
    assert edges == LATENCY_BUCKETS_S
    assert list(edges) == sorted(set(edges))
    assert edges[0] == 1e-6 and edges[-1] >= 100.0
    with pytest.raises(ValueError):
        log_spaced_buckets(0.0, 1.0)
    with pytest.raises(ValueError):
        log_spaced_buckets(1.0, 0.5)


def test_counter_gauge_basics():
    m = MetricsRegistry()
    c = m.counter("c")
    c.inc()
    c.inc(3)
    assert c.value == 4
    assert m.counter("c") is c                 # get-or-create
    with pytest.raises(ValueError):
        c.inc(-1)
    g = m.gauge("g")
    g.set(2.5)
    g.set(1.0)
    assert g.value == 1.0
    assert m.value("c") == 4 and m.value("g") == 1.0
    assert m.value("never-touched") == 0


def test_kind_collision_raises():
    m = MetricsRegistry()
    m.counter("x")
    with pytest.raises(TypeError):
        m.gauge("x")
    with pytest.raises(TypeError):
        m.histogram("x")


def test_histogram_observe_and_percentile():
    m = MetricsRegistry()
    h = m.histogram("h")
    with pytest.raises(ValueError):
        h.percentile(0.5)                      # empty
    for v in (2e-6, 2e-6, 2e-6, 0.5):
        h.observe(v)
    assert h.count == 4
    assert h.min == 2e-6 and h.max == 0.5
    # nearest-rank: p50 falls in the bucket covering 2e-6; the returned
    # value is that bucket's upper edge (>= the true value, < next decade)
    p50 = h.percentile(0.5)
    assert 2e-6 <= p50 < 1e-5
    assert h.percentile(1.0) >= 0.5
    with pytest.raises(ValueError):
        h.percentile(1.5)
    with pytest.raises(ValueError):
        h.percentile(-0.1)


def test_histogram_overflow_reports_exact_max():
    m = MetricsRegistry()
    h = m.histogram("h")
    h.observe(12345.0)                         # above the 100 s ladder
    assert h.percentile(0.99) == 12345.0


def test_snapshot_reset_merge():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("n").inc(2)
    a.gauge("g").set(7.0)
    a.histogram("h").observe(0.001)
    snap = a.snapshot()
    assert json.loads(json.dumps(snap)) == snap        # JSON-able
    b.counter("n").inc(1)
    b.merge(snap)
    assert b.value("n") == 3
    assert b.value("g") == 7.0
    assert b.histogram("h").count == 1
    a.reset()
    assert a.value("n") == 0 and a.histogram("h").count == 0
    # merging histograms with different edges must refuse, not corrupt
    c = MetricsRegistry()
    c.histogram("h", buckets=(1.0, 2.0)).observe(1.5)
    with pytest.raises(ValueError):
        c.merge(snap)


@pytest.mark.parametrize("values", [
    [2e-6, 2e-6, 2e-6, 0.5],
    [3e-4, 1e-6, 12345.0, 0.0, 1e-6 * (1 + 1e-12)],   # edge, overflow
    list(np.random.default_rng(0).lognormal(-7.0, 2.0, 513)),
    [0.004],
    [],
], ids=["few", "edges_and_overflow", "many", "single", "empty"])
def test_observe_many_equals_observe(values):
    one, many = MetricsRegistry(), MetricsRegistry()
    one.histogram("h")
    for v in values:
        one.histogram("h").observe(v)
    many.histogram("h").observe_many(values)
    assert many.snapshot() == one.snapshot()
    many.histogram("h").observe_many(np.asarray(values[:2]))
    for v in values[:2]:
        one.histogram("h").observe(v)
    assert many.snapshot() == one.snapshot()


def test_single_sample_histogram_percentiles():
    h = MetricsRegistry().histogram("h")
    h.observe(0.004)
    for q in (0.0, 0.5, 0.99, 1.0):
        assert h.percentile(q) >= 0.004


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

def test_disabled_tracer_is_noop_singleton():
    tr = Tracer()
    assert not tr.enabled
    assert tr.span("a") is tr.span("b") is _NOOP
    with tr.span("a", k=1):
        pass
    tr.instant("x")
    tr.add_complete("y", 0, 10)
    assert tr.events == []


def test_spans_record_chrome_events():
    clock = iter(range(0, 100_000, 1_000))
    tr = Tracer(enabled=True, clock_ns=lambda: next(clock))
    with tr.span("outer", batch=4):
        with tr.span("inner"):
            pass
    tr.instant("mark", rid=7)
    tr.add_complete("explicit", 5_000, 9_000, rid=1)
    evs = tr.events
    assert [e["name"] for e in evs] == ["inner", "outer", "mark", "explicit"]
    outer = evs[1]
    assert outer["ph"] == "X" and outer["args"] == {"batch": 4}
    assert outer["dur"] > evs[0]["dur"]        # outer contains inner
    assert evs[2]["ph"] == "i"
    assert evs[3]["ts"] == 5.0 and evs[3]["dur"] == 4.0   # ns -> us
    doc = tr.chrome_trace()
    assert doc["traceEvents"] == evs
    assert json.loads(json.dumps(doc)) == doc


def test_tracer_bounded_buffer_counts_drops():
    tr = Tracer(enabled=True, max_events=2)
    for i in range(5):
        tr.instant(f"e{i}")
    assert len(tr.events) == 2
    assert tr.dropped == 3
    tr.clear()
    assert tr.events == [] and tr.dropped == 0


def test_tracer_export(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("s"):
        pass
    path = tmp_path / "trace.json"
    tr.export(str(path))
    doc = json.load(open(path))
    assert doc["traceEvents"][0]["name"] == "s"
    assert doc["displayTimeUnit"] == "ms"


def test_telemetry_bundle():
    tel = Telemetry()
    assert not tel.tracer.enabled
    assert tel.enable_tracing() is tel
    assert tel.tracer.enabled
    prev = telemetry.set_default(tel)
    try:
        assert telemetry.default() is tel
    finally:
        telemetry.set_default(prev)


# ---------------------------------------------------------------------------
# latency_percentile edge cases (the CLI/bench shared definition)
# ---------------------------------------------------------------------------

def test_latency_percentile_empty_raises():
    with pytest.raises(ValueError):
        SV.latency_percentile([], 0.5)


def test_latency_percentile_bad_q_raises():
    with pytest.raises(ValueError):
        SV.latency_percentile([1.0], 2.0)      # p200 typo != p100
    with pytest.raises(ValueError):
        SV.latency_percentile([1.0], -0.5)


def test_latency_percentile_single_and_ranks():
    assert SV.latency_percentile([3.0], 0.0) == 3.0
    assert SV.latency_percentile([3.0], 0.99) == 3.0
    assert SV.latency_percentile([3.0], 1.0) == 3.0
    vals = [1.0, 2.0, 3.0, 4.0]
    assert SV.latency_percentile(vals, 0.0) == 1.0
    assert SV.latency_percentile(vals, 0.5) == 3.0
    assert SV.latency_percentile(vals, 1.0) == 4.0


# ---------------------------------------------------------------------------
# instrumented serving lifecycle
# ---------------------------------------------------------------------------

def _smoke_server(**kw):
    params, spec, kind = cnn.demo_model("bmlp", smoke=True)
    srv = SV.PackedInferenceServer(**kw)
    srv.register("m", params, spec, kind=kind, backend="jnp")
    return srv


def test_serve_metrics_lifecycle():
    clock = SV.SimClock()
    srv = _smoke_server(max_batch=4, clock=clock)
    m = srv.telemetry.metrics
    eng = srv.engine()
    xs = np.zeros((5, *eng.example_shape), np.uint8)
    for x in xs[:3]:
        srv.submit(x)
    assert m.value("serve.submitted") == 3
    assert m.value("serve.queue_depth") == 3
    rid = srv.submit(xs[3])
    assert srv.cancel(rid)
    assert m.value("serve.cancelled") == 1
    clock.advance(1.0)                         # expire deadlines
    done = srv.step()
    assert len(done) == 3
    assert m.value("serve.completed") == 3
    assert m.value("serve.flushes") == 1
    assert m.value("serve.padded_rows") == 1   # 3 requests in bucket 4
    assert m.value("serve.route.gemv") == 1
    assert m.value("serve.queue_depth") == 0
    assert m.histogram("serve.request_latency_s").count == 3
    assert m.histogram("serve.queue_wait_s").count == 3
    assert m.histogram("serve.flush_wall_s").count == 1


def test_serve_backpressure_counts_rejections():
    srv = _smoke_server(max_batch=4, max_queue=1)
    x = np.zeros(srv.engine().example_shape, np.uint8)
    srv.submit(x)
    with pytest.raises(RuntimeError):
        srv.submit(x)
    assert srv.telemetry.metrics.value("serve.rejected") == 1


def test_serve_trace_spans_per_flush():
    srv = _smoke_server(max_batch=4)
    srv.telemetry.enable_tracing()
    x = np.zeros(srv.engine().example_shape, np.uint8)
    srv.serve([x, x])
    names = srv.telemetry.tracer.span_names()
    for want in ("serve.queue_wait", "serve.flush",
                 "serve.bucket_pad", "serve.pack", "serve.dispatch",
                 "serve.compute", "serve.ready", "serve.readback",
                 "serve.complete"):
        assert want in names, names
    flushes = [e for e in srv.telemetry.tracer.events
               if e["name"] == "serve.flush"]
    assert len(flushes) == 1
    assert flushes[0]["args"] == {"batch": 2, "bucket": 2, "route": "gemv"}
    # children nest inside the flush window
    f = flushes[0]
    for e in srv.telemetry.tracer.events:
        if e["name"] in ("serve.pack", "serve.dispatch", "serve.compute",
                         "serve.ready", "serve.readback"):
            assert f["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= f["ts"] + f["dur"] + 1e-6


def test_serve_spans_are_the_flush_stamps():
    """With the tracer on, every serve.* span of a flush is the interval
    between two stamps of its FlushRecord: one system, not two."""
    ticks = iter(range(10**6, 10**12, 1_000))
    tel = Telemetry(tracer=Tracer(enabled=True,
                                  clock_ns=lambda: next(ticks)))
    srv = _smoke_server(max_batch=4, telemetry=tel)
    x = np.zeros(srv.engine().example_shape, np.uint8)
    for n in (3, 1, 4):
        srv.serve([x] * n)
    assert len(srv.flushes) == 3
    spans = [e for e in tel.tracer.events if e["name"] != "serve.queue_wait"]
    for name, a, b in SV.PHASE_SPANS:
        got = [(e["ts"], e["dur"]) for e in spans if e["name"] == name]
        want = [(getattr(f, a) / 1e3, (getattr(f, b) - getattr(f, a)) / 1e3)
                for f in srv.flushes]
        assert got == want, name
    # the flush starts at its first stamp and ends after its last, so a
    # reader that tests containment in microseconds keeps every phase
    flushes = [e for e in spans if e["name"] == "serve.flush"]
    for e, f in zip(flushes, srv.flushes, strict=True):
        assert e["ts"] == f.start_ns / 1e3
        assert e["ts"] + e["dur"] > f.done_ns / 1e3
    waits = [e for e in tel.tracer.events if e["name"] == "serve.queue_wait"]
    assert [e["ts"] + e["dur"] for e in waits] == \
        [f.start_ns / 1e3 for f in srv.flushes for _ in range(f.batch)]


def test_serve_tracing_disabled_records_nothing():
    srv = _smoke_server(max_batch=4)
    x = np.zeros(srv.engine().example_shape, np.uint8)
    srv.serve([x])
    assert srv.telemetry.tracer.events == []
    # metrics still live
    assert srv.telemetry.metrics.value("serve.flushes") == 1


# ---------------------------------------------------------------------------
# cache / pool accounting across register -> swap -> swap-back
# ---------------------------------------------------------------------------

def test_cache_counters_across_model_swaps():
    params, spec, kind = cnn.demo_model("bmlp", smoke=True)
    params2, spec2, kind2 = cnn.demo_model("bmlp", smoke=True, seed=1)
    srv = SV.PackedInferenceServer(max_batch=4)
    m = srv.telemetry.metrics
    srv.register("a", params, spec, kind=kind, backend="jnp")
    srv.register("b", params2, spec2, kind=kind2, backend="jnp")
    assert m.value("serve.cache.misses") == 2          # packed once each
    assert m.value("serve.cache.hits") == 0
    x = np.zeros(srv.engine("a").example_shape, np.uint8)
    srv.use("a")
    srv.serve([x])
    srv.use("b")
    srv.serve([x])
    srv.use("a")                                        # swap back
    srv.register("a", params, spec, kind=kind, backend="jnp")
    srv.serve([x])
    assert m.value("serve.cache.misses") == 2           # never re-packed
    assert m.value("serve.cache.hits") == 1             # the re-register
    srv.invalidate("a")
    assert m.value("serve.cache.invalidations") == 1
    srv.register("a", params, spec, kind=kind, backend="jnp")
    assert m.value("serve.cache.misses") == 3           # re-pack after inval


def test_pool_counters_buffer_reuse():
    srv = _smoke_server(max_batch=4)
    m = srv.telemetry.metrics
    eng = srv.engine()
    x = np.zeros(eng.example_shape, np.uint8)
    srv.serve([x])                                      # warm bucket 1
    assert m.value("serve.pool.allocations") == 1
    assert m.value("serve.pool.reuses") == 0
    for _ in range(3):
        srv.serve([x])                                  # steady state
    assert m.value("serve.pool.allocations") == 1       # zero new allocs
    assert m.value("serve.pool.reuses") == 3
    srv.serve([x, x, x])                                # new bucket (4? no: 4)
    assert m.value("serve.pool.allocations") == 2
    buf1 = srv.pool.batch_buffer(1, eng.example_shape)
    buf2 = srv.pool.batch_buffer(1, eng.example_shape)
    assert buf1 is buf2                                  # same object reused


def test_dispatch_batch_counted_once_per_flush():
    g = telemetry.default().metrics
    srv = _smoke_server(max_batch=4)
    x = np.zeros(srv.engine().example_shape, np.uint8)
    before = g.value("ops.dispatch.gemv") + g.value("ops.dispatch.gemm")
    for n in (1, 3, 4, 2):
        srv.serve([x] * n)
    after = g.value("ops.dispatch.gemv") + g.value("ops.dispatch.gemm")
    assert after - before == len(srv.flushes) == 4


def test_dispatch_batch_counts_routes():
    g = telemetry.default().metrics
    before_v = g.value("ops.dispatch.gemv")
    before_m = g.value("ops.dispatch.gemm")
    assert kops.dispatch_batch(1, 16) == "gemv"
    assert kops.dispatch_batch(64, 16) == "gemm"
    assert g.value("ops.dispatch.gemv") == before_v + 1
    assert g.value("ops.dispatch.gemm") == before_m + 1


# ---------------------------------------------------------------------------
# shared jaxpr traversal (repro.analysis.graph)
# ---------------------------------------------------------------------------

def test_pallas_launches_names_and_grids():
    params, spec, kind = cnn.demo_model("bmlp", smoke=True)
    packed = cnn.pack_bmlp(params, spec)
    fwd = cnn.make_packed_forward(packed, backend="pallas")
    x = np.zeros((1, *cnn.packed_input_shape(packed)), np.uint8)
    launches = pallas_launches(lambda a: fwd(a), x)
    assert launches, "no pallas launches traced"
    for ln in launches:
        assert isinstance(ln.kernel, str) and ln.kernel
        assert isinstance(ln.grid, tuple)
        assert all(isinstance(d, int) and d >= 1 for d in ln.grid)
    # the three views are one traversal: they cannot disagree
    assert count_pallas_calls(lambda a: fwd(a), x) == len(launches)
    assert pallas_grids(lambda a: fwd(a), x) == [l.grid for l in launches]
    nbytes, shape = max_intermediate_bytes(lambda a: fwd(a), x)
    assert nbytes > 0 and len(shape) >= 1


def test_max_intermediate_ignores_kernel_internals():
    # jnp backend traces no pallas_call; the fused pallas epilogue must
    # not surface larger HBM intermediates than the unfused jnp path.
    params, spec, kind = cnn.demo_model("bmlp", smoke=True)
    packed = cnn.pack_bmlp(params, spec)
    x = np.zeros((8, *cnn.packed_input_shape(packed)), np.uint8)
    fused = cnn.make_packed_forward(packed, backend="pallas")
    unfused = cnn.make_packed_forward(packed, backend="jnp")
    assert count_pallas_calls(lambda a: unfused(a), x) == 0
    nb_fused, _ = max_intermediate_bytes(lambda a: fused(a), x)
    nb_unfused, _ = max_intermediate_bytes(lambda a: unfused(a), x)
    assert nb_fused <= nb_unfused

