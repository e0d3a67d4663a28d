"""Serving-layer tests: the packed-inference server + the LM driver.

PackedInferenceServer (train/serve.py): queue lifecycle under a
simulated clock (ragged arrival order, deadline flush, no head-of-line
blocking, eviction/backpressure), pack-once weight-cache semantics
across config swaps, scratch-pool steady state, bit-exactness of served
outputs against the direct packed forwards over a
(model, batch, backend) matrix, and the GEMV-vs-GEMM launch-shape
contract of the ``kernels.ops.dispatch_batch`` seam.

BatchedServer (LM): request accounting + slot-cache hygiene —
regression for two silent-loss bugs: requests in flight (or still
queued) when the shared cache ran out of positions were returned in
NEITHER ``done`` nor an error, and a freed slot's next occupant
inherited the previous request's stale KV rows.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops as kops
from repro.models import cnn
from repro.models import model as M
from repro.train import serve as SV
from repro.utils.jaxpr import pallas_grids


# ---------------------------------------------------------------------------
# PackedInferenceServer fixtures
# ---------------------------------------------------------------------------

def _bmlp(sizes=(96, 128, 64, 10)):
    spec = cnn.BMLPSpec(sizes=sizes)
    params = cnn.init_bmlp(jax.random.PRNGKey(0), spec)
    return params, spec, "bmlp"


def _bcnn():
    spec = cnn.BCNNSpec(input_hw=(8, 8), c_in=3,
                        stages=(cnn.ConvStage(32),
                                cnn.ConvStage(64, pool=True)),
                        dense=(96, 10))
    params = cnn.init_bcnn(jax.random.PRNGKey(1), spec)
    return params, spec, "bcnn"


def _server(**kw):
    clock = SV.SimClock()
    kw.setdefault("max_batch", 8)
    kw.setdefault("default_deadline", 0.010)
    return SV.PackedInferenceServer(clock=clock, **kw), clock


def _inputs(n, shape, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (n, *shape), dtype=np.uint8)


def _direct(params, spec, kind, xs, backend):
    packed = (cnn.pack_bcnn if kind == "bcnn" else cnn.pack_bmlp)(params,
                                                                 spec)
    fwd = (cnn.bcnn_forward_packed if kind == "bcnn"
           else cnn.bmlp_forward_packed)
    return np.asarray(fwd(packed, jnp.asarray(xs), backend=backend))


# ---------------------------------------------------------------------------
# Queue lifecycle (simulated clock)
# ---------------------------------------------------------------------------

def test_deadline_flush():
    """A partial batch waits for riders until the OLDEST deadline
    expires, then flushes everything pending — not just the expired
    prefix."""
    params, spec, kind = _bmlp()
    srv, clock = _server()
    srv.register("m", params, spec, kind=kind, backend="jnp")
    xs = _inputs(3, srv.engine().example_shape)
    srv.submit(xs[0], deadline=0.010)
    clock.advance(0.004)
    srv.submit(xs[1], deadline=0.010)          # deadline at t=0.014
    srv.submit(xs[2], deadline=0.050)          # far-future deadline
    assert srv.step() == []                    # t=0.004: nothing due
    clock.advance(0.004)
    assert srv.step() == []                    # t=0.008: still early
    clock.advance(0.004)                       # t=0.012: oldest expired
    done = srv.step()
    assert [r.rid for r in done] == [0, 1, 2]  # FIFO, all ride the flush
    assert srv.pending() == 0
    assert len(srv.flushes) == 1 and srv.flushes[0].batch == 3


def test_full_window_flushes_without_deadline():
    params, spec, kind = _bmlp()
    srv, _ = _server(max_batch=4)
    srv.register("m", params, spec, kind=kind, backend="jnp")
    xs = _inputs(9, srv.engine().example_shape)
    for x in xs:
        srv.submit(x)
    done = srv.step()                          # two full windows, no clock
    assert len(done) == 8
    assert srv.pending() == 1                  # the ragged tail waits
    assert [f.batch for f in srv.flushes] == [4, 4]


def test_ragged_arrivals_no_head_of_line_blocking():
    """A request arriving after a flush started rides the NEXT flush;
    it can neither delay the in-flight window nor be starved by it."""
    params, spec, kind = _bmlp()
    srv, clock = _server(max_batch=4)
    srv.register("m", params, spec, kind=kind, backend="jnp")
    xs = _inputs(6, srv.engine().example_shape)
    first = [srv.submit(x, deadline=0.005) for x in xs[:3]]
    clock.advance(0.006)
    done = srv.step()                          # deadline flush of 0..2
    assert [r.rid for r in done] == first
    late = [srv.submit(x, deadline=0.005) for x in xs[3:]]
    assert srv.step() == []                    # late arrivals not yet due
    clock.advance(0.006)
    done = srv.step()
    assert [r.rid for r in done] == late
    assert [f.batch for f in srv.flushes] == [3, 3]


def test_submission_order_preserved_across_windows():
    params, spec, kind = _bmlp()
    srv, clock = _server(max_batch=4)
    srv.register("m", params, spec, kind=kind, backend="jnp")
    xs = _inputs(10, srv.engine().example_shape)
    rids = [srv.submit(x) for x in xs]
    clock.advance(1.0)
    done = srv.step()
    assert [r.rid for r in done] == rids       # FIFO across 4+4+2 windows
    assert [f.batch for f in srv.flushes] == [4, 4, 2]


def test_cancel_and_backpressure():
    params, spec, kind = _bmlp()
    srv, _ = _server(max_queue=3)
    srv.register("m", params, spec, kind=kind, backend="jnp")
    xs = _inputs(4, srv.engine().example_shape)
    rids = [srv.submit(x) for x in xs[:3]]
    with pytest.raises(RuntimeError, match="backpressure"):
        srv.submit(xs[3])
    assert srv.cancel(rids[1])                 # evict a queued request
    assert not srv.cancel(rids[1])             # already gone
    srv.submit(xs[3])                          # slot freed
    done = srv.flush()
    assert [r.rid for r in done] == [rids[0], rids[2], 3]


def test_serve_backpressure_is_atomic():
    """serve() sheds the WHOLE batch when it would overflow max_queue —
    it never strands a half-submitted prefix in the queue."""
    params, spec, kind = _bmlp()
    srv, _ = _server(max_queue=4)
    srv.register("m", params, spec, kind=kind, backend="jnp")
    xs = _inputs(6, srv.engine().example_shape)
    with pytest.raises(RuntimeError, match="backpressure"):
        srv.serve(list(xs))
    assert srv.pending() == 0                  # nothing submitted
    got = np.stack(srv.serve(list(xs[:4])))    # within bound: works
    assert np.array_equal(got, _direct(params, spec, kind, xs[:4], "jnp"))


def test_use_swaps_model_after_force_flush():
    pa, sa, ka = _bmlp((96, 128, 64, 10))
    pb, sb, kb = _bmlp((96, 64, 10))
    srv, _ = _server()
    srv.register("a", pa, sa, kind=ka, backend="jnp")
    srv.register("b", pb, sb, kind=kb, backend="jnp")
    assert srv.active == "a"
    xs = _inputs(2, srv.engine().example_shape)
    rids = [srv.submit(x) for x in xs]
    done = srv.use("b")                        # pending work flushed first
    assert [r.rid for r in done] == rids
    assert srv.active == "b" and srv.pending() == 0


# ---------------------------------------------------------------------------
# Pack-once weight cache + scratch pool
# ---------------------------------------------------------------------------

def test_cache_hit_after_config_swap():
    """Swapping configs and back re-packs NOTHING: the packed tree and
    the compiled forwards of both models stay warm."""
    pa, sa, ka = _bmlp((96, 128, 64, 10))
    pb, sb, kb = _bmlp((96, 64, 10))
    srv, _ = _server()
    srv.register("a", pa, sa, kind=ka, backend="jnp")
    srv.register("b", pb, sb, kind=kb, backend="jnp")
    assert (srv.cache.misses, srv.cache.hits) == (2, 0)
    eng_a = srv.engine("a")
    srv.use("b")
    srv.use("a")                               # swap away and back
    srv.register("a", pa, sa, kind=ka, backend="jnp")   # re-register too
    assert srv.cache.misses == 2               # never re-packed
    assert srv.cache.hits == 1
    assert srv.engine("a") is eng_a            # engine (jit cache) kept
    xs = _inputs(2, eng_a.example_shape)
    assert np.array_equal(np.stack(srv.serve(list(xs))),
                          _direct(pa, sa, ka, xs, "jnp"))


def test_invalidate_forces_repack():
    pa, sa, ka = _bmlp()
    srv, _ = _server()
    srv.register("a", pa, sa, kind=ka, backend="jnp")
    srv.invalidate("a")
    assert srv.active is None
    srv.register("a", pa, sa, kind=ka, backend="jnp")
    assert srv.cache.misses == 2               # repacked after invalidate


def test_invalidate_active_model_flushes_pending_first():
    """Queued requests were admitted under the old weights: invalidating
    the active model serves them (old engine) instead of stranding them
    against a dead key."""
    pa, sa, ka = _bmlp()
    srv, clock = _server()
    srv.register("a", pa, sa, kind=ka, backend="jnp")
    xs = _inputs(2, srv.engine().example_shape)
    rids = [srv.submit(x) for x in xs]
    done = srv.invalidate("a")
    assert [r.rid for r in done] == rids
    assert srv.pending() == 0 and srv.active is None
    clock.advance(1.0)
    assert srv.step() == []                    # no crash on a dead key


def test_take_recovers_foreign_flush_completions():
    """A request drained by ANOTHER caller's serve()/flush() is not
    lost: its completion stays claimable via take(rid)."""
    params, spec, kind = _bmlp()
    srv, _ = _server()
    srv.register("m", params, spec, kind=kind, backend="jnp")
    xs = _inputs(3, srv.engine().example_shape)
    rid = srv.submit(xs[0])                    # caller A, polling step()
    srv.serve(list(xs[1:]))                    # caller B drains the queue
    assert srv.step() == []                    # A's poll: already flushed
    got = srv.take(rid)
    assert got is not None and got.rid == rid
    assert np.array_equal(
        got.result, _direct(params, spec, kind, xs[:1], "jnp")[0])
    assert srv.take(rid) is None               # claimed exactly once


def test_scratch_pool_steady_state_zero_allocations():
    """Once a bucket is warm, serving allocates no new staging buffers:
    the same array is reused flush after flush."""
    params, spec, kind = _bmlp()
    srv, _ = _server(max_batch=4)
    srv.register("m", params, spec, kind=kind, backend="jnp")
    eng = srv.engine()
    xs = _inputs(4, eng.example_shape)
    srv.serve(list(xs))                        # warm the 4-bucket
    allocs = srv.pool.allocations
    buf = srv.pool.batch_buffer(4, eng.example_shape)
    for _ in range(3):
        srv.serve(list(xs))
    assert srv.pool.allocations == allocs
    assert srv.pool.batch_buffer(4, eng.example_shape) is buf


# ---------------------------------------------------------------------------
# Bit-exactness: served == direct packed forward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("batch", [1, 5, 12])
@pytest.mark.parametrize("build", [_bmlp, _bcnn], ids=["bmlp", "bcnn"])
def test_served_outputs_bit_exact(build, batch, backend):
    """Padding to buckets and splitting into windows never changes a
    row: served outputs == the direct ``*_forward_packed`` on the exact
    submitted batch, bit-for-bit, on both backends."""
    params, spec, kind = build()
    srv, _ = _server(max_batch=8)
    srv.register("m", params, spec, kind=kind, backend=backend)
    xs = _inputs(batch, srv.engine().example_shape, seed=batch)
    got = np.stack(srv.serve(list(xs)))
    want = _direct(params, spec, kind, xs, backend)
    assert got.shape == want.shape
    assert np.array_equal(got, want)
    # every flush was recorded with the route the kernels actually took
    assert all(f.route in ("gemv", "gemm") for f in srv.flushes)


# ---------------------------------------------------------------------------
# The dispatch_batch seam + launch-shape evidence
# ---------------------------------------------------------------------------

def test_dispatch_batch_rule():
    assert kops.dispatch_batch(1, 25) == "gemv"
    assert kops.dispatch_batch(8, 4096) == "gemv"     # boundary: fits
    assert kops.dispatch_batch(9, 25) == "gemm"       # M over sublane min
    assert kops.dispatch_batch(1, 4097) == "gemm"     # K over GEMV bound
    assert kops.dispatch_batch(32, 128) == "gemm"
    with pytest.raises(ValueError):
        kops.dispatch_batch(0, 25)
    with pytest.raises(ValueError):
        kops.dispatch_batch(4, 0)


def test_server_route_matches_dispatch_batch():
    params, spec, kind = _bmlp()
    srv, _ = _server(max_batch=32)
    srv.register("m", params, spec, kind=kind, backend="jnp")
    kw = srv.engine().kw_words
    assert srv.route_for(1) == "gemv" == kops.dispatch_batch(1, kw)
    assert srv.route_for(3) == "gemv"                 # bucket 4 still ≤ 8
    assert srv.route_for(9) == "gemm"                 # bucket 16
    assert srv.route_for(32) == "gemm" == kops.dispatch_batch(32, kw)


def test_launch_shapes_gemv_vs_gemm():
    """The launch-shape contract behind ``dispatch_batch``: a batch-1
    flush lowers every dense contraction to the 1-D N-major GEMV grid
    (NO 3-D blocked-GEMM launch in the whole trace), while a batch-32
    flush lowers its contractions to the 3-D (M, N, K) grid."""
    params, spec, kind = _bmlp()
    packed = cnn.pack_bmlp(params, spec)
    fwd = cnn.make_packed_forward(packed, backend="pallas",
                                  dense_stack="per_layer")
    shape = cnn.packed_input_shape(packed)

    g1 = pallas_grids(lambda x: fwd(x), np.zeros((1, *shape), np.uint8))
    assert g1, "no pallas launches traced"
    assert not [g for g in g1 if len(g) == 3], g1     # zero GEMM grids
    assert [g for g in g1 if len(g) == 1], g1         # GEMV grids present

    g32 = pallas_grids(lambda x: fwd(x), np.zeros((32, *shape), np.uint8))
    assert [g for g in g32 if len(g) == 3], g32       # blocked GEMM grids

    # and the server's per-flush records agree with the traced shapes
    srv, _ = _server(max_batch=32)
    srv.register("m", params, spec, kind=kind, backend="pallas")
    eng = srv.engine()
    srv.serve(list(_inputs(1, eng.example_shape)))
    srv.serve(list(_inputs(32, eng.example_shape)))
    assert [f.route for f in srv.flushes] == ["gemv", "gemm"]
    assert [f.bucket for f in srv.flushes] == [1, 32]


def test_serve_beyond_mailbox_cap():
    """serve() collects its results from the flush returns directly, so
    it works for request counts beyond the bounded take() mailbox."""
    params, spec, kind = _bmlp()
    srv, _ = _server(max_batch=8, completed_mailbox=4)
    srv.register("m", params, spec, kind=kind, backend="jnp")
    xs = _inputs(24, srv.engine().example_shape)   # 24 >> cap (16)
    got = np.stack(srv.serve(list(xs)))
    assert np.array_equal(got, _direct(params, spec, kind, xs, "jnp"))


def test_serve_cli_exits_nonzero_when_a_request_fails(monkeypatch):
    """``python -m repro.launch.serve`` must not report a run whose
    requests failed (say, a kernel the chip refused) as a success."""
    import sys

    from repro.launch import serve as cli
    from repro.utils import compile_cache

    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(sys, "argv", ["serve", "--model", "bmlp", "--smoke",
                                      "--requests", "3"])
    cli.main()                                  # healthy run: returns

    def refuse(self, eng, buf, reqs):
        raise RuntimeError("injected dispatch failure")

    monkeypatch.setattr(SV.PackedInferenceServer, "_dispatch", refuse)
    monkeypatch.setattr(SV.RetryPolicy, "backoff", lambda self, attempt: 0.0)
    with pytest.raises(SystemExit) as exc:
        cli.main()
    assert "3 of 3 requests did not end ok" in str(exc.value.code)


def test_history_is_bounded():
    """served/flushes are observability history, capped like the
    mailbox — a long-running server cannot leak request objects."""
    params, spec, kind = _bmlp()
    srv, _ = _server(max_batch=4, completed_mailbox=2)
    srv.register("m", params, spec, kind=kind, backend="jnp")
    cap = srv._completed_cap
    xs = _inputs(4 * cap, srv.engine().example_shape)
    for x in xs:
        srv.serve([x])
    assert len(srv.served) <= cap
    assert len(srv.flushes) <= cap
    assert len(srv._completed) <= cap


def _stamps(f):
    return [getattr(f, k) for k in SV.STAMPS]


def _poison_first_row(eng, buf, reqs, default):
    if reqs[0].rid == 0:
        raise RuntimeError("poison request")
    return default()


def _fail_twice():
    calls = []

    def hook(eng, buf, reqs, default):
        calls.append(1)
        if len(calls) <= 2:
            raise RuntimeError("transient")
        return default()
    return hook


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("path", ["healthy", "retry", "bisect"])
def test_flush_stamps_monotone(path, traced):
    """Every FlushRecord's phase stamps run in order from flush start to
    done, and one flush ends before the next starts — also when the
    dispatch is retried or the cohort bisected around a poison row.
    ``ready_ns`` is stamped only while tracing (0 otherwise)."""
    from repro.telemetry import Telemetry, Tracer

    params, spec, kind = _bmlp()
    ticks = iter(range(10**6, 10**12, 1_000))
    tel = Telemetry(tracer=Tracer(enabled=traced,
                                  clock_ns=lambda: next(ticks)))
    srv, clock = _server(max_batch=8, telemetry=tel)
    srv.register("m", params, spec, kind=kind, backend="jnp")
    srv.flush_hook = {"healthy": None, "retry": _fail_twice(),
                      "bisect": _poison_first_row}[path]
    xs = _inputs(11, srv.engine().example_shape)
    for x in xs:
        srv.submit(x)
    done = srv.flush()
    assert len(done) == 11
    n_ok = sum(r.status == "ok" for r in done)
    assert sum(f.batch for f in srv.flushes) == n_ok
    assert n_ok == (10 if path == "bisect" else 11)
    # bisect: [0..7] -> [0..3] -> [0, 1] -> [0] errors alone, [1] is
    # served; then [2, 3], [4..7] and the second window [8..10]
    assert len(srv.flushes) == {"healthy": 2, "retry": 2, "bisect": 4}[path]
    if path == "retry":
        assert srv.flushes[0].retries == 2
        # the failed attempts and their backoff lie between packed and
        # the call that succeeded
        f = srv.flushes[0]
        assert f.called_ns - f.packed_ns > f.dispatched_ns - f.called_ns
    prev_done = 0
    for f in srv.flushes:
        st = _stamps(f)
        if not traced:
            assert f.ready_ns == 0
            st.remove(0)
        assert st == sorted(st), st       # a bisected half pops nothing
        assert st[0] > prev_done
        prev_done = st[-1]


def test_flush_stamps_ready_while_profiling(tmp_path):
    """A JAX profiler trace, with the server's tracer off, is enough for
    the server to stamp ``ready_ns``: the profiled half of a benchmark
    run splits the wait for the device from the copy to the host."""
    params, spec, kind = _bmlp(sizes=(96, 128, 64, 12))
    srv, _ = _server(max_batch=4)
    srv.register("m", params, spec, kind=kind, backend="jnp")
    x = _inputs(1, srv.engine().example_shape)[0]
    srv.serve([x])
    jax.profiler.start_trace(str(tmp_path))
    try:
        srv.serve([x])
    finally:
        jax.profiler.stop_trace()
    srv.serve([x])
    assert [f.ready_ns > 0 for f in srv.flushes] == [False, True, False]
    f = srv.flushes[1]
    assert f.dispatched_ns <= f.ready_ns <= f.on_host_ns


def test_flush_record_counts_compiles():
    """``compiles`` counts the JAX backend compilations that started
    during a flush's dispatch: a bucket's first flush compiles its
    forward, the next flush through it compiles nothing."""
    params, spec, kind = _bmlp(sizes=(96, 128, 64, 12))
    srv, _ = _server(max_batch=4)
    srv.register("m", params, spec, kind=kind, backend="jnp")
    x = _inputs(1, srv.engine().example_shape)[0]
    srv.serve([x] * 3)
    srv.serve([x] * 3)
    assert srv.flushes[0].compiles >= 1
    assert srv.flushes[1].compiles == 0


def test_register_validation():
    params, spec, _ = _bmlp()
    srv, _ = _server()
    with pytest.raises(ValueError, match="kind"):
        srv.register("m", params, spec, kind="mlp")
    with pytest.raises(RuntimeError, match="no model"):
        srv.submit(np.zeros((96,), np.uint8))
    with pytest.raises(RuntimeError, match="no model"):
        srv.route_for(1)
    srv.register("m", params, spec, kind="bmlp", backend="jnp")
    with pytest.raises(KeyError):
        srv.use("nope")


# ---------------------------------------------------------------------------
# BatchedServer (LM decode driver)
# ---------------------------------------------------------------------------

def _lm_server(slots=2, max_len=8):
    cfg = get_config("starcoder2-3b", reduced=True)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    return SV.BatchedServer(cfg, params, slots, max_len)


def _lm_reqs(n, prompt_len=2, max_new=2):
    return [SV.Request(rid=i,
                       prompt=jnp.arange(prompt_len, dtype=jnp.int32) + i,
                       max_new=max_new)
            for i in range(n)]


def test_every_request_accounted_for():
    """More requests than the cache can serve: completed ones come back
    finished, the rest come back flagged truncated (never dropped)."""
    srv = _lm_server(slots=2, max_len=5)
    reqs = _lm_reqs(5, prompt_len=2, max_new=2)
    out = srv.submit_and_run(reqs)
    assert {r.rid for r in out} == {r.rid for r in reqs}
    finished = [r for r in out if not r.truncated]
    truncated = [r for r in out if r.truncated]
    assert finished and truncated
    for r in finished:
        assert len(r.out) == r.max_new
    for r in truncated:
        assert len(r.out) < r.max_new       # includes never-started (0)


def test_all_complete_when_cache_suffices():
    srv = _lm_server(slots=2, max_len=16)
    out = srv.submit_and_run(_lm_reqs(4, prompt_len=2, max_new=2))
    assert len(out) == 4
    assert all(not r.truncated and len(r.out) == 2 for r in out)


def test_server_survives_exhaustion_and_retries_truncated():
    """A call that exhausts the cache must not leave the server dead:
    the next call starts a fresh window, and resubmitting the truncated
    requests restarts them cleanly (stale partial output discarded, flag
    cleared) rather than splicing tokens from the aborted window."""
    srv = _lm_server(slots=2, max_len=5)
    first = srv.submit_and_run(_lm_reqs(5, prompt_len=2, max_new=2))
    retry = [r for r in first if r.truncated]
    assert retry
    second = srv.submit_and_run(retry[:2])
    assert len(second) == 2
    assert all(not r.truncated and len(r.out) == 2 for r in second)


def test_freed_slot_cache_is_reset():
    """After a request completes, its slot's cache rows are zeroed so the
    next occupant can't read the previous request's KV state."""
    srv = _lm_server(slots=2, max_len=16)
    srv.submit_and_run(_lm_reqs(2, prompt_len=2, max_new=2))
    for leaf in jax.tree.leaves(srv.cache):
        if hasattr(leaf, "ndim") and leaf.ndim >= 2 and \
                leaf.shape[1] == srv.slots:
            assert not np.asarray(leaf[:, 0]).any()
            assert not np.asarray(leaf[:, 1]).any()


def test_reset_slot_is_slot_local():
    srv = _lm_server(slots=2, max_len=8)
    srv.cache = jax.tree.map(
        lambda a: jnp.ones_like(a) if hasattr(a, "ndim") else a, srv.cache)
    srv._reset_slot(0)
    touched = False
    for leaf in jax.tree.leaves(srv.cache):
        if hasattr(leaf, "ndim") and leaf.ndim >= 2 and \
                leaf.shape[1] == srv.slots:
            assert not np.asarray(leaf[:, 0]).any()
            assert np.asarray(leaf[:, 1]).all()
            touched = True
    assert touched
