"""Serving layer: the Espresso prediction-phase engine + the LM driver.

Two servers live here (see ``docs/serving.md``):

* :class:`PackedInferenceServer` — the paper's whole point made
  operational: a forward-only engine over the packed BCNN/BMLP networks
  (``models/cnn.py``) with a continuous-batching request queue
  (admit/evict per step, deadline-aware flush, no head-of-line blocking
  on ragged arrivals), a packed weight cache keyed by model config
  (pack + fold BN thresholds ONCE, paper C2, reused across requests),
  and a packed-activation scratch pool so steady-state serving does
  zero repacking and zero per-flush host allocation.  Flushes of
  batch ≤ 8 lower to the PR-4 N-major GEMV grid and larger flushes to
  the fused GEMM/stack path — decided by the ONE
  ``kernels.ops.dispatch_batch`` seam the kernels themselves consult.
  A ``(data, model)`` mesh can sit behind the queue: pass
  ``mesh=`` and the engine builds on
  ``distributed.sharding.make_sharded_forward``, sizing its flush
  buckets to the mesh's ``batch_multiple``.

* :class:`BatchedServer` — the LM decode driver (continuous batching
  over a shared KV-cache slot ring); ``make_prefill_step`` /
  ``make_decode_step`` are the step factories the dry-run cells lower.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.kernels import ops as kops
from repro.models import cnn as C
from repro.models import model as M
from repro.telemetry import MetricsRegistry, Telemetry


# ---------------------------------------------------------------------------
# Packed-inference serving (Espresso prediction phase)
# ---------------------------------------------------------------------------

class BackpressureError(RuntimeError):
    """Typed admission shed: the queue is full, the request was NEVER
    admitted (no rid) — the caller sheds or retries later.  Subclasses
    ``RuntimeError`` so pre-existing callers that caught the untyped
    backpressure signal keep working."""


class DeviceLossError(RuntimeError):
    """A device backing the active engine disappeared mid-flush.

    NOT batch-local: retrying or bisecting the batch cannot help when
    the hardware under the compiled forward is gone, so the server
    requeues the in-flight window (zero requests lost) and re-raises
    for a supervisor (``runtime.ServingSupervisor``) to shrink the mesh
    and rebuild the engine on the survivors.
    """

    def __init__(self, survivors: int, msg: str | None = None):
        super().__init__(msg or f"device lost; {survivors} survivor(s)")
        self.survivors = survivors


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded exponential backoff for failing flushes.

    A cohort gets ``1 + max_retries`` dispatch attempts; the k-th retry
    sleeps ``min(max_backoff_s, backoff_base_s * backoff_factor**(k-1))``
    first.  Once the budget is spent a multi-request cohort BISECTS —
    each half gets a fresh budget — so one poison request cannot
    repeatedly kill whole cohorts: bisection isolates it in
    ``O(log batch)`` dispatches and only the singleton completes as
    ``error``.  ``DeviceLossError`` is never retried here (it is not a
    batch-local fault; see its docstring).
    """
    max_retries: int = 2
    backoff_base_s: float = 0.001
    backoff_factor: float = 2.0
    max_backoff_s: float = 0.250

    def backoff(self, attempt: int) -> float:
        """Sleep before retry ``attempt`` (1-based), capped."""
        return min(self.max_backoff_s,
                   self.backoff_base_s * self.backoff_factor
                   ** (attempt - 1))


#: Terminal request states (exactly one per admitted request):
#: served (``ok``), deadline exceeded past the grace factor
#: (``timeout``), flush failed after retries + bisection (``error``).
#: The fourth lifecycle outcome, ``shed``, never gets a rid — ``submit``
#: raises :class:`BackpressureError` before admission.
TERMINAL_STATES = ("ok", "timeout", "error")


@dataclasses.dataclass
class ServeRequest:
    """One forward request in the continuous-batching queue.

    ``x`` is a single example (shape ``models.cnn.packed_input_shape``,
    uint8); ``deadline`` is the absolute clock time by which the request
    must be flushed even if the batch is not full.  ``status`` moves
    ``pending`` → exactly one of :data:`TERMINAL_STATES`; ``result`` /
    ``completed_at`` are filled at completion (``result`` stays None and
    ``error`` carries the exception for non-``ok`` outcomes).
    """
    rid: int
    x: Any
    deadline: float
    submitted_at: float
    status: str = "pending"
    error: BaseException | None = None
    result: np.ndarray | None = None
    completed_at: float | None = None
    # tracer-clock stamp (perf_counter_ns) taken at submit when tracing
    # is enabled — the queue-wait span's start point.  The serving clock
    # may be simulated (SimClock), so it cannot anchor trace timestamps.
    trace_submit_ns: int | None = None

    @property
    def latency(self) -> float | None:
        if self.completed_at is None:
            return None
        return self.completed_at - self.submitted_at


#: The phase stamps of a flush, in order, on the server tracer's clock
#: (``Tracer.now_ns``, ns): flush start; queue popped (end of bucket
#: pad); routed (the cohort's timeouts triaged and its bucket and route
#: chosen: the start of packing); input packed; called (the start of the
#: forward call that succeeded, after any retries); dispatched (that call
#: returned); ready (the device's result is ready); on host (the logits
#: copied to the host); done (every request of the flush completed).
#: ``ready_ns`` is 0 unless the tracer is on or a JAX profiler trace is
#: being taken: waiting for ready apart from the copy wakes the host
#: twice per flush, which only a measured run pays for.
STAMPS = ("start_ns", "popped_ns", "routed_ns", "packed_ns", "called_ns",
          "dispatched_ns", "ready_ns", "on_host_ns", "done_ns")

#: The span each pair of stamps becomes while the tracer is on
#: (``serve.ready`` and ``serve.readback`` split ``serve.compute``).
#: Triage and routing (popped -> routed) and failed attempts with their
#: backoff (packed -> called) belong to no phase.
PHASE_SPANS = (("serve.bucket_pad", "start_ns", "popped_ns"),
               ("serve.pack", "routed_ns", "packed_ns"),
               ("serve.dispatch", "called_ns", "dispatched_ns"),
               ("serve.compute", "dispatched_ns", "on_host_ns"),
               ("serve.ready", "dispatched_ns", "ready_ns"),
               ("serve.readback", "ready_ns", "on_host_ns"),
               ("serve.complete", "on_host_ns", "done_ns"))

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles_started = 0
_compile_listener_registered = False

try:        # private to JAX: read defensively, a miss reads "not profiling"
    from jax._src.profiler import _profile_state as _jax_profile_state
except ImportError:                                   # pragma: no cover
    _jax_profile_state = None


def _profiling() -> bool:
    """True while a JAX profiler trace is being taken in this process."""
    return getattr(_jax_profile_state, "profile_session", None) is not None


def _on_compile_start(event: str, value: float, **_) -> None:
    global _compiles_started
    if event == BACKEND_COMPILE_EVENT:
        _compiles_started += 1


def _count_compiles() -> None:
    """Count JAX backend compilations (or compile-cache loads) as they
    start, process-wide; registered once, however many servers."""
    global _compile_listener_registered
    if not _compile_listener_registered:
        jax.monitoring.register_scalar_listener(_on_compile_start)
        _compile_listener_registered = True


@dataclasses.dataclass(frozen=True, slots=True)
class FlushRecord:
    """Per-flush bookkeeping: how many real requests rode which bucket
    through which dense grid (``route`` ∈ {'gemv', 'gemm'}), how many
    retry attempts the dispatch needed (0 on the healthy path), the
    flush's phase stamps (:data:`STAMPS`), and how many JAX backend
    compilations started during its dispatch."""
    batch: int
    bucket: int
    route: str
    at: float
    wall_s: float
    retries: int = 0
    start_ns: int = 0
    popped_ns: int = 0
    routed_ns: int = 0
    packed_ns: int = 0
    called_ns: int = 0
    dispatched_ns: int = 0
    ready_ns: int = 0
    on_host_ns: int = 0
    done_ns: int = 0
    compiles: int = 0


class PackedModelCache:
    """Pack/fold-once cache keyed by model config (paper C2).

    ``get_or_pack(key, pack_fn)`` returns the cached packed tree for
    ``key`` or calls ``pack_fn()`` exactly once and caches the result —
    re-registering a config the server has already seen (including
    after swapping to a different model and back) never re-packs
    weights or re-folds BN thresholds.  ``invalidate(key)`` drops an
    entry when its underlying parameters changed (the ONLY correct
    response to a weight update — packed trees are derived data).
    Hit/miss/invalidation counts live in a telemetry metrics registry
    (``serve.cache.*`` — pass the server's via ``metrics=``, or a fresh
    one is created); ``hits``/``misses`` remain as read-only views.
    """

    def __init__(self, metrics: MetricsRegistry | None = None):
        self._entries: dict[Any, Any] = {}
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._hits = self.metrics.counter("serve.cache.hits")
        self._misses = self.metrics.counter("serve.cache.misses")
        self._invalidations = self.metrics.counter(
            "serve.cache.invalidations")

    @property
    def hits(self) -> int:
        return self._hits.value

    @property
    def misses(self) -> int:
        return self._misses.value

    def get_or_pack(self, key, pack_fn: Callable[[], Any]):
        if key in self._entries:
            self._hits.inc()
        else:
            self._misses.inc()
            self._entries[key] = pack_fn()
        return self._entries[key]

    def invalidate(self, key) -> bool:
        """Drop ``key``; True if it was cached."""
        dropped = self._entries.pop(key, None) is not None
        if dropped:
            self._invalidations.inc()
        return dropped

    def __contains__(self, key) -> bool:
        return key in self._entries

    def __len__(self) -> int:
        return len(self._entries)


class ActivationPool:
    """Reusable host staging buffers, one per (bucket, example shape).

    Steady-state serving writes every flush into the same preallocated
    buffer — ``allocations`` stops growing once all buckets are warm
    (asserted by ``tests/test_serve_batching.py``), so the request path
    allocates nothing per flush.  Inter-stage activations never appear
    here at all: they stay bit-packed on device inside the jitted
    forward (the fused-epilogue contract, ``docs/kernels.md``).

    Buffer accounting lives in a telemetry metrics registry
    (``serve.pool.allocations`` / ``serve.pool.reuses`` — pass the
    server's via ``metrics=``); ``allocations`` remains a read-only
    view.
    """

    def __init__(self, metrics: MetricsRegistry | None = None):
        self._bufs: dict[tuple, np.ndarray] = {}
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._allocations = self.metrics.counter("serve.pool.allocations")
        self._reuses = self.metrics.counter("serve.pool.reuses")

    @property
    def allocations(self) -> int:
        return self._allocations.value

    def batch_buffer(self, bucket: int, example_shape: tuple[int, ...],
                     dtype=np.uint8) -> np.ndarray:
        key = (bucket, tuple(example_shape), np.dtype(dtype).str)
        buf = self._bufs.get(key)
        if buf is None:
            self._allocations.inc()
            buf = np.zeros((bucket, *example_shape), dtype)
            self._bufs[key] = buf
        else:
            self._reuses.inc()
        return buf


@dataclasses.dataclass
class _Engine:
    """One registered model: its packed tree + compiled forward + the
    static facts the queue needs to size and route flushes."""
    kind: str
    packed: Any
    fwd: Callable[[Any], jax.Array]
    example_shape: tuple[int, ...]
    kw_words: int
    batch_multiple: int
    buckets: tuple[int, ...]


def _default_buckets(max_batch: int) -> tuple[int, ...]:
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return tuple(sorted(set(out)))


class PackedInferenceServer:
    """Continuous-batching server over the packed BCNN/BMLP forwards.

    Queue lifecycle (``docs/serving.md``): ``submit`` admits a request
    FIFO with an absolute flush ``deadline``; every ``step`` flushes
    (a) all full ``max_batch`` windows and (b) — once the OLDEST
    pending deadline has expired — everything still queued, padded up
    to the smallest warm bucket.  Arrivals after a flush started simply
    ride the next one, so a ragged arrival can neither block earlier
    requests (they flush on their own deadline) nor be blocked by them
    (the deadline flush takes the whole queue, not just the expired
    prefix).  ``cancel`` evicts a queued request; ``max_queue`` bounds
    admission (``submit`` raises ``RuntimeError`` when full — the
    backpressure seam).

    Batches are padded to power-of-two buckets (rounded up to the
    engine's ``batch_multiple`` when a mesh sits behind the queue) so
    the compiled-forward cache stays finite; padded rows are zeros and
    their outputs are discarded — served outputs are bit-identical to
    the direct ``*_forward_packed`` call on the unpadded batch
    (``tests/test_serve_batching.py``).  Flushes of bucket ≤ 8 lower
    to the N-major GEMV grid, larger ones to the blocked GEMM / resident
    stack — the ``kernels.ops.dispatch_batch`` seam, recorded per flush
    in ``flushes``.

    Fault tolerance (``docs/robustness.md``): every admitted request
    reaches exactly ONE terminal state (:data:`TERMINAL_STATES`).  A
    flush that raises fails only its own window — it is retried under
    the bounded-backoff :class:`RetryPolicy` and then bisected so a
    poison request errors alone while its cohort is served; a request
    whose deadline is exceeded by more than ``timeout_grace`` × its
    deadline budget completes as ``timeout`` instead of being served
    stale (``timeout_grace=None``, the default, never times out —
    deadlines then only drive flush scheduling); a full queue sheds
    with :class:`BackpressureError`.  ``flush_hook`` is the
    fault-injection seam (``runtime.faults.FaultInjector``) wrapping
    the device dispatch of ``_flush_window``; on
    :class:`DeviceLossError` the window is requeued and the error
    propagates to the ``runtime.ServingSupervisor``, which degrades the
    mesh and rebuilds the engine via :meth:`rebuild_engine`.
    """

    def __init__(self, *, max_batch: int = 32,
                 buckets: tuple[int, ...] | None = None,
                 default_deadline: float = 0.010,
                 max_queue: int | None = None,
                 completed_mailbox: int = 1024,
                 clock: Callable[[], float] = time.monotonic,
                 retry: RetryPolicy | None = None,
                 timeout_grace: float | None = None,
                 sleep: Callable[[float], Any] | None = None,
                 telemetry: Telemetry | None = None):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.max_batch = max_batch
        self._bucket_template = (tuple(sorted(set(buckets)))
                                 if buckets else _default_buckets(max_batch))
        if self._bucket_template[-1] < max_batch:
            raise ValueError(
                f"largest bucket {self._bucket_template[-1]} smaller than "
                f"max_batch {max_batch}")
        self.default_deadline = default_deadline
        self.max_queue = max_queue
        self._clock = clock
        self.retry = retry if retry is not None else RetryPolicy()
        if timeout_grace is not None and timeout_grace < 1.0:
            raise ValueError(
                f"timeout_grace must be >= 1 (a multiple of the deadline "
                f"budget) or None, got {timeout_grace}")
        self.timeout_grace = timeout_grace
        # Backoff sleeps must not stall a simulated clock forever: when
        # the injected clock can advance (SimClock), sleeping IS
        # advancing it, so retry/backoff stays deterministic in tests.
        if sleep is not None:
            self._sleep = sleep
        elif callable(getattr(clock, "advance", None)):
            self._sleep = clock.advance
        else:
            self._sleep = time.sleep
        # The fault-injection seam: when set, `_flush_window` routes its
        # device dispatch through `flush_hook(eng, buf, reqs, default)`
        # instead of calling `default()` (= `eng.fwd(buf)`) directly.
        # `runtime.faults.FaultInjector.attach` installs itself here.
        self.flush_hook: Callable[..., Any] | None = None
        # Per-server telemetry (isolated; tracing off by default — the
        # disabled span path is one attribute check).  The cache and
        # pool write their counters into the SAME registry, so one
        # snapshot carries the whole serve.* taxonomy
        # (docs/observability.md).
        self.telemetry = telemetry if telemetry is not None else Telemetry()
        m = self.telemetry.metrics
        self._m_submitted = m.counter("serve.submitted")
        self._m_completed = m.counter("serve.completed")
        self._m_cancelled = m.counter("serve.cancelled")
        self._m_rejected = m.counter("serve.rejected")
        self._m_flushes = m.counter("serve.flushes")
        self._m_errors = m.counter("serve.errors")
        self._m_retries = m.counter("serve.retries")
        self._m_timeouts = m.counter("serve.timeouts")
        self._m_shed = m.counter("serve.shed")
        self._m_bisections = m.counter("serve.bisections")
        self._m_padded = m.counter("serve.padded_rows")
        self._m_routes = {r: m.counter(f"serve.route.{r}")
                          for r in ("gemv", "gemm")}
        self._m_depth = m.gauge("serve.queue_depth")
        self._h_latency = m.histogram("serve.request_latency_s")
        self._h_wait = m.histogram("serve.queue_wait_s")
        self._h_flush = m.histogram("serve.flush_wall_s")
        self._m_finished = {"ok": self._m_completed,
                            "timeout": self._m_timeouts,
                            "error": self._m_errors}
        _count_compiles()
        self.cache = PackedModelCache(metrics=m)
        self.pool = ActivationPool(metrics=m)
        self._engines: dict[Any, _Engine] = {}
        self._active: Any = None
        self._queue: collections.deque[ServeRequest] = collections.deque()
        # rid -> completed request, claimable via take(); bounded FIFO so
        # callers that consume step()/flush() returns directly (and never
        # claim) cannot leak the mailbox.  served/flushes are bounded the
        # same way — they are observability history, and an unbounded
        # list of requests (each holding its input and result row) would
        # be a steady-state leak in a long-running server.
        self._completed: collections.OrderedDict[int, ServeRequest] = \
            collections.OrderedDict()
        self._completed_cap = max(completed_mailbox, 2 * max_batch)
        self._next_rid = 0
        self.flushes: list[FlushRecord] = []
        self.served: list[ServeRequest] = []

    # -- model registry ----------------------------------------------------

    def register(self, key, params=None, spec=None, *, kind: str | None = None,
                 packed=None, backend: str = "auto",
                 dense_stack: str = "auto", mesh=None) -> Any:
        """Register a model config under ``key`` and activate it if the
        server is idle.

        Either pass float ``params`` + ``spec`` (+ ``kind`` 'bcnn' |
        'bmlp' | 'reactnet' | 'transformer'; for 'transformer' ``spec`` is the
        ``ArchConfig`` and ``params`` come from
        ``models.transformer.init_binary_lm``) — the weight cache packs
        + folds ONCE per key — or a pre-``pack_*`` tree via ``packed=``.  Re-registering a known key
        is a cache hit: neither the packed tree nor the compiled
        forwards are rebuilt.  ``mesh`` puts a ``(data, model)`` device
        mesh behind the queue (``make_sharded_forward``); flush buckets
        are then rounded up to the mesh's data-axis multiple.
        """
        if key not in self._engines:
            self._engines[key] = self._build_engine(
                key, params, spec, kind=kind, packed=packed,
                backend=backend, dense_stack=dense_stack, mesh=mesh)
        else:
            # touch the weight cache so a re-register is an observable hit
            self.cache.get_or_pack(key, lambda: self._engines[key].packed)
        if self._active is None:
            self._active = key
        return key

    def _build_engine(self, key, params, spec, *, kind, packed, backend,
                      dense_stack, mesh) -> _Engine:
        if packed is not None:
            packed_tree = self.cache.get_or_pack(key, lambda: packed)
        else:
            if kind not in ("bcnn", "bmlp", "reactnet", "transformer"):
                raise ValueError(
                    f"kind must be 'bcnn', 'bmlp', 'reactnet' or "
                    f"'transformer', got {kind!r}")
            if kind == "transformer":
                from repro.models import transformer as TF
                pack = TF.pack_transformer
            else:
                pack = {"bcnn": C.pack_bcnn, "bmlp": C.pack_bmlp,
                        "reactnet": C.R.pack_reactnet}[kind]
            packed_tree = self.cache.get_or_pack(
                key, lambda: pack(params, spec))
        kind = C.packed_kind(packed_tree)
        if kind in ("transformer", "reactnet") and mesh is not None:
            raise ValueError(
                f"mesh serving is not supported for the {kind} "
                f"workload (the sharding rules cover bcnn/bmlp)")
        if mesh is not None:
            from repro.distributed.sharding import make_sharded_forward
            fwd = make_sharded_forward(packed_tree, mesh, backend=backend,
                                       dense_stack=dense_stack,
                                       telemetry=self.telemetry)
            batch_multiple = fwd.batch_multiple
        else:
            fwd = C.make_packed_forward(packed_tree, backend=backend,
                                        dense_stack=dense_stack)
            batch_multiple = 1
        buckets = tuple(sorted({_ceil_mult(b, batch_multiple)
                                for b in self._bucket_template}))
        return _Engine(kind=kind, packed=packed_tree, fwd=fwd,
                       example_shape=C.packed_input_shape(packed_tree),
                       kw_words=C.packed_dense_kw_words(packed_tree),
                       batch_multiple=batch_multiple, buckets=buckets)

    def use(self, key) -> list[ServeRequest]:
        """Switch the active model.  Pending requests were submitted
        against the current model, so they are force-flushed first; the
        completions are returned.  Compiled forwards and packed weights
        of BOTH models stay warm — swapping back is free (cache hit)."""
        if key not in self._engines:
            raise KeyError(f"unknown model key {key!r}")
        done = self.flush() if self._queue else []
        self._active = key
        return done

    def invalidate(self, key) -> list[ServeRequest]:
        """Evict ``key`` from the weight cache and engine registry (call
        after a weight update; the next ``register`` re-packs).

        Requests queued against the active model were admitted under the
        OLD weights, so invalidating it force-flushes them first (same
        contract as :meth:`use`); the completions are returned.
        """
        done = (self.flush()
                if key == self._active and self._queue else [])
        self.cache.invalidate(key)
        self._engines.pop(key, None)
        if self._active == key:
            self._active = None
        return done

    def rebuild_engine(self, key, *, packed=None, params=None, spec=None,
                       kind: str | None = None, backend: str = "auto",
                       dense_stack: str = "auto", mesh=None) -> Any:
        """Drop and rebuild the engine for ``key`` WITHOUT flushing
        pending work — the elastic-degradation seam.

        ``use``/``invalidate`` force-flush through the OLD engine first;
        after a device loss that engine's compiled forward can never
        complete, so the supervisor swaps the engine out from under the
        queue instead: the cache entry and compiled forwards are
        dropped, a new engine is built from ``packed`` (typically the
        warm-restored, resharded tree) on ``mesh``, and the still-queued
        requests are served by the NEW engine on the next step — zero
        requests lost.
        """
        if key not in self._engines:
            raise KeyError(f"unknown model key {key!r}")
        self.cache.invalidate(key)
        self._engines.pop(key)
        self._engines[key] = self._build_engine(
            key, params, spec, kind=kind, packed=packed,
            backend=backend, dense_stack=dense_stack, mesh=mesh)
        return key

    def engine(self, key=None) -> _Engine:
        """The registered engine for ``key`` (active model if None) —
        read-only introspection for tests, benchmarks, and the sharded
        verifier (packed tree, compiled forward, buckets, route facts)."""
        key = self._active if key is None else key
        if key not in self._engines:
            raise KeyError(f"unknown model key {key!r}")
        return self._engines[key]

    # -- queue -------------------------------------------------------------

    @property
    def active(self):
        return self._active

    def pending(self) -> int:
        return len(self._queue)

    def submit(self, x, *, deadline: float | None = None) -> int:
        """Admit one example FIFO; returns its rid.  ``deadline`` is
        seconds from now (``default_deadline`` if None).  Raises
        :class:`BackpressureError` when ``max_queue`` requests are
        already pending — the request is SHED, never admitted (the
        fourth lifecycle outcome; the caller backs off or retries)."""
        if self._active is None:
            raise RuntimeError("no model registered")
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            self._m_rejected.inc()
            self._m_shed.inc()
            raise BackpressureError(
                f"queue full ({self.max_queue} pending) — backpressure")
        now = self._clock()
        dl = self.default_deadline if deadline is None else deadline
        req = ServeRequest(rid=self._next_rid, x=x, deadline=now + dl,
                           submitted_at=now)
        self._next_rid += 1
        self._queue.append(req)
        self._m_submitted.inc()
        self._m_depth.set(len(self._queue))
        tr = self.telemetry.tracer
        if tr.enabled:
            req.trace_submit_ns = tr.now_ns()
        return req.rid

    def cancel(self, rid: int) -> bool:
        """Evict a still-queued request; True if it was pending."""
        for r in self._queue:
            if r.rid == rid:
                self._queue.remove(r)
                self._m_cancelled.inc()
                self._m_depth.set(len(self._queue))
                return True
        return False

    def step(self, now: float | None = None) -> list[ServeRequest]:
        """One scheduling step: flush every full ``max_batch`` window,
        then — if the oldest pending deadline has expired — flush the
        rest of the queue too.  Returns the requests completed by this
        step (possibly empty: a partial batch whose deadline is still
        in the future keeps waiting for riders)."""
        now = self._clock() if now is None else now
        done: list[ServeRequest] = []
        while len(self._queue) >= self.max_batch:
            done += self._flush_window(self.max_batch)
        if self._queue and min(r.deadline for r in self._queue) <= now:
            while self._queue:
                done += self._flush_window(self.max_batch)
        return done

    def flush(self) -> list[ServeRequest]:
        """Force-drain the queue regardless of deadlines (shutdown /
        model swap)."""
        done: list[ServeRequest] = []
        while self._queue:
            done += self._flush_window(self.max_batch)
        return done

    def serve(self, xs, *, deadline: float | None = None
              ) -> list[np.ndarray]:
        """Convenience: submit every example, drain, return results in
        submission order (the batch-API view of the queue).

        The drain flushes the WHOLE queue, so requests other callers had
        pending complete too; their completions stay claimable via
        :meth:`take` (they are not lost to this caller's return value).
        Own results are collected from the flush returns directly, so
        ``serve`` works for request counts beyond the mailbox cap.
        Backpressure is all-or-nothing: if the batch would overflow
        ``max_queue``, ``RuntimeError`` is raised before ANY submit, so
        a failed call never strands half its requests in the queue.
        """
        xs = list(xs)
        if self.max_queue is not None and \
                len(self._queue) + len(xs) > self.max_queue:
            self._m_rejected.inc(len(xs))   # same pair submit() bumps
            self._m_shed.inc(len(xs))
            raise BackpressureError(
                f"serve({len(xs)}) would overflow max_queue="
                f"{self.max_queue} ({len(self._queue)} pending) — "
                "backpressure")
        rids = [self.submit(x, deadline=deadline) for x in xs]
        by_rid = {r.rid: r for r in self.flush()}
        for rid in rids:                       # claimed here, not via take()
            self._completed.pop(rid, None)
        bad = [(rid, by_rid[rid].status) for rid in rids
               if by_rid[rid].status != "ok"]
        if bad:
            # the batch-API view has no per-request status channel, so a
            # non-ok outcome must raise rather than hand back None rows
            raise RuntimeError(
                f"serve(): {len(bad)} request(s) ended non-ok: {bad[:4]}"
                f"{'...' if len(bad) > 4 else ''}")
        return [np.asarray(by_rid[rid].result) for rid in rids]

    def take(self, rid: int) -> ServeRequest | None:
        """Claim a completed request by rid (None if unknown / still
        pending).  Every flush parks its completions here until claimed,
        so a caller polling ``step()`` for its own rid still gets its
        result even when ANOTHER caller's flush/serve drained the queue
        — each completion is delivered exactly once per channel."""
        return self._completed.pop(rid, None)

    def route_for(self, batch: int) -> str:
        """Which dense grid a flush of ``batch`` requests lowers to for
        the ACTIVE model ('gemv' | 'gemm') — ``kernels.ops.dispatch_batch``
        on the padded bucket and the model's widest packed-K extent.
        Raises ``RuntimeError`` when no model is active."""
        eng = self._active_engine()
        return kops.dispatch_batch(self._bucket_for(eng, batch),
                                   eng.kw_words)

    # -- flush machinery ---------------------------------------------------

    def _active_engine(self) -> _Engine:
        if self._active is None:
            raise RuntimeError("no model registered")
        return self._engines[self._active]

    def _bucket_for(self, eng: _Engine, n: int) -> int:
        for b in eng.buckets:
            if b >= n:
                return b
        return eng.buckets[-1]

    def _timed_out(self, r: ServeRequest, now: float) -> bool:
        """Deadline exceeded past the grace factor: the request is
        completed as ``timeout`` instead of served stale.  Grace is a
        multiple of the request's own deadline BUDGET (submit → flush
        deadline), so a 5 ms-deadline request with grace 4 times out
        20 ms after submission; ``timeout_grace=None`` disables.

        A non-positive budget (``submit(x, deadline=0)`` means "flush
        me NOW", not "time me out now") would make ANY later flush a
        timeout under a wall clock, so it falls back to the server's
        ``default_deadline`` as the grace base."""
        if self.timeout_grace is None:
            return False
        budget = r.deadline - r.submitted_at
        if budget <= 0.0:
            budget = self.default_deadline
        return now > r.submitted_at + self.timeout_grace * budget

    def _finish(self, reqs: list[ServeRequest], status: str, now: float, *,
                results=None, error: BaseException | None = None) -> None:
        """Move requests to one terminal state — the ONLY writer of
        ``status``, so 'exactly one terminal state per rid' holds by
        construction (re-finishing a finished request is a bug).
        ``results[i]`` is the row of ``reqs[i]``.  Metrics move once
        per call, not once per request."""
        assert status in TERMINAL_STATES, status
        for i, r in enumerate(reqs):
            assert r.status == "pending", (r.rid, r.status, status)
            r.status = status
            r.result = None if results is None else results[i]
            r.error = error
            r.completed_at = now
            self._completed[r.rid] = r
        self._h_latency.observe_many([now - r.submitted_at for r in reqs])
        self._m_finished[status].inc(len(reqs))
        self.served += reqs
        del self.served[:-self._completed_cap]
        while len(self._completed) > self._completed_cap:
            self._completed.popitem(last=False)

    def _dispatch(self, eng: _Engine, buf, reqs: list[ServeRequest]):
        """The flush seam: everything device-side of one dispatch
        attempt.  ``flush_hook`` (fault injection, chaos testing) wraps
        the default ``eng.fwd(buf)`` call when installed."""
        if self.flush_hook is not None:
            return self.flush_hook(eng, buf, reqs, lambda: eng.fwd(buf))
        return eng.fwd(buf)

    def _serve_cohort(self, reqs: list[ServeRequest], eng: _Engine,
                      bucket: int, route: str, start_ns: int,
                      popped_ns: int) -> list[ServeRequest]:
        """Serve one cohort: pad to its bucket, dispatch with bounded
        retry/backoff, bisect on persistent failure, complete every
        request terminally.  Failure isolation contract:

        * an exception from the dispatch fails only THIS cohort — it is
          retried ``retry.max_retries`` times with exponential backoff,
          then the cohort bisects (fresh budget per half) until the
          poison singleton completes as ``error`` while its former
          cohort-mates are served;
        * :class:`DeviceLossError` short-circuits all of that: EVERY
          still-pending request of the cohort goes back to the FRONT of
          the queue — including bisection siblings that were never
          dispatched, at any recursion depth — and the error propagates
          to the supervisor (mesh shrink + engine rebuild), after which
          the requeued requests are served by the new engine.

        The requeue lives HERE, on the outermost cohort, not inside the
        bisection recursion: a per-half requeue would save only the half
        that was dispatching and silently lose its not-yet-dispatched
        siblings (no terminal state, ``take()`` returns None forever).
        """
        try:
            return self._dispatch_cohort(reqs, eng, bucket, route,
                                         start_ns, popped_ns)
        except DeviceLossError:
            pending = [r for r in reqs if r.status == "pending"]
            self._queue.extendleft(reversed(pending))
            self._m_depth.set(len(self._queue))
            raise

    def _route(self, eng: _Engine, n: int) -> tuple[int, str]:
        """The bucket and dense route of an ``n``-row flush."""
        bucket = self._bucket_for(eng, n)
        return bucket, kops.dispatch_batch(bucket, eng.kw_words)

    def _dispatch_cohort(self, reqs: list[ServeRequest], eng: _Engine,
                         bucket: int, route: str, start_ns: int,
                         popped_ns: int) -> list[ServeRequest]:
        """Pack, dispatch, wait, read back and complete one cohort routed
        to ``bucket``/``route``, stamping each phase boundary
        (:data:`STAMPS`) on the tracer's clock into its
        :class:`FlushRecord`.  Each half of a bisected cohort is a flush
        of its own, which starts and pops when the bisection routes it."""
        tr = self.telemetry.tracer
        routed_ns = tr.now_ns()
        t0 = self._clock()
        buf = self.pool.batch_buffer(bucket, eng.example_shape)
        for i, r in enumerate(reqs):
            buf[i] = np.asarray(r.x, buf.dtype)
        buf[len(reqs):] = 0
        packed_ns = tr.now_ns()
        split_ready = tr.enabled or _profiling()
        ready_ns = 0
        compiles = _compiles_started
        attempt = 0
        while True:
            try:
                called_ns = tr.now_ns()
                out_dev = self._dispatch(eng, buf, reqs)
                dispatched_ns = tr.now_ns()
                if split_ready:
                    # ask for the copy to the host now, behind the forward
                    # on the device, as np.asarray alone would: waiting for
                    # ready first would start the copy only once the host
                    # heard
                    if hasattr(out_dev, "copy_to_host_async"):
                        out_dev.copy_to_host_async()
                    jax.block_until_ready(out_dev)
                    ready_ns = tr.now_ns()
                out = np.asarray(out_dev)       # blocks on device work
                on_host_ns = tr.now_ns()
                break
            except DeviceLossError:
                raise        # not batch-local: _serve_cohort requeues
            except Exception as e:
                if attempt < self.retry.max_retries:
                    attempt += 1
                    self._m_retries.inc()
                    self._sleep(self.retry.backoff(attempt))
                    continue
                if len(reqs) == 1:
                    self._finish(reqs, "error", self._clock(), error=e)
                    self._m_depth.set(len(self._queue))
                    return list(reqs)
                self._m_bisections.inc()
                mid = len(reqs) // 2
                done: list[ServeRequest] = []
                for half in (reqs[:mid], reqs[mid:]):
                    half_ns = tr.now_ns()
                    done += self._dispatch_cohort(
                        half, eng, *self._route(eng, len(half)),
                        half_ns, half_ns)
                return done
        compiles = _compiles_started - compiles
        now = self._clock()
        self._h_wait.observe_many([max(0.0, t0 - r.submitted_at)
                                   for r in reqs])
        self._finish(reqs, "ok", now, results=out)
        self._m_flushes.inc()
        self._m_routes[route].inc()
        self._m_padded.inc(bucket - len(reqs))
        self._m_depth.set(len(self._queue))
        self._h_flush.observe(now - t0)
        done_ns = tr.now_ns()
        rec = FlushRecord(
            batch=len(reqs), bucket=bucket, route=route, at=now,
            wall_s=now - t0, retries=attempt, start_ns=start_ns,
            popped_ns=popped_ns, routed_ns=routed_ns, packed_ns=packed_ns,
            called_ns=called_ns, dispatched_ns=dispatched_ns,
            ready_ns=ready_ns, on_host_ns=on_host_ns, done_ns=done_ns,
            compiles=compiles)
        self.flushes.append(rec)
        del self.flushes[:-self._completed_cap]
        if tr.enabled:
            args = {"serve.pack": {"batch": len(reqs), "bucket": bucket},
                    "serve.dispatch": {"route": route}}
            for name, a, b in PHASE_SPANS:
                tr.add_complete(name, getattr(rec, a), getattr(rec, b),
                                **args.get(name, {}))
        return list(reqs)

    def _flush_window(self, limit: int) -> list[ServeRequest]:
        """One flush: pop a FIFO window, triage expired requests to
        ``timeout``, then serve the live cohort (`_serve_cohort` does
        pad → dispatch-with-retry → complete, bisecting on failure).

        Every flush is stamped per phase into its :class:`FlushRecord`
        whether or not tracing is on.  With the server's tracer enabled
        the same stamps become spans (taxonomy in
        ``docs/observability.md``): a ``serve.flush`` parent over
        :data:`PHASE_SPANS`, plus one explicit-time ``serve.queue_wait``
        span per request (submit → flush start).  Metrics (queue-wait /
        latency / flush-wall histograms, route + padded-row + lifecycle
        counters) update unconditionally, once per flush.
        """
        tr = self.telemetry.tracer
        start_ns = tr.now_ns()
        reqs = [self._queue.popleft()
                for _ in range(min(limit, len(self._queue)))]
        if not reqs:
            return []
        eng = self._active_engine()
        now = self._clock()
        popped_ns = tr.now_ns()
        live: list[ServeRequest] = []
        done: list[ServeRequest] = []
        for r in reqs:
            (done if self._timed_out(r, now) else live).append(r)
        if done:
            self._finish(done, "timeout", now)
        flush_args: dict = {"batch": len(reqs)}
        if live:
            bucket, route = self._route(eng, len(live))
            flush_args.update(bucket=bucket, route=route)
            done += self._serve_cohort(live, eng, bucket, route, start_ns,
                                       popped_ns)
        else:
            self._m_depth.set(len(self._queue))
        if tr.enabled:
            # read after the last stamp, so that every phase span ends
            # strictly inside the flush's even after the ns -> us division
            tr.add_complete("serve.flush", start_ns, tr.now_ns(),
                            **flush_args)
            for r in reqs:
                if r.trace_submit_ns is not None:
                    tr.add_complete("serve.queue_wait", r.trace_submit_ns,
                                    start_ns, rid=r.rid)
        return done


def latency_percentile(sorted_vals, q: float):
    """Nearest-rank percentile over a pre-sorted latency list — the one
    definition the serving CLI (``launch/serve.py``) reports.

    Raises ``ValueError`` on an empty sequence (``sorted_vals[-1]`` would
    silently report the caller's last GC'd value as a latency) and on a
    ``q`` outside [0, 1] (``q > 1`` used to clamp to the max — a p200
    typo would masquerade as p100).  A single sample returns that sample
    for every ``q``.
    """
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"percentile q must be in [0, 1], got {q!r}")
    n = len(sorted_vals)
    if n == 0:
        raise ValueError("latency_percentile of an empty sequence")
    return sorted_vals[min(n - 1, int(n * q))]


class SimClock:
    """Deterministic monotonic clock for tests and benches: inject as
    ``PackedInferenceServer(clock=...)`` and drive time by hand."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


def _ceil_mult(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# LM decode serving (scaffold models): step factories + slot-ring driver
# ---------------------------------------------------------------------------

def make_prefill_step(cfg: ArchConfig, max_len: int):
    def prefill_step(params, batch):
        return M.prefill(params, cfg, batch, max_len)
    return prefill_step


def make_decode_step(cfg: ArchConfig):
    def decode_step(params, cache, tokens, idx):
        return M.decode_step(params, cfg, tokens, cache, idx)
    return decode_step


@dataclasses.dataclass
class Request:
    rid: int
    prompt: jax.Array          # (S,) int32
    max_new: int
    out: list = dataclasses.field(default_factory=list)
    truncated: bool = False    # hit the cache length before max_new tokens


class BatchedServer:
    """Minimal continuous-batching server over the jitted decode step.

    All sequences share one ring of decode slots; finished requests free
    their slot for the next queued prompt.  Single-host demo driver for
    examples/serve_binary_lm.py — the distributed serving path is the
    jitted step itself.
    """

    def __init__(self, cfg: ArchConfig, params, batch_slots: int,
                 max_len: int):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.slots = batch_slots
        self.cache = M.init_cache(params, cfg, batch_slots, max_len)
        self.decode = jax.jit(make_decode_step(cfg))
        self.active: dict[int, Request] = {}
        self.idx = 0

    def _reset_slot(self, s: int) -> None:
        """Zero the freed slot's cache rows (K/V and recurrent state).

        A reused slot would otherwise inherit the previous request's rows
        at positions < self.idx — the new occupant's attention reads them.
        Cache leaves are (L, B, ...) with the slot axis at 1.

        Zeroing removes the cross-request information leak (zero V rows
        contribute a zero vector), but the decode mask is global
        (j <= idx), so the zeroed positions still take softmax weight and
        dilute the new occupant's attention vs decoding it alone.  Exact
        isolation needs a per-slot start-position mask in the attention
        step — out of scope for this Python-level driver.
        """
        self.cache = jax.tree.map(
            lambda a: a.at[:, s].set(jnp.zeros_like(a[:, s]))
            if hasattr(a, "ndim") and a.ndim >= 2 and a.shape[1] == self.slots
            else a,
            self.cache)

    def submit_and_run(self, requests: list[Request]) -> list[Request]:
        """Greedy decode all requests (prompts are consumed token-by-token
        — teacher-forcing the prompt through the decode path keeps this
        driver cache-layout agnostic).

        Every submitted request appears in the return value: either
        completed (``max_new`` tokens) or flagged ``truncated=True`` when
        the shared cache ran out of positions before it finished (requests
        still queued at that point come back truncated with empty output).
        """
        queue = list(requests)
        # Resubmitting a truncated request is the natural retry: restart
        # it cleanly (its prompt is re-decoded, so stale tokens from the
        # aborted window must not count toward max_new).
        for r in queue:
            r.out = []
            r.truncated = False
        done: list[Request] = []
        slot_req: dict[int, Request] = {}
        tok = jnp.zeros((self.slots, 1), jnp.int32)
        pos = [0] * self.slots
        # Every slot was freed AND reset when the previous call returned,
        # so each call starts a fresh cache window — without this, one
        # exhausting call would leave idx == max_len forever and every
        # later call would return instantly, all-truncated.
        self.idx = 0
        while (queue or slot_req) and self.idx < self.max_len:
            for s in range(self.slots):
                if s not in slot_req and queue:
                    slot_req[s] = queue.pop(0)
                    pos[s] = 0
            step_tok = []
            for s in range(self.slots):
                r = slot_req.get(s)
                if r is None:
                    step_tok.append(0)
                elif pos[s] < len(r.prompt):
                    step_tok.append(int(r.prompt[pos[s]]))
                else:
                    step_tok.append(r.out[-1] if r.out else 0)
            tok = jnp.asarray(step_tok, jnp.int32)[:, None]
            logits, self.cache = self.decode(self.params, self.cache, tok,
                                             jnp.int32(self.idx))
            nxt = jnp.argmax(logits[:, 0], axis=-1)
            for s in list(slot_req):
                r = slot_req[s]
                pos[s] += 1
                if pos[s] >= len(r.prompt):
                    r.out.append(int(nxt[s]))
                    if len(r.out) >= r.max_new:
                        done.append(r)
                        del slot_req[s]
                        self._reset_slot(s)
            self.idx += 1
        # Cache exhausted: account for every in-flight and queued request,
        # and scrub the abandoned slots so the next call starts clean.
        for s, r in list(slot_req.items()):
            r.truncated = True
            done.append(r)
            self._reset_slot(s)
        for r in queue:
            r.truncated = True
            done.append(r)
        return done
