#!/usr/bin/env python3
"""The benchmark: one cell of ``BENCHMARK.json`` on the chips of this host.

    python bench/run.py --workload bmlp.interactive --seed 7 --seconds 40 --trace 0

One run is one process.  It refuses to run without a TPU; turns on
JAX's persistent compilation cache inside the checkout; builds the
configuration's weights on the device; registers them with
``PackedInferenceServer(backend="pallas")``; warms the buckets the cell
can flush through (all of that is ``setup_s``); drives the cell's
traffic for ``--seconds``; then checks a sample of what the window
served against the plain float reference.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``), then
``checks``, the numbers compared beside their limits.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by name: ``bench/configs/<config>.json`` (with
its plain reference ``bench/reference/<reference>.py``),
``bench/traffic/<traffic>.json`` and ``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for _p in (BENCH, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import checks  # noqa: E402
import loadgen  # noqa: E402
import serving  # noqa: E402
import trace_reduce  # noqa: E402

RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# -- finding things by name --------------------------------------------------

def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration, traffic mix) for workload ``name``."""
    bench = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = _json(os.path.join(root, cfg_entry["file"]))
    mix = _json(os.path.join(root, "bench", "traffic",
                             cell["traffic"] + ".json"))
    return bench, cell, cfg, mix


def reference_module(cfg: dict):
    return importlib.import_module(f"reference.{cfg['reference']}")


def metric_reader(name: str, root: str = ROOT):
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that apply to ``cell``."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]


# -- the last line -----------------------------------------------------------

def result_line(*, correct: bool, attempted: int, failed: int,
                metrics: dict, device: dict, checks_: dict,
                breakdown: dict | None = None) -> str:
    """The contract's last line; ``checks`` comes last."""
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks_
    return json.dumps(out)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- what happens inside the window -------------------------------------------

class Watch:
    """JAX's duration events (tracing, compilation, cache retrieval) by
    the phase of the run they fall in, and, inside the window, the
    pauses of Python's garbage collector (start, generation, seconds)."""

    def __init__(self):
        import jax
        self.phase = "setup"
        self.events: dict[tuple[str, str], list[float]] = \
            collections.defaultdict(list)
        self.gc_pauses: list[tuple[float, int, float]] = []
        self._gc_t0: float | None = None
        jax.monitoring.register_event_duration_secs_listener(self._on_jax)
        gc.callbacks.append(self._on_gc)

    def _on_jax(self, event: str, duration: float, **_):
        self.events[(self.phase, event)].append(duration)

    def compiles(self, phase: str) -> int:
        return sum(len(v) for (p, e), v in self.events.items()
                   if p == phase and e.startswith("/jax/core/compile/"))

    def summary(self, phase: str) -> str:
        return ", ".join(f"{e} {len(v)} x {sum(v):.3f}s"
                         for (p, e), v in sorted(self.events.items())
                         if p == phase)

    def _on_gc(self, phase: str, info: dict):
        if self.phase != "window":
            return
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_pauses.append((self._gc_t0, info["generation"],
                                   time.perf_counter() - self._gc_t0))
            self._gc_t0 = None


# -- the window ---------------------------------------------------------------

@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    attempted: int
    failed: int
    latencies_s: list[float]
    lateness_s: list[tuple[float, float]]   # (due into the window, seconds late)
    images: int
    sample_x: np.ndarray          # images of the compared sample
    sample_y: np.ndarray          # what the server returned for them
    flushes: list                 # the server's FlushRecords of the window
    spans: list[dict]             # the server's complete spans, if traced


class Sink:
    """Moves the server's flush records, and its tracer's complete spans
    when tracing, out after every call, so that neither bounded buffer
    drops one in a long window."""

    def __init__(self, srv):
        self.srv = srv
        self.tracer = srv.telemetry.tracer
        self.flushes: list = []
        self.spans: list[dict] = []

    def __call__(self) -> None:
        self.flushes += self.srv.flushes
        self.srv.flushes.clear()
        if self.tracer.enabled:
            self.spans += [e for e in self.tracer.events if e["ph"] == "X"]
            self.tracer.clear()


def drive_open(srv, traffic: loadgen.Traffic, mix: dict,
               seconds: float) -> Window:
    """Open loop: submit each request at its due time, step the server
    while anything is queued.  Latency runs from the due time to the
    row on the host, so a request due during a flush counts the wait."""
    due, image_of, pool = traffic.due, traffic.image_of, traffic.pool
    n = len(due)
    done_at = np.full(n, np.nan)
    submitted_at = np.full(n, np.nan)
    rows: dict[int, np.ndarray] = {}
    status: dict[int, str] = {}
    index_of: dict[int, int] = {}
    sink = Sink(srv)
    i = 0
    t0 = time.perf_counter()
    drain_end = t0 + seconds + mix["drain_s"]
    while True:
        now = time.perf_counter()
        while i < n and t0 + due[i] <= now:
            rid = srv.submit(pool[image_of[i]], deadline=0.0)
            index_of[rid] = i
            submitted_at[i] = now
            i += 1
        if srv.pending():
            done = srv.step()
            sink()
            for r in done:
                k = index_of.pop(r.rid)
                status[k] = r.status
                done_at[k] = r.completed_at
                if r.status == "ok":
                    rows[k] = r.result
            continue
        if i >= n or now > drain_end:
            break
        wait = t0 + due[i] - time.perf_counter()
        if wait > 0.002:
            time.sleep(wait - 0.001)
        while time.perf_counter() < t0 + due[i]:
            pass
    t1 = time.perf_counter()
    ok = [k for k in range(n) if status.get(k) == "ok"]
    lat = [float(done_at[k] - (t0 + due[k])) for k in ok]
    late = [(float(due[k]), float(submitted_at[k] - (t0 + due[k])))
            for k in range(i)]
    failed = n - len(ok)
    keep = np.array(sorted(rows), dtype=np.int64)
    sx = pool[image_of[keep]] if len(keep) else pool[:0]
    sy = (np.stack([np.asarray(rows[k]) for k in keep]) if len(keep)
          else np.zeros((0, 1), np.float32))
    return Window(t0=t0, t1=t1, attempted=n, failed=failed,
                  latencies_s=lat, lateness_s=late, images=len(ok),
                  sample_x=sx, sample_y=sy, flushes=sink.flushes,
                  spans=sink.spans)


def drive_closed(srv, traffic: loadgen.Traffic, mix: dict, seconds: float,
                 seed: int) -> Window:
    """Closed loop: one client serves batch after batch, back to back,
    until ``seconds`` have passed; the window ends when the last call
    returns.  A seeded reservoir keeps ``check_batches`` calls' results
    for the comparison."""
    rng = np.random.default_rng([seed, 2])
    keep_n = mix["check_batches"]
    kept: list[tuple[int, list]] = []
    images = failed = 0
    sink = Sink(srv)
    gen = loadgen.closed_batch(traffic.pool)
    t0 = time.perf_counter()
    end = t0 + seconds
    while time.perf_counter() < end:
        k, batch = next(gen)
        try:
            out = srv.serve(batch, deadline=0.0)
        except RuntimeError as e:          # a request ended non-ok
            log(f"serve failed: {e}")
            failed += len(batch)
            continue
        finally:
            sink()
        images += len(out)
        if len(kept) < keep_n:
            kept.append((k, out))
        else:
            j = int(rng.integers(0, k + 1))
            if j < keep_n:
                kept[j] = (k, out)
    t1 = time.perf_counter()
    pool = traffic.pool
    sx = np.concatenate([pool[k % len(pool)] for k, _ in kept])
    sy = np.concatenate([np.stack(out) for _, out in kept])
    return Window(t0=t0, t1=t1, attempted=images + failed, failed=failed,
                  latencies_s=[], lateness_s=[], images=images, sample_x=sx,
                  sample_y=sy, flushes=sink.flushes, spans=sink.spans)


DRIVERS = {"open_poisson": drive_open, "closed_batch": drive_closed}


# -- end-to-end metrics (host clock) -------------------------------------------

def percentile(values: list[float], q: float) -> float:
    """Linear-interpolated percentile, ``q`` in [0, 100]."""
    return float(np.percentile(np.asarray(values), q))


def end_to_end(w: Window, setup_s: float) -> dict[str, float]:
    out = {"setup_s": setup_s}
    if w.latencies_s:
        for q in (50, 95):
            out[f"latency_p{q}_ms"] = percentile(w.latencies_s, q) * 1e3
    else:
        out["images_per_s"] = w.images / (w.t1 - w.t0)
    return out


# -- per-layer metrics (device trace + the server's spans) ---------------------

@dataclasses.dataclass
class LayerContext:
    """What a per-layer reader may read: the device trace and flush
    records of the profiled half of a traced window, and the server's
    spans of its other half."""
    trace: trace_reduce.Reduced
    spans: list[dict]             # the server's complete spans
    flushes: list[dict]           # FlushRecords in the trace: batch, bucket, route
    images: int                   # rows served in the trace
    cfg: dict
    reference: object             # bench/reference module of the config
    device_kind: str


def breakdown(red: trace_reduce.Reduced) -> dict:
    ops = sorted(red.op_s.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in red.gaps[:10]]}


# -- one run --------------------------------------------------------------------

def device_info(devices, chips: int, require_tpu: bool) -> dict:
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform {dev.platform!r} "
                     f"({dev.device_kind})")
    if require_tpu and len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


@dataclasses.dataclass
class Ready:
    """A warmed server and what was built for it."""
    srv: object
    params: dict
    refmod: object
    device: dict
    phases: dict[str, float]      # seconds of each step of the set-up
    watch: Watch


def setup(cell: dict, cfg: dict, mix: dict, *,
          require_tpu: bool = True) -> Ready:
    """Device check, compilation cache, weights on the device, the
    server, and every bucket warmed; each step timed."""
    phases: dict[str, float] = {}
    last = [T_START]

    def mark(name: str) -> None:
        now = time.perf_counter()
        phases[name] = now - last[0]
        last[0] = now

    import jax
    import repro.train.serve  # noqa: F401
    watch = Watch()
    mark("imports")
    device = device_info(jax.devices(), cell["chips"], require_tpu)
    mark("devices")
    if require_tpu:
        from repro.utils.compile_cache import enable_compile_cache
        log(f"compile cache: {enable_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    refmod = reference_module(cfg)
    params = jax.block_until_ready(
        refmod.init_params(cfg, cfg["weights_seed"]))
    mark("weights")
    srv = serving.build_server(cfg, mix, params)
    mark("register")
    shape = tuple(cfg["input_shape"])
    for bucket, sec in serving.warm(srv, np.zeros(shape, np.uint8)).items():
        phases[f"warm b{bucket}"] = sec
    mark("warm total")
    return Ready(srv=srv, params=params, refmod=refmod, device=device,
                 phases=phases, watch=watch)


def lateness_outside_flushes(w: Window) -> list[float]:
    """Lateness of the requests due while no flush ran (from a flush's
    ``start_ns`` stamp to its end, ``at``): how far the open loop fell
    behind its schedule for a reason other than waiting for a flush."""
    if not w.lateness_s:
        return []
    due, late = (np.array(x) for x in zip(*w.lateness_s))
    if not w.flushes:
        return late.tolist()
    spans = np.array(sorted((f.start_ns * 1e-9, f.at) for f in w.flushes))
    ends = np.maximum.accumulate(spans[:, 1])
    t = w.t0 + due
    k = np.searchsorted(spans[:, 0], t, side="right") - 1
    inside = (k >= 0) & (t <= ends[np.maximum(k, 0)])
    return late[~inside].tolist()


def _outside(w: Window) -> str:
    late = lateness_outside_flushes(w)
    if not late:
        return "none due outside flushes;"
    return (f"due outside flushes {len(late)}: lateness p99 "
            f"{percentile(late, 99) * 1e3:.4f}ms max "
            f"{max(late) * 1e3:.4f}ms;")


def _log_window(tag: str, w: Window) -> None:
    routes: dict = {}
    for f in w.flushes:
        routes[(f.bucket, f.route)] = routes.get((f.bucket, f.route), 0) + 1
    span = w.t1 - w.t0
    wall = (sum(f.wall_s for f in w.flushes) / len(w.flushes) * 1e3
            if w.flushes else float("nan"))
    longest = sorted(w.flushes, key=lambda f: -f.wall_s)[:3]
    log(f"{tag} window {span:.3f}s: attempted {w.attempted}, failed "
        f"{w.failed}, images {w.images}; {len(w.flushes)} flushes, "
        f"{len(w.flushes) / span:.3f}/s, mean flush wall {wall:.4f}ms, "
        f"longest (s into the window, ms) "
        f"{[(round(f.at - w.t0, 3), round(f.wall_s * 1e3, 3)) for f in longest]}"
        f"; (bucket, route): count {sorted(routes.items())}")
    if w.lateness_s:
        late = sorted(x for _, x in w.lateness_s)
        worst = sorted(w.lateness_s, key=lambda d: -d[1])[:3]
        log(f"{tag} generator lateness: p50 {percentile(late, 50) * 1e3:.4f}"
            f"ms p99 {percentile(late, 99) * 1e3:.4f}ms over {len(late)} "
            f"requests; worst (due s, late ms) "
            f"{[(round(a, 3), round(b * 1e3, 3)) for a, b in worst]}; "
            + _outside(w) +
            f" latency p50 / p95 / p99 over {len(w.latencies_s)} samples: "
            + " / ".join(f"{percentile(w.latencies_s, q) * 1e3:.4f}"
                         for q in (50, 95, 99)) + " ms")


PROFILE_HOST_TRACER_LEVEL = 0
"""No host events in the profile: at level 1 or 2 the runtime records
a ``Transpose`` event per chunk of every input transfer's layout change,
some 18,750 per 256-image BCNN flush, which slows that flush from 30 to
72 ms.  The trace's reduction needs only the device planes."""


def run(bench: dict, cell: dict, cfg: dict, mix: dict, *, seed: int,
        seconds: float, trace: bool, require_tpu: bool = True,
        keep_trace: str | None = None, on_server=None) -> str:
    """One run of ``cell``; returns the last line.  ``on_server`` is
    called with the warmed server (tests plant faults there).

    A traced run splits its window in two halves of the same traffic:
    the device profile is taken in the first, with the server's tracer
    off, and the server's spans in the second, with the profiler off,
    so that neither pays for the other."""
    import jax

    ready = setup(cell, cfg, mix, require_tpu=require_tpu)
    srv = ready.srv
    t = time.perf_counter()
    parts = 2 if trace else 1
    part_s = seconds / parts
    traffic = loadgen.make(mix, part_s, seed, tuple(cfg["input_shape"]))
    drive = DRIVERS[traffic.arrivals]
    kw = {"seed": seed} if traffic.arrivals == "closed_batch" else {}
    if on_server is not None:
        on_server(srv)
    gc.collect()
    gc.freeze()          # set-up's objects leave the collector's scans
    ready.phases["traffic"] = time.perf_counter() - t
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = PROFILE_HOST_TRACER_LEVEL
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    setup_s = time.perf_counter() - T_START
    watch = ready.watch
    log(f"setup {setup_s:.3f}s: " + ", ".join(
        f"{k} {v:.3f}s" for k, v in ready.phases.items()))
    log(f"setup JAX events: {watch.summary('setup')}")
    watch.phase = "window"
    w = drive(srv, traffic, mix, part_s, **kw)
    windows = [w]
    if trace:
        jax.profiler.stop_trace()
        srv.telemetry.tracer.enable()
        windows.append(drive(srv, traffic, mix, part_s, **kw))
        srv.telemetry.tracer.disable()
    watch.phase = "after"
    device = dict(ready.device)
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    for tag, win in zip(("profiled half", "spans half") if trace else
                        ("timed",), windows):
        _log_window(tag, win)
    long_gc = sorted(watch.gc_pauses, key=lambda p: -p[2])[:3]
    log(f"in the window: compilations {watch.compiles('window')}; garbage "
        f"collections {len(watch.gc_pauses)}, longest (s into the window, "
        f"generation, ms) {[(round(a - w.t0, 3), g, round(d * 1e3, 3)) for a, g, d in long_gc]}")

    metrics: dict = {}
    extra = None
    if trace:
        paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if keep_trace:
            shutil.copy(paths[0], keep_trace)
        red = trace_reduce.reduce(trace_reduce.load(paths[0]),
                                  families=cfg["kernel_families"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = LayerContext(
            trace=red, spans=windows[1].spans,
            flushes=[dataclasses.asdict(f) for f in w.flushes],
            images=sum(f.batch for f in w.flushes), cfg=cfg,
            reference=ready.refmod, device_kind=device["kind"])
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        for m in cell_metrics(bench, cell["name"], "per_layer"):
            v = metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        extra = breakdown(red)
        slowest = max((e for e in ctx.spans if e["name"] == "serve.flush"),
                      key=lambda e: e["dur"], default=None)
        if slowest is not None:
            a, b = slowest["ts"], slowest["ts"] + slowest["dur"]
            inner = [(e["name"], round(e["dur"] * 1e-3, 3)) for e in ctx.spans
                     if e is not slowest and a <= e["ts"] <= b
                     and e["name"] != "serve.queue_wait"]
            log(f"slowest flush of the spans half: "
                f"{slowest['dur'] * 1e-3:.3f}ms, phases (ms) {inner}")
        log(f"trace: window {red.window_s:.6f}s busy {red.busy_s:.6f}s over "
            f"{red.devices} device(s), {len(ctx.flushes)} flushes; device "
            f"time by family {red.family_s}; idle {red.gap_s_by_kind}; "
            f"{len(ctx.spans)} spans")
    else:
        e2e = end_to_end(w, setup_s)
        for m in cell_metrics(bench, cell["name"], "end_to_end"):
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    # the program's state goes before the reference runs on the chip
    sample_x = np.concatenate([v.sample_x for v in windows])
    sample_y = np.concatenate([v.sample_y for v in windows])
    attempted = sum(v.attempted for v in windows)
    failed = sum(v.failed for v in windows)
    params, refmod = ready.params, ready.refmod
    del srv, w, windows, ready
    gc.unfreeze()
    gc.collect()
    t = time.perf_counter()
    ref = checks.reference_logits(refmod, cfg, params, sample_x)
    gap = (checks.logit_gap(sample_y, ref) if len(sample_y) else
           float("inf"))
    correct, checked = checks.judge(gap, failed)
    log(f"compared {len(ref)} served rows with the reference in "
        f"{time.perf_counter() - t:.3f}s")
    for name, c in checked.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return result_line(correct=correct, attempted=attempted, failed=failed,
                       metrics=metrics, device=device, checks_=checked,
                       breakdown=extra)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb here (how the "
                         "trace under bench/tests/data was recorded)")
    args = ap.parse_args(argv)
    bench, cell, cfg, mix = load_cell(args.workload)
    try:
        line = run(bench, cell, cfg, mix, seed=args.seed,
                   seconds=args.seconds, trace=bool(args.trace),
                   keep_trace=args.keep_trace)
    except NoChip as e:
        log(f"bench: {e}")
        return 2
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
