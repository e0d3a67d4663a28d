"""Packed-inference serving launcher (the Espresso prediction phase).

Builds a reduced BCNN/BMLP with random weights, registers it with the
``train.serve.PackedInferenceServer`` (pack + fold BN ONCE via the
weight cache), replays a deterministic arrival trace against the
continuous-batching queue, and prints per-request p50/p99 latency,
throughput, and the GEMV/GEMM route of every flush:

    PYTHONPATH=src python -m repro.launch.serve --model bmlp \
        --requests 32 --max-batch 8 --deadline-ms 5

    # a (data, model) mesh behind the queue (forced host devices):
    PYTHONPATH=src python -m repro.launch.serve --model bcnn --mesh 2,2

    # CI smoke: tiny shapes, few requests
    PYTHONPATH=src python -m repro.launch.serve --model bmlp --smoke

    # chaos drill: scripted faults (docs/robustness.md), recovery report
    PYTHONPATH=src python -m repro.launch.serve --chaos --smoke

The old LM prefill/decode demo lives in ``examples/serve_binary_lm.py``
(the ``BatchedServer`` driver).
"""
from __future__ import annotations

import os
import sys

# Forced host devices must be set before ANY jax import (same pattern as
# distributed/verify_sharded.py): pre-scan argv for --mesh, in both the
# space-separated ("--mesh 2,2") and equals ("--mesh=2,2") forms.
def _prescan_mesh(argv: list[str]) -> str | None:
    for i, a in enumerate(argv):
        if a == "--mesh" and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith("--mesh="):
            return a.split("=", 1)[1]
    return None


_shape = _prescan_mesh(sys.argv)
if _shape is None and "--chaos" in sys.argv:
    _shape = "4,2"          # the chaos drill needs 8 devices to lose 4
if _shape is not None:
    try:
        _n = 1
        for _d in _shape.split(","):
            _n *= int(_d)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={_n}")
    except ValueError:
        pass                                    # argparse will complain

import argparse
import dataclasses
import json
import statistics
import time

import numpy as np

from repro import telemetry
from repro.models import cnn
from repro.train import serve as SV


def run_chaos(args) -> None:
    """The chaos drill: scripted faults of every kind against one
    supervised server, then a recovery report + hard invariants.

    Phases (each installs a fresh ``FaultInjector`` so its dispatch
    indices are phase-local; the ``SimClock`` makes the whole drill
    deterministic):

    1. ``transient``   — dispatch fails twice, heals inside the retry
       budget: every request ``ok``, retries > 0.
    2. ``poison``      — one rid fails every cohort containing it:
       bisection isolates it (``error``), cohort-mates ``ok``.
    3. ``persistent``  — a whole cohort keeps failing (``error`` x4);
       the NEXT wave is untouched (failure isolation).
    4. ``slow``        — a 1 s flush stall; the following wave ages past
       ``timeout_grace`` and completes ``timeout``.
    5. ``device_loss`` — 8 -> 4 devices: elastic degrade (remesh +
       packed-checkpoint warm restore + engine rebuild under the
       queue), requeued wave served ``ok`` and bit-exact.
    6. ``device_loss@bisect`` — the loss OVERLAPS bisection: a poison
       rid splits the cohort, the loss strikes a clean bisected half,
       and the not-yet-dispatched siblings must requeue too (the
       whole-window requeue regression); degrade 4 -> 2, poison
       ``error``, everything else ``ok``.
    7. ``shed``        — queue filled to ``max_queue``; the next submit
       raises the typed ``BackpressureError``.
    8. ``recovery``    — a clean wave on the degraded mesh: all ``ok``,
       bit-exact, degraded gauge back at 0.

    Exits non-zero if any invariant fails (the CI chaos job's gate):
    retries > 0, zero requests lost (every admitted rid terminal),
    degraded gauge 0 after recovery, post-degrade rows bit-exact.
    """
    import tempfile

    import jax

    from repro.launch.mesh import make_mesh
    from repro.runtime import FaultInjector, FaultPlan, FaultSpec
    from repro.runtime.supervisor import ServingSupervisor

    assert len(jax.devices()) == 8, jax.devices()
    params, spec, kind = cnn.demo_model(args.model, smoke=True)
    clock = SV.SimClock()
    srv = SV.PackedInferenceServer(
        max_batch=8, default_deadline=args.deadline_ms / 1e3,
        max_queue=16, timeout_grace=50.0, clock=clock)
    srv.register("demo", params, spec, kind=kind, backend=args.backend,
                 mesh=make_mesh((4, 2), ("data", "model")))
    eng = srv.engine()
    sup = ServingSupervisor(srv, "demo",
                            ckpt_dir=tempfile.mkdtemp(prefix="chaos_ckpt_"),
                            backend=args.backend)
    sup.checkpoint()                     # healthy-path packed checkpoint

    rng = np.random.default_rng(0)
    xs = rng.integers(0, 256, (16, *eng.example_shape), dtype=np.uint8)
    from repro.distributed.sharding import reshard_packed
    ref_fwd = cnn.make_packed_forward(
        reshard_packed(eng.packed, None), backend="jnp")
    ref = np.asarray(ref_fwd(xs))        # single-device truth rows

    submitted: list[int] = []
    finished: dict[int, SV.ServeRequest] = {}
    shed = 0
    report: list[dict] = []

    def wave(n: int, *, plan=None, supervised=False, advance=0.006,
             phase=""):
        nonlocal finished
        inj = FaultInjector(plan).attach(srv) if plan is not None else None
        if plan is None:
            srv.flush_hook = None
        wave_rids = []
        for _ in range(n):
            i = len(submitted) % 16
            rid = srv.submit(xs[i])
            submitted.append(rid)
            wave_rids.append((rid, i))
        clock.advance(advance)
        done = sup.step() if supervised else srv.step()
        for r in done:
            finished[r.rid] = r
        statuses = {rid: finished[rid].status if rid in finished else "LOST"
                    for rid, _ in wave_rids}
        exact = all(
            finished[rid].status != "ok"
            or (np.asarray(finished[rid].result) == ref[i]).all()
            for rid, i in wave_rids)
        report.append({"phase": phase, "statuses": list(statuses.values()),
                       "bitexact": exact,
                       "injected": list(inj.injected) if inj else []})
        return [finished.get(rid) for rid, _ in wave_rids]

    print("chaos drill: 8 phases on a (4,2) mesh, SimClock-driven")
    wave(8, plan=FaultPlan.of(FaultSpec("transient", times=2)),
         phase="transient")
    poison_rid = len(submitted) + 3
    wave(8, plan=FaultPlan.of(FaultSpec("poison", rid=poison_rid)),
         phase="poison")
    wave(4, plan=FaultPlan.of(FaultSpec("persistent")), phase="persistent")
    wave(4, plan=None, phase="persistent-aftermath")
    wave(4, plan=FaultPlan.of(FaultSpec("slow", delay_s=1.0)), phase="slow")
    wave(4, plan=None, advance=0.400, phase="slow-aftermath(timeout)")
    wave(8, plan=FaultPlan.of(FaultSpec("device_loss", survivors=4)),
         supervised=True, phase="device_loss")
    # device loss overlapping bisection: with the default 3-attempt
    # budget, dispatches 0-2 fail on the full poisoned cohort and 3-5 on
    # its poisoned first half, so dispatch 6 is the first CLEAN bisected
    # pair — the armed loss fires there, with the poison pair and the
    # whole second half never dispatched.  Zero-lost then requires the
    # whole-window requeue (a per-half requeue loses the siblings).
    poison_rid2 = len(submitted) + 3
    wave(8, plan=FaultPlan.of(
            FaultSpec("poison", rid=poison_rid2),
            FaultSpec("device_loss", survivors=2, at_dispatch=6)),
         supervised=True, phase="device_loss@bisect")
    # shed: fill the queue to max_queue, the next submit must raise
    srv.flush_hook = None
    shed_rids = [srv.submit(xs[i % 16]) for i in range(16)]
    submitted.extend(shed_rids)
    try:
        srv.submit(xs[0])
        report.append({"phase": "shed", "statuses": ["NOT-RAISED"],
                       "bitexact": True, "injected": []})
    except SV.BackpressureError:
        shed += 1
        report.append({"phase": "shed", "statuses": ["shed"],
                       "bitexact": True, "injected": []})
    clock.advance(0.006)
    for r in sup.step():
        finished[r.rid] = r
    wave(8, plan=None, phase="recovery")

    m = srv.telemetry.metrics
    lost = [rid for rid in submitted
            if rid not in finished
            or finished[rid].status not in SV.TERMINAL_STATES]
    tally = {s: sum(1 for r in finished.values() if r.status == s)
             for s in SV.TERMINAL_STATES}
    tally["shed"] = shed
    invariants = {
        "retries>0": m.value("serve.retries") > 0,
        "errors>0": m.value("serve.errors") > 0,
        "timeouts>0": m.value("serve.timeouts") > 0,
        "shed>0": m.value("serve.shed") > 0,
        "degraded==2": m.value("serve.degraded") == 2,
        "degraded_state==0": m.value("serve.degraded_state") == 0,
        "zero_lost": not lost,
        "all_waves_bitexact": all(p["bitexact"] for p in report),
        "recovery_all_ok": all(
            r.status == "ok" for r in finished.values()
            if r.rid in submitted[-8:]),
        "ckpt_restore": bool(sup.events
                             and all(e.restored_from == "checkpoint"
                                     for e in sup.events)),
        "survivor_mesh": ([e.mesh_shape for e in sup.events]
                          == [(2, 2), (1, 2)]),
    }
    for p in report:
        print(f"  {p['phase']:26s} {p['statuses']}"
              f"{'' if p['bitexact'] else '  BITEXACT-FAIL'}")
    print(f"terminal tally: {tally}  (submitted={len(submitted)}, "
          f"lost={len(lost)})")
    print(f"degrade events: {[dataclasses.asdict(e) for e in sup.events]}")
    print("recovery invariants:")
    for name, ok in invariants.items():
        print(f"  [{'PASS' if ok else 'FAIL'}] {name}")
    out = {
        "tally": tally, "submitted": len(submitted),
        "lost": len(lost), "invariants": invariants, "phases": report,
        "events": [dataclasses.asdict(e) for e in sup.events],
        "metrics": {k: v for k, v in m.snapshot().items()
                    if k.startswith(("serve.", "faults."))},
    }
    if args.chaos_report:
        with open(args.chaos_report, "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
        print(f"wrote chaos report -> {args.chaos_report}")
    bad = [n for n, ok in invariants.items() if not ok]
    if bad:
        raise SystemExit(f"chaos drill FAILED: {bad}")
    print("chaos drill PASSED: server degraded, recovered, lost nothing")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=("bcnn", "bmlp"), default="bmlp")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--deadline-ms", type=float, default=5.0)
    ap.add_argument("--arrival-ms", type=float, default=0.0,
                    help="inter-arrival gap (0 = back-to-back)")
    ap.add_argument("--backend", default="auto",
                    help="'auto' (pallas on TPU, jnp elsewhere) | 'pallas' "
                         "| 'jnp' | 'ref' (pallas runs interpret-mode "
                         "off-TPU)")
    ap.add_argument("--mesh", default=None,
                    help="data,model mesh behind the queue, e.g. 2,2")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized shapes and request count")
    ap.add_argument("--chaos", action="store_true",
                    help="run the scripted fault-injection drill "
                         "(docs/robustness.md) and print a recovery "
                         "report; exits non-zero if any recovery "
                         "invariant fails")
    ap.add_argument("--chaos-report", default=None, metavar="PATH",
                    help="write the chaos recovery report as JSON")
    ap.add_argument("--metrics", action="store_true",
                    help="print the server's and the process-wide "
                         "(kernel dispatch) telemetry metrics snapshot "
                         "as JSON after the run")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable span tracing and write a Chrome "
                         "trace_event JSON (open in Perfetto / "
                         "chrome://tracing)")
    args = ap.parse_args()
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        args.requests = min(args.requests, 12)
    if args.chaos:
        run_chaos(args)
        return

    params, spec, kind = cnn.demo_model(args.model, smoke=args.smoke)
    srv = SV.PackedInferenceServer(max_batch=args.max_batch,
                                   default_deadline=args.deadline_ms / 1e3)
    if args.trace_out:
        srv.telemetry.enable_tracing()
    t0 = time.monotonic()
    mesh = None
    if args.mesh:
        try:
            shape = tuple(int(d) for d in args.mesh.split(","))
            if len(shape) != 2 or any(d < 1 for d in shape):
                raise ValueError(args.mesh)
        except ValueError:
            ap.error(f"--mesh must be 'data,model' positive ints, "
                     f"got {args.mesh!r}")
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(shape, ("data", "model"))
    srv.register("demo", params, spec, kind=kind, backend=args.backend,
                 mesh=mesh)
    eng = srv.engine()
    print(f"registered {kind} (packed once in {time.monotonic() - t0:.2f}s)"
          f" buckets={eng.buckets} batch_multiple={eng.batch_multiple}"
          f" route@1={srv.route_for(1)} route@{args.max_batch}="
          f"{srv.route_for(args.max_batch)}")

    rng = np.random.default_rng(0)
    xs = rng.integers(0, 256, (args.requests, *eng.example_shape),
                      dtype=np.uint8)
    t0 = time.monotonic()
    # Collect completions from the step() returns, NOT from srv.served:
    # served is bounded observability history (truncated to the mailbox
    # cap), so percentiles over it silently drop the oldest requests
    # once --requests exceeds the cap.
    done = []
    for i in range(args.requests):
        srv.submit(xs[i])
        if args.arrival_ms:
            time.sleep(args.arrival_ms / 1e3)
        done += srv.step()
    while srv.pending():
        done += srv.step()
        time.sleep(args.deadline_ms / 4e3)
    wall = time.monotonic() - t0

    lats = sorted(r.latency for r in done)
    p50 = statistics.median(lats)
    p99 = SV.latency_percentile(lats, 0.99)
    print(f"served {len(done)} requests in {wall:.2f}s "
          f"({len(done) / wall:.1f} req/s)")
    print(f"latency p50={p50 * 1e3:.2f}ms p99={p99 * 1e3:.2f}ms")
    for f in srv.flushes:
        print(f"  flush batch={f.batch} bucket={f.bucket} route={f.route} "
              f"wall={f.wall_s * 1e3:.2f}ms")
    print(f"weight cache: {srv.cache.misses} pack(s), {srv.cache.hits} "
          f"hit(s); scratch pool: {srv.pool.allocations} buffer(s) for "
          f"{len(srv.flushes)} flushes")
    if args.metrics:
        # The server's registry, plus the process-wide one the kernel
        # dispatch seams write (ops.dispatch.*, sharding.gathers).
        snap = {**telemetry.default().metrics.snapshot(),
                **srv.telemetry.metrics.snapshot()}
        print(json.dumps(snap, indent=1, sort_keys=True))
    if args.trace_out:
        srv.telemetry.tracer.export(args.trace_out)
        print(f"wrote {len(srv.telemetry.tracer.events)} trace events -> "
              f"{args.trace_out} (open in Perfetto / chrome://tracing)")
    bad = [r for r in done if r.status != "ok"]
    if bad:
        raise SystemExit(f"{len(bad)} of {len(done)} requests did not end "
                         f"ok, first: rid {bad[0].rid} {bad[0].status} "
                         f"{bad[0].error!r}")


if __name__ == "__main__":
    main()
