"""A flush's phases on the host, matched to its executions on the device.

The server stamps every flush on its tracer's clock (``FlushRecord``:
start, popped, routed, packed, called, dispatched, ready, on host,
done), whether or not its tracer is on; ``ready`` only while a profiler
trace is being taken (or its tracer is on), as in the profiled half.  A device profile taken over the same flushes
holds the forward's executions (the ``XLA Modules`` line), on the
profile's clock.  This module joins the two:

* match: the profiled flushes to the executions, by order (``k``
  executions per flush where the forward runs several);
* offset: host clock minus device clock, bracketed by causality, since
  the profile's absolute times cannot be trusted across clocks: each
  flush's first execution starts after its forward call began
  (``called``) and its last ends before its result is ready; the
  estimate is the middle of the range that the most flushes admit;
* phases of each flush: bucket pad, pack, dispatch, complete (the host
  phases, each a span with the tracer on), triage and routing (popped
  to routed, with any failed attempts: packed to called; no span),
  launch (the call's return to the device's start), device (first
  execution's start to the last one's end), completion notice (device
  end to ready), readback (ready to on host);
* idle gaps between executions, named by the phase that took most of
  the gap (``outside a flush`` when no flush was running: the harness,
  or waiting for an arrival); gaps inside an execution keep that name.

``runtime(ctx)`` is what the per-layer readers can compute from a
traced run's ``LayerContext`` alone, which carries the stamps and the
trace's totals but not its executions: the same wait and readback as
the per-flush match, less exactly.  Once the harness hands its readers
the executions, ``runtime`` is to read them, and ``main``/``report``
are to go.

    python3 bench/phases.py --workload bmlp.interactive --seed 7 --seconds 20

profiles one window of a cell (the server's tracer off, as in the
profiled half of ``bench/run.py --trace 1``) and prints the match, the
offset, each phase's mean, the named gaps and the slowest flush;
``--out PATH`` writes the same as JSON.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import sys

import trace_reduce

OUTSIDE = "outside a flush"
LAUNCH, NOTICE = "launch", "completion notice"
ROUTING = "triage and routing"
EXECUTION = "execution"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def stamped(flushes: list[dict]) -> str:
    """Why the flush records cannot be split into phases, or ""."""
    if not flushes or not all(f.get("done_ns") for f in flushes):
        return "the flush records carry no phase stamps"
    if not all(f["ready_ns"] for f in flushes):
        return ("the flush records carry no ready stamp (neither a "
                "profiler trace nor the server's tracer was on)")
    return ""


# -- executions --------------------------------------------------------------

def executions(ops: list[tuple[str, float, float]],
               modules: list[tuple[float, float]]
               ) -> list[tuple[float, float, float]]:
    """(start, end, busy) of each execution of one device plane: busy is
    the union of its operations' intervals within it (ns)."""
    starts = [a for _, a, _ in ops]
    out = []
    for ms, me in modules:
        lo = bisect.bisect_left(starts, ms)
        hi = bisect.bisect_right(starts, me)
        inside = [(a, min(b, me)) for _, a, b in ops[lo:hi]]
        out.append((ms, me, sum(b - a for a, b in
                                trace_reduce._union(inside))))
    return out


def group(execs: list[tuple[float, float, float]], n: int
          ) -> tuple[list[tuple[float, float, float]] | None, str]:
    """The executions of each of ``n`` flushes, by order: (first start,
    last end, busy) per flush, or None and why."""
    m = len(execs)
    if not n or not m or m % n:
        return None, (f"{m} executions for {n} flushes: not a whole "
                      f"number of executions per flush")
    k = m // n
    return [(execs[i * k][0], execs[i * k + k - 1][1],
             sum(e[2] for e in execs[i * k:(i + 1) * k]))
            for i in range(n)], f"{k} execution(s) per flush"


# -- the clock offset ----------------------------------------------------------

def offset_bracket(flushes: list[dict],
                   groups: list[tuple[float, float, float]]
                   ) -> tuple[float, float, int]:
    """(lo, hi, count): the range of offsets (host ns - device ns) that
    the most flushes admit, and how many do.  A flush admits every
    offset at which its executions lie between its forward call's start
    (``called``) and its result's being ready."""
    events = []
    for f, (es, ee, _) in zip(flushes, groups):
        lo, hi = f["called_ns"] - es, f["ready_ns"] - ee
        if lo <= hi:
            events += [(lo, 0), (hi, 1)]          # opens sort before closes
    events.sort()
    best, count, at = 0, 0, -1
    for i, (_, kind) in enumerate(events):
        count += 1 if kind == 0 else -1
        if count > best:
            best, at = count, i
    if not best:
        return float("nan"), float("nan"), 0
    # the most flushes hold from that open to the next close
    hi = next(x for x, kind in events[at + 1:] if kind == 1)
    return events[at][0], hi, best


# -- phases of each flush --------------------------------------------------------

def boundaries(f: dict, es: float, ee: float) -> list[tuple[str, float]]:
    """(phase, end) in order through one flush, on the host's clock:
    ``es`` and ``ee`` are its executions' start and end, moved there."""
    return [("serve.bucket_pad", f["popped_ns"]),
            (ROUTING, f["routed_ns"]),
            ("serve.pack", f["packed_ns"]),
            (ROUTING, f["called_ns"]),
            ("serve.dispatch", f["dispatched_ns"]),
            (LAUNCH, es), (EXECUTION, ee), (NOTICE, f["ready_ns"]),
            ("serve.readback", f["on_host_ns"]),
            ("serve.complete", f["done_ns"])]


def flush_row(f: dict, g: tuple[float, float, float], off: float) -> dict:
    """Each phase of one flush, ms.  ``wait`` (launch + completion
    notice) and ``device`` do not depend on the offset; their split
    does, within the bracket's width."""
    es, ee, busy = g
    ms = 1e-6
    return {"bucket_pad": (f["popped_ns"] - f["start_ns"]) * ms,
            "routing": (f["routed_ns"] - f["popped_ns"] +
                        f["called_ns"] - f["packed_ns"]) * ms,
            "pack": (f["packed_ns"] - f["routed_ns"]) * ms,
            "dispatch": (f["dispatched_ns"] - f["called_ns"]) * ms,
            "launch": (es + off - f["dispatched_ns"]) * ms,
            "device": (ee - es) * ms, "device_busy": busy * ms,
            "notice": (f["ready_ns"] - ee - off) * ms,
            "wait": (f["ready_ns"] - f["dispatched_ns"] - (ee - es)) * ms,
            "readback": (f["on_host_ns"] - f["ready_ns"]) * ms,
            "complete": (f["done_ns"] - f["on_host_ns"]) * ms,
            "wall": (f["done_ns"] - f["start_ns"]) * ms,
            "compiles": f.get("compiles", 0), "batch": f["batch"]}


def segments(flushes: list[dict], groups: list[tuple[float, float, float]],
             off: float) -> list[tuple[float, float, str]]:
    """Every flush's phases as non-overlapping (start, end, phase), in
    order, on the host's clock."""
    out = []
    for f, (es, ee, _) in zip(flushes, groups):
        cur = f["start_ns"]
        for name, end in boundaries(f, es + off, ee + off):
            end = max(cur, end)
            if end > cur:
                out.append((cur, end, name))
            cur = end
    return out


def overlaps(a: float, b: float, segs: list[tuple[float, float, str]],
             starts: list[float]) -> dict[str, float]:
    """How much of [a, b] (host clock) each phase took, ns."""
    k = max(0, bisect.bisect_right(starts, a) - 1)
    took: dict[str, float] = collections.defaultdict(float)
    covered = 0.0
    while k < len(segs) and segs[k][0] < b:
        s, e, name = segs[k]
        ov = min(b, e) - max(a, s)
        if ov > 0:
            took[LAUNCH if name == EXECUTION else name] += ov
            covered += ov
        k += 1
    took[OUTSIDE] += (b - a) - covered
    return took


def name_gap(a: float, b: float, segs: list[tuple[float, float, str]],
             starts: list[float]) -> str:
    """The phase that took most of [a, b] (host clock)."""
    took = overlaps(a, b, segs, starts)
    return max(took, key=took.get)


@dataclasses.dataclass
class Phases:
    flushes: int
    executions: int
    note: str                     # how many executions per flush
    offset_ms: float              # host clock - device clock
    bracket_ms: tuple[float, float]
    admitted: float               # share of flushes the offset admits
    after_return: float           # share of flushes whose device start
    #                               lies after their forward call returned
    rows: list[dict]              # per flush, ms (flush_row)
    gap_s_by_name: dict[str, float]
    gaps: list[tuple[str, float]]  # (name, s), longest first
    idle_s_by_phase: dict[str, float]   # each phase's share of the gaps

    def mean(self, key: str) -> float:
        return sum(r[key] for r in self.rows) / len(self.rows)


def analyse(pd, flushes: list[dict], top_gaps: int = 10
            ) -> tuple[Phases | None, str]:
    """Match the stamped ``flushes`` to the executions of the profile
    ``pd``'s first device plane; (Phases, "") or (None, why not)."""
    why = stamped(flushes)
    if why:
        return None, why
    planes = [p for p in trace_reduce.device_ops(pd) if p[1]]
    if not planes:
        return None, "the trace holds no execution"
    ops, modules = planes[0]
    execs = executions(ops, modules)
    groups, note = group(execs, len(flushes))
    if groups is None:
        return None, note
    lo, hi, count = offset_bracket(flushes, groups)
    if not count:
        return None, "no offset puts any execution inside its flush"
    off = (lo + hi) / 2
    rows = [flush_row(f, g, off) for f, g in zip(flushes, groups)]
    after = sum(g[0] + off >= f["dispatched_ns"]
                for f, g in zip(flushes, groups))
    segs = segments(flushes, groups, off)
    starts = [s for s, _, _ in segs]
    merged = trace_reduce._union([(a, b) for _, a, b in ops])
    edges = [x for iv in merged for x in iv]
    by_name: dict[str, float] = collections.defaultdict(float)
    by_phase: dict[str, float] = collections.defaultdict(float)
    gaps = []
    for a, b in zip(edges[1::2], edges[2::2]):
        if b <= a:
            continue
        if trace_reduce._inside(modules, (a + b) / 2):
            name = trace_reduce.INSIDE
            by_phase[name] += (b - a) * 1e-9
        else:
            took = overlaps(a + off, b + off, segs, starts)
            name = max(took, key=took.get)
            for phase, ns in took.items():
                by_phase[phase] += ns * 1e-9
        by_name[name] += (b - a) * 1e-9
        gaps.append((name, (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Phases(flushes=len(flushes), executions=len(execs), note=note,
                  offset_ms=off * 1e-6, bracket_ms=(lo * 1e-6, hi * 1e-6),
                  admitted=count / len(flushes),
                  after_return=after / len(flushes), rows=rows,
                  gap_s_by_name=dict(by_name), gaps=gaps[:top_gaps],
                  idle_s_by_phase=dict(by_phase)), ""


# -- what the per-layer readers compute from a LayerContext ----------------------

@dataclasses.dataclass
class Runtime:
    wait_ms: float                # median launch + completion notice
    readback_ms: float            # median ready -> on host
    idle_pct: float               # 100 x sum(wait + readback) / window


def median(xs: list[float]) -> float:
    xs = sorted(xs)
    n = len(xs)
    return (xs[n // 2] + xs[(n - 1) // 2]) / 2


def runtime(ctx) -> Runtime | None:
    """The runtime's part of the profiled flushes, from their stamps
    and the trace's totals.  Readback is the median over flushes of
    ready -> on host.  Wait is the median of dispatched -> ready less
    the device's mean time per flush: its busy time plus its idle inside
    executions, over the flushes (every execution belongs to a flush:
    nothing else runs on the device in the profiled half).  Medians, so
    that a rare stall flush does not move them.  The idle share sums
    each flush's wait and readback over the window.  None, with the
    reason on stderr, where the stamps are missing or the device was
    busier than the flushes' calls could make it.  The executions are
    not counted here (the context does not carry them): a profile that
    lost some reads a longer wait."""
    fl = ctx.flushes
    why = stamped(fl)
    if why:
        log(f"runtime readers: {why}")
        return None
    device_s = ctx.trace.busy_s + ctx.trace.gap_s_by_kind.get(
        trace_reduce.INSIDE, 0.0)
    call_to_ready_s = sum(f["ready_ns"] - f["called_ns"] for f in fl) * 1e-9
    if device_s > call_to_ready_s:
        log(f"runtime readers: the device ran {device_s:.6f}s, longer "
            f"than the {len(fl)} flushes' calls to their results "
            f"({call_to_ready_s:.6f}s): executions outside the flushes")
        return None
    device_ns = device_s * 1e9 / len(fl)
    waits = [f["ready_ns"] - f["dispatched_ns"] - device_ns for f in fl]
    reads = [f["on_host_ns"] - f["ready_ns"] for f in fl]
    return Runtime(wait_ms=median(waits) * 1e-6,
                   readback_ms=median(reads) * 1e-6,
                   idle_pct=100.0 * (sum(waits) + sum(reads)) * 1e-9 /
                   ctx.trace.window_s)


# -- a profiled window of one cell ------------------------------------------------

PHASE_KEYS = ("bucket_pad", "routing", "pack", "dispatch", "launch", "device",
              "device_busy", "notice", "readback", "complete", "wait",
              "wall")


def report(ph: Phases, red, ctx_runtime: Runtime | None) -> dict:
    """The summary of one analysed window, as printed and written."""
    host = sum(ph.mean(k) for k in ("bucket_pad", "pack", "dispatch",
                                    "complete"))
    wait, dev, rb = ph.mean("wait"), ph.mean("device"), ph.mean("readback")
    routing = ph.mean("routing")
    runtime_s = sum(r["wait"] + r["readback"] for r in ph.rows) * 1e-3
    idle_s = red.window_s - red.busy_s
    slow = max(range(len(ph.rows)), key=lambda i: ph.rows[i]["wall"])
    out = {
        "flushes": ph.flushes, "executions": ph.executions, "match": ph.note,
        "offset_ms": ph.offset_ms, "bracket_ms": list(ph.bracket_ms),
        "bracket_width_ms": ph.bracket_ms[1] - ph.bracket_ms[0],
        "admitted": ph.admitted, "after_return": ph.after_return,
        "mean_ms": {k: ph.mean(k) for k in PHASE_KEYS},
        "median_ms": {k: median([r[k] for r in ph.rows])
                      for k in PHASE_KEYS},
        "sum_ms": {"host": host, "routing": routing, "wait": wait,
                   "device": dev, "readback": rb,
                   "total": host + routing + wait + dev + rb},
        "window_s": red.window_s, "busy_s": red.busy_s,
        "idle_pct": 100.0 * idle_s / red.window_s,
        "idle_in_runtime_pct": 100.0 * runtime_s / red.window_s,
        "gap_s_by_name": ph.gap_s_by_name,
        "idle_s_by_phase": ph.idle_s_by_phase,
        "gaps": [[n, s] for n, s in ph.gaps],
        "slowest": dict(ph.rows[slow], index=slow),
        "compiles": sum(r["compiles"] for r in ph.rows),
    }
    if ctx_runtime is not None:
        out["readers"] = dataclasses.asdict(ctx_runtime)
    return out


def main(argv: list[str] | None = None) -> int:
    import argparse
    import gc
    import glob
    import json
    import os
    import shutil
    import tempfile
    import time

    import loadgen
    import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    bench, cell, cfg, mix = run.load_cell(args.workload)
    try:
        ready = run.setup(cell, cfg, mix)
    except run.NoChip as e:
        log(f"phases: {e}")
        return 2
    traffic = loadgen.make(mix, args.seconds, args.seed,
                           tuple(cfg["input_shape"]))
    kw = ({"seed": args.seed} if traffic.arrivals == "closed_batch"
          else {})
    gc.collect()
    gc.freeze()
    trace_dir = tempfile.mkdtemp(prefix="phases_trace_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = run.PROFILE_HOST_TRACER_LEVEL
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    t0 = time.perf_counter()
    w = run.DRIVERS[traffic.arrivals](ready.srv, traffic, mix, args.seconds,
                                      **kw)
    span = time.perf_counter() - t0
    jax.profiler.stop_trace()
    log(f"window {span:.3f}s: {len(w.flushes)} flushes "
        f"({len(w.flushes) / span:.3f}/s), {w.images} images, "
        f"{w.failed} failed")
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    pd = trace_reduce.load(path)
    red = trace_reduce.reduce(pd, families=cfg["kernel_families"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    flushes = [dataclasses.asdict(f) for f in w.flushes]
    ph, why = analyse(pd, flushes)
    if ph is None:
        log(f"phases: no match: {why}")
        return 1
    ctx = run.LayerContext(trace=red, spans=[], flushes=flushes,
                           images=w.images, cfg=cfg, reference=ready.refmod,
                           device_kind=ready.device["kind"])
    out = report(ph, red, runtime(ctx))
    for k, v in out.items():
        log(f"{k}: {v}")
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(dict(out, workload=args.workload, seed=args.seed,
                           rows=ph.rows), f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
