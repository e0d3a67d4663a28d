#!/usr/bin/env python3
"""The knee of an open-loop mix: the highest Poisson rate a
configuration's server sustains within a tail-latency limit, found by
one sweep, before a cell at a share of it enters ``BENCHMARK.json``.

    python bench/knee.py --config bcnn --traffic serve-knee --start 1200

The search of MLPerf Inference's Server scenario (Reddi et al., 2020,
arXiv:1911.02549): single-sample queries arrive as a Poisson process,
and the result is the highest rate at which the tail latency meets its
bound.  In one process: one server warmed with the configuration and
the mix's server settings, then windows of the mix, as long as the
benchmark's runs, at rates from ``--start`` upward by factors of 1.25,
each on 3 seeds, until a rate fails; then once more halfway between the
last rate that held and the first that failed.

A window holds when it completes every request (``failed`` 0) with a
p95 latency of at most 25 ms.  A rate holds when every seed's window
holds, or holds on a second run of the same seed: a stall of the
process that one window meets and its rerun does not is not load.  The
knee is the highest rate that held.

The benchmark's own runs never run this.  Prints one JSON line per
window and the knee last; needs the chip like ``run.py``.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import gc
import json
import os
import sys
import types
from typing import Callable

import numpy as np

import loadgen
import run

LIMIT_MS = 25.0
FACTOR = 1.25
SEEDS = [2**31 + 211 + 7919 * s for s in range(3)]


def load(config: str, traffic: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration, traffic mix), as ``run.load_cell``
    gives them: the configuration named in ``BENCHMARK.json`` and
    ``bench/traffic/<traffic>.json``, as one chip's cell of both."""
    bench = run._json(os.path.join(run.ROOT, "BENCHMARK.json"))
    entry = {c["name"]: c for c in bench["configs"]}[config]
    cfg = run._json(os.path.join(run.ROOT, entry["file"]))
    mix = run._json(os.path.join(run.BENCH, "traffic", traffic + ".json"))
    return bench, {"name": f"{config}.{traffic}", "chips": 1}, cfg, mix


def probe(srv, mix: dict, shape: tuple[int, ...], rate: float,
          seconds: float, seed: int) -> dict:
    """One window of ``mix`` at ``rate`` through the warmed server."""
    mix = dict(mix, rate_per_s=rate)
    traffic = loadgen.make(mix, seconds, seed, shape)
    w = run.DRIVERS[traffic.arrivals](srv, traffic, mix, seconds)
    e2e = run.end_to_end(w, 0.0) if w.latencies_s else {}
    late = [x for _, x in w.lateness_s]
    outside = run.lateness_outside_flushes(w)
    flushes = [dataclasses.asdict(f) for f in w.flushes]
    padded = run.metric_reader("server.padded_share").read(
        types.SimpleNamespace(flushes=flushes))
    return {"rate_per_s": rate, "seed": seed, "attempted": w.attempted,
            "failed": w.failed,
            "served_per_s": w.images / max(seconds, w.t1 - w.t0),
            "p50_ms": e2e.get("latency_p50_ms", float("inf")),
            "p95_ms": e2e.get("latency_p95_ms", float("inf")),
            "p99_ms": (run.percentile(w.latencies_s, 99) * 1e3
                       if w.latencies_s else float("inf")),
            "lateness_p99_ms": run.percentile(late, 99) * 1e3,
            "lateness_outside_flushes_p99_ms":
                run.percentile(outside, 99) * 1e3 if outside else 0.0,
            "flushes": len(flushes),
            "rows_per_flush": sum(f["batch"] for f in flushes)
                              / max(1, len(flushes)),
            "flushes_by_bucket": dict(sorted(collections.Counter(
                f["bucket"] for f in flushes).items())),
            "padded_share": padded}


def holds(row: dict, limit_ms: float) -> bool:
    return row["failed"] == 0 and row["p95_ms"] <= limit_ms


def sweep(measure: Callable[[float, int], dict], *, start: float,
          seeds: list[int], limit_ms: float = LIMIT_MS,
          max_rates: int = 30) -> tuple[list[dict], float | None]:
    """Rows of every window ``measure(rate, seed)`` ran, and the knee
    (None where ``start`` already fails).  A seed that fails runs once
    more; a rate stops at its first seed that fails twice.  With no
    failing rate within ``max_rates`` there is nothing to bisect and the
    knee is the last rate."""
    rows: list[dict] = []

    def window(rate: float, seed: int) -> bool:
        rows.append(measure(rate, seed))
        print(json.dumps(rows[-1]), flush=True)
        return holds(rows[-1], limit_ms)

    def all_hold(rate: float) -> bool:
        return all(window(rate, s) or window(rate, s) for s in seeds)

    rate, good = start, None
    for _ in range(max_rates):
        if not all_hold(rate):
            break
        good, rate = rate, rate * FACTOR
    else:
        return rows, good
    if good is None:
        return rows, None
    mid = (good + rate) / 2
    return rows, mid if all_hold(mid) else good


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--start", type=float, required=True)
    args = ap.parse_args(argv)

    bench, cell, cfg, mix = load(args.config, args.traffic)
    seconds = bench["run_seconds"]
    ready = run.setup(cell, cfg, mix)
    gc.collect()
    gc.freeze()
    shape = tuple(cfg["input_shape"])
    rows, knee = sweep(
        lambda rate, seed: probe(ready.srv, mix, shape, rate, seconds, seed),
        start=args.start, seeds=SEEDS)
    by_rate: dict[float, list[dict]] = {}
    for r in rows:
        by_rate.setdefault(r["rate_per_s"], []).append(r)
    table = [{"rate_per_s": rate, "windows": len(rs),
              "failed": sum(r["failed"] for r in rs),
              **{k: float(np.max([r[k] for r in rs]))
                 for k in ("p50_ms", "p95_ms", "p99_ms",
                           "lateness_p99_ms")},
              "served_per_s": float(np.min([r["served_per_s"] for r in rs])),
              **{k: float(np.mean([r[k] for r in rs]))
                 for k in ("rows_per_flush", "padded_share")}}
             for rate, rs in sorted(by_rate.items())]
    print(json.dumps({"cell": cell["name"], "limit_p95_ms": LIMIT_MS,
                      "seconds": seconds, "table": table,
                      "knee_per_s": knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
