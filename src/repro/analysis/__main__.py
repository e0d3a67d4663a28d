"""CLI for the merged static-analysis report::

    PYTHONPATH=src python -m repro.analysis [--write|--check|--json]
                                            [--no-sharded]

``--check`` (the CI analysis job) fails on hard invariant violations
(packedness escapes, over-budget launches, lint/sharding violations)
AND on any drift against ``experiments/ANALYSIS_baseline.json``;
``--write`` regenerates the baseline after an intentional change.

The sharding cells need 8 devices: like ``telemetry/probes.py``, the
CLI re-execs itself with ``REPRO_ANALYSIS_FORCE_DEVICES`` set so the
XLA host-device override below lands before jax's first import.
"""
from __future__ import annotations

import os
import sys

# ``python -m repro.analysis`` imports the package __init__ (and so
# jax) BEFORE this module runs — but jax only reads XLA_FLAGS at lazy
# backend initialization, which nothing in the import chain triggers,
# so setting the flag here still lands in the fresh child process.
if os.environ.get("REPRO_ANALYSIS_FORCE_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        " --xla_force_host_platform_device_count=" +
        os.environ["REPRO_ANALYSIS_FORCE_DEVICES"])

import argparse
import json
import subprocess

from repro.analysis import report as R


def _respawn_with_devices(argv: list[str]) -> int:
    env = dict(os.environ)
    env["REPRO_ANALYSIS_FORCE_DEVICES"] = str(R.SHARDED_DEVICES)
    env.pop("XLA_FLAGS", None)          # the child derives its own
    env["JAX_PLATFORMS"] = "cpu"        # forced host devices; never the chip
    env["PYTHONPATH"] = (os.path.join(R.repo_root(), "src") + os.pathsep +
                         env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis", *argv],
        env=env, cwd=R.repo_root())
    return proc.returncode


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="merged static-analysis report (see docs/analysis.md)")
    ap.add_argument("--write", action="store_true",
                    help="regenerate the committed baseline")
    ap.add_argument("--check", action="store_true",
                    help="verify invariants + diff against the baseline; "
                         "exit 1 on any violation or drift")
    ap.add_argument("--json", action="store_true",
                    help="print the full report as JSON")
    ap.add_argument("--no-sharded", action="store_true",
                    help="skip the sharding cells (no 8-device need)")
    ap.add_argument("--baseline",
                    default=os.path.join(R.repo_root(), R.BASELINE_PATH))
    args = ap.parse_args(argv)

    sharded = not args.no_sharded
    if sharded:
        import jax
        if len(jax.devices()) < R.SHARDED_DEVICES and \
                not os.environ.get("REPRO_ANALYSIS_FORCE_DEVICES"):
            return _respawn_with_devices(argv)

    report = R.merged_report(sharded=sharded)
    if args.json:
        print(json.dumps(report, indent=1))
    if args.write:
        os.makedirs(os.path.dirname(args.baseline), exist_ok=True)
        with open(args.baseline, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        print(f"wrote {len(report['cells'])} analysis cells -> "
              f"{args.baseline}")
    if args.check:
        bad = R.report_ok(report)
        if bad:
            print(f"ANALYSIS VIOLATIONS ({len(bad)}):")
            for line in bad:
                print(f"  {line}")
            return 1
        with open(args.baseline) as f:
            baseline = json.load(f)
        if args.no_sharded:                 # compare only what we ran
            baseline = {"schema": baseline["schema"],
                        "cells": {k: v
                                  for k, v in baseline["cells"].items()
                                  if k in report["cells"]}}
        drift = R.diff_reports(baseline, report)
        if drift:
            print(f"ANALYSIS DRIFT vs {args.baseline} "
                  f"({len(drift)} differences):")
            for line in drift:
                print(f"  {line}")
            print("If intentional, regenerate: "
                  "PYTHONPATH=src python -m repro.analysis --write")
            return 1
        print(f"analysis clean, matches baseline "
              f"({len(report['cells'])} cells)")
    if not (args.json or args.write or args.check):
        for name, cell in report["cells"].items():
            if name.startswith("packedness/"):
                print(f"{name}: {cell['launch_count']} launches, "
                      f"max_live_unpacked={cell['max_live_unpacked_bytes']}B"
                      f" escapes={len(cell['escapes'])}")
            elif name.startswith("vmem/"):
                worst = max(cell, key=lambda c: c["bytes"], default=None)
                if worst:
                    print(f"{name}: {len(cell)} launches, worst "
                          f"{worst['kernel']} {worst['bytes']}B "
                          f"fits={worst['fits']}")
            elif name == "lint":
                print(f"lint: {len(cell['violations'])} violation(s)")
            else:
                print(f"{name}: kinds={cell['kinds']} "
                      f"violations={len(cell['violations'])}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
