"""Static cost probes: standing regression gates on what a forward
*lowers to*, independent of wall-clock noise.

Each probe cell traces a registered model config and records only
machine-independent facts:

* kernel-launch count + per-launch kernel name and grid shape
  (``analysis.pallas_launches`` — the per-PR "traces to exactly 1
  pallas_call" asserts, turned into a committed baseline);
* the GEMV-vs-GEMM route ``kernels.ops.dispatch_batch`` picks for the
  cell's batch;
* the largest HBM intermediate (bytes + shape) — the fused-epilogue
  contract that packed activations never unpack between stages;
* for sharded cells: per-device collective wire bytes and kinds from
  the compiled HLO (``analysis.collectives.analyze_hlo``) on a
  forced-8-CPU (4, 2) mesh — all-gather-only, byte-stable.

This module is a thin consumer of the static-analysis subsystem
(``repro.analysis`` — see ``docs/analysis.md``); the deeper invariants
(packedness dataflow, VMEM budgets, lint) are gated separately by
``python -m repro.analysis --check``.

The canonical cells cover the shared demo configs
(``models.cnn.demo_model(smoke=True)`` — the same shapes the serving
CLI and bench use) at serving-relevant batches.  CI runs

    PYTHONPATH=src python -m repro.telemetry.probes --check

and fails on ANY drift against ``experiments/PROBES_baseline.json``;
after an intentional kernel/grid/collective change, regenerate with
``--write`` and commit the diff (see ``docs/observability.md``).

Importing this module never mutates the environment.  The sharded
cells need 8 devices: ``main()`` re-execs itself in a subprocess with
``REPRO_PROBES_FORCE_DEVICES=8`` when the current process has fewer
(the env knob below must act before jax's first import, which is only
guaranteed in the fresh process).
"""
from __future__ import annotations

import os
import sys

if os.environ.get("REPRO_PROBES_FORCE_DEVICES") and "jax" not in sys.modules:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        " --xla_force_host_platform_device_count=" +
        os.environ["REPRO_PROBES_FORCE_DEVICES"])

import argparse
import json
import subprocess

import numpy as np

SHARDED_MESH = (4, 2)
SHARDED_DEVICES = SHARDED_MESH[0] * SHARDED_MESH[1]


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))


BASELINE_PATH = os.path.join("experiments", "PROBES_baseline.json")


# ---------------------------------------------------------------------------
# probe cells
# ---------------------------------------------------------------------------

def probe_forward(packed, batch: int, *, backend: str = "pallas",
                  dense_stack: str = "auto") -> dict:
    """Static cost report for one packed forward at one batch size.

    Pure tracing — no kernel executes (``jax.make_jaxpr``), so the
    pallas backend is cheap to probe even off-TPU.
    """
    from repro.analysis import max_intermediate_bytes, pallas_launches
    from repro.kernels import ops as kops
    from repro.models import cnn

    fwd = cnn.make_packed_forward(packed, backend=backend,
                                  dense_stack=dense_stack)
    x = np.zeros((batch, *cnn.packed_input_shape(packed)), np.uint8)
    launches = pallas_launches(lambda a: fwd(a), x)
    nbytes, shape = max_intermediate_bytes(lambda a: fwd(a), x)
    return {
        "kind": cnn.packed_kind(packed), "batch": batch, "backend": backend,
        "launch_count": len(launches),
        "launches": [{"kernel": ln.kernel, "grid": list(ln.grid)}
                     for ln in launches],
        "route": kops.dispatch_batch(batch,
                                     cnn.packed_dense_kw_words(packed)),
        "max_intermediate_bytes": int(nbytes),
        "max_intermediate_shape": list(shape),
    }


def probe_sharded(packed, batch: int, *,
                  mesh_shape: tuple[int, int] = SHARDED_MESH) -> dict:
    """Collective-traffic report for one packed forward on a (data,
    model) mesh: wire bytes + collective kinds from the compiled HLO,
    plus the per-stage shard plan.  Requires ``prod(mesh_shape)``
    devices (CI forces host devices; see module docstring)."""
    from repro.analysis.collectives import analyze_hlo
    from repro.distributed import sharding as SH
    from repro.launch.mesh import make_mesh
    from repro.models import cnn

    mesh = make_mesh(mesh_shape, ("data", "model"))
    fwd = SH.make_sharded_forward(packed, mesh, backend="jnp")
    x = np.zeros((batch, *cnn.packed_input_shape(packed)), np.uint8)
    kinds, by_kind = analyze_hlo(fwd.lower(x).compile().as_text())
    return {
        "kind": fwd.kind, "mesh": list(mesh_shape), "batch": batch,
        "shard_plan": {k: list(v) for k, v in fwd.shard_plan.items()},
        "collective_bytes": float(by_kind.get("total", 0.0)),
        "collective_kinds": kinds,
    }


def _demo_packed(kind: str):
    from repro.analysis.report import demo_packed

    return demo_packed(kind)


def standard_report(*, sharded: bool = True) -> dict:
    """The committed probe cells: the BMLP and BCNN demo networks and the
    reduced gemma2 binary LM at the GEMV (≤ 8) and GEMM (> 8) serving
    batches, the ReActNet demo network at batch 8,
    plus the (4, 2)-mesh collective cells (bmlp/bcnn only — the
    sharding rules don't cover the transformer workload).  Keys are
    stable — they ARE the baseline diff surface."""
    cells = {}
    for kind in ("bmlp", "bcnn", "transformer"):
        packed = _demo_packed(kind)
        for batch in (1, 8, 32):
            cells[f"{kind}/b{batch}"] = probe_forward(packed, batch)
        if sharded and kind != "transformer":
            cells[f"sharded/{kind}_{SHARDED_MESH[0]}x{SHARDED_MESH[1]}"] = \
                probe_sharded(packed, batch=8)
    # ReActNet has no GEMV/GEMM route to switch: one batch covers it.
    cells["reactnet/b8"] = probe_forward(_demo_packed("reactnet"), 8)
    return {"schema": 1, "cells": cells}


# ---------------------------------------------------------------------------
# baseline diff (shared with the analysis baseline gate)
# ---------------------------------------------------------------------------

from repro.analysis.report import diff_reports  # noqa: E402  (re-export)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _respawn_with_devices(argv: list[str]) -> int:
    env = dict(os.environ)
    env["REPRO_PROBES_FORCE_DEVICES"] = str(SHARDED_DEVICES)
    env.pop("XLA_FLAGS", None)          # the child derives its own
    env["JAX_PLATFORMS"] = "cpu"        # forced host devices; never the chip
    env["PYTHONPATH"] = (os.path.join(repo_root(), "src") + os.pathsep +
                         env.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, "-m", "repro.telemetry.probes", *argv],
        env=env, cwd=repo_root())
    return proc.returncode


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--write", action="store_true",
                    help="regenerate the committed baseline")
    ap.add_argument("--check", action="store_true",
                    help="diff against the baseline; exit 1 on drift")
    ap.add_argument("--json", action="store_true",
                    help="print the full report as JSON")
    ap.add_argument("--no-sharded", action="store_true",
                    help="skip the collective cells (no 8-device need)")
    ap.add_argument("--baseline",
                    default=os.path.join(repo_root(), BASELINE_PATH))
    args = ap.parse_args(argv)

    sharded = not args.no_sharded
    if sharded:
        import jax
        if len(jax.devices()) < SHARDED_DEVICES and \
                not os.environ.get("REPRO_PROBES_FORCE_DEVICES"):
            return _respawn_with_devices(argv)

    report = standard_report(sharded=sharded)
    if args.json:
        print(json.dumps(report, indent=1))
    if args.write:
        os.makedirs(os.path.dirname(args.baseline), exist_ok=True)
        with open(args.baseline, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        print(f"wrote {len(report['cells'])} probe cells -> "
              f"{args.baseline}")
    if args.check:
        with open(args.baseline) as f:
            baseline = json.load(f)
        if args.no_sharded:                 # compare only what we probed
            baseline = {"schema": baseline["schema"],
                        "cells": {k: v
                                  for k, v in baseline["cells"].items()
                                  if k in report["cells"]}}
        drift = diff_reports(baseline, report)
        if drift:
            print(f"PROBE DRIFT vs {args.baseline} "
                  f"({len(drift)} differences):")
            for line in drift:
                print(f"  {line}")
            print("If intentional, regenerate: "
                  "PYTHONPATH=src python -m repro.telemetry.probes --write")
            return 1
        print(f"probes match baseline ({len(report['cells'])} cells)")
    if not (args.json or args.write or args.check):
        for name, cell in report["cells"].items():
            if "launch_count" in cell:
                print(f"{name}: {cell['launch_count']} launches "
                      f"route={cell['route']} "
                      f"max_intermediate={cell['max_intermediate_bytes']}B "
                      f"{cell['max_intermediate_shape']}")
            else:
                print(f"{name}: collectives={cell['collective_kinds']} "
                      f"{cell['collective_bytes']:.0f}B "
                      f"plan={cell['shard_plan']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
