"""Merged static-analysis report: every pass over the canonical demo
models, drift-gated against one committed baseline.

Cells (stable keys — they ARE the baseline diff surface):

* ``forward/{kind}_b{batch}`` — what each packed forward lowers to:
  per-launch kernel name and grid, the launch count, the GEMV/GEMM
  route ``kernels.ops.dispatch_batch`` picks for the batch, and the
  largest HBM intermediate (bytes + shape).  bmlp, bcnn and the
  transformer at the GEMV (1, 8) and GEMM (32) serving batches;
  reactnet, which has no route to switch, at batch 8;
* ``packedness/{bmlp,bcnn,transformer,reactnet}`` — the packedness
  dataflow verdict for each packed forward at the serving batch (8):
  HBM crossing classification, max live unpacked bytes, escapes (must
  stay empty);
* ``vmem/{kind}_b8`` — the traced per-launch VMEM estimates (kernel,
  grid, bytes, fits) for the same forwards;
* ``lint`` — the repo lint pass over ``src/`` (violations must stay
  empty);
* ``sharding/{bmlp,bcnn}_4x2`` — one compile of the sharded forward on
  the (4, 2) model-parallel mesh: the collective-rule verdict
  (all-gather-only), collective kinds and wire bytes, and the per-stage
  shard plan.

CI runs ``PYTHONPATH=src python -m repro.analysis --check`` and fails
on ANY drift against ``experiments/ANALYSIS_baseline.json``; after an
intentional kernel/model change, regenerate with ``--write`` and
commit the diff (see ``docs/analysis.md``).  The sharding cells need 8
devices — the CLI re-execs itself with forced host devices.
"""
from __future__ import annotations

import os
from typing import Any

BASELINE_PATH = os.path.join("experiments", "ANALYSIS_baseline.json")
SHARDED_MESH = (4, 2)
SHARDED_DEVICES = SHARDED_MESH[0] * SHARDED_MESH[1]
REPORT_BATCH = 8
FORWARD_BATCHES = (1, 8, 32)


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))


def demo_packed(kind: str) -> Any:
    """The shared demo configs every cell traces: the three smoke-sized
    demo image networks and the reduced gemma2 binary LM."""
    from repro.models import cnn

    if kind == "transformer":
        import jax

        from repro.configs import get_config
        from repro.models import transformer as TF

        cfg = get_config("gemma2-9b", reduced=True)
        params = TF.init_binary_lm(jax.random.PRNGKey(0), cfg)
        return TF.pack_transformer(params, cfg, max_len=8)
    params, spec, kind = cnn.demo_model(kind, smoke=True)
    pack = {"bcnn": cnn.pack_bcnn, "bmlp": cnn.pack_bmlp,
            "reactnet": cnn.R.pack_reactnet}[kind]
    return pack(params, spec)


def _forward_and_input(packed: Any, batch: int):
    import numpy as np

    from repro.models import cnn

    fwd = cnn.make_packed_forward(packed, backend="pallas")
    x = np.zeros((batch, *cnn.packed_input_shape(packed)), np.uint8)
    return (lambda a: fwd(a)), x


def forward_cell(packed: Any, batch: int) -> dict:
    """Launch inventory, route and largest HBM intermediate of one
    packed forward at one batch.  Pure tracing (``jax.make_jaxpr``):
    no kernel executes."""
    from repro.analysis.graph import max_intermediate_bytes, pallas_launches
    from repro.kernels import ops as kops
    from repro.models import cnn

    fn, x = _forward_and_input(packed, batch)
    launches = pallas_launches(fn, x)
    nbytes, shape = max_intermediate_bytes(fn, x)
    return {
        "kind": cnn.packed_kind(packed), "batch": batch, "backend": "pallas",
        "launch_count": len(launches),
        "launches": [{"kernel": ln.kernel, "grid": list(ln.grid)}
                     for ln in launches],
        "route": kops.dispatch_batch(batch,
                                     cnn.packed_dense_kw_words(packed)),
        "max_intermediate_bytes": int(nbytes),
        "max_intermediate_shape": list(shape),
    }


def packedness_cell(packed: Any, *, batch: int = REPORT_BATCH) -> dict:
    """Packedness verdict for one demo forward (pallas backend)."""
    from repro.analysis.packedness import analyze_packedness, model_policy
    from repro.models import cnn

    fn, x = _forward_and_input(packed, batch)
    return analyze_packedness(
        fn, x, policy=model_policy(cnn.packed_kind(packed))).to_json()


def vmem_cell(packed: Any, *, batch: int = REPORT_BATCH) -> list[dict]:
    """Traced per-launch VMEM estimates for one demo forward."""
    from repro.analysis.vmem import estimate_forward

    fn, x = _forward_and_input(packed, batch)
    return [est.to_json() for est in estimate_forward(fn, x)]


def lint_cell(root: str | None = None) -> dict:
    """The repo lint pass over ``src/`` as a report cell."""
    from repro.analysis.lint import lint_paths

    root = os.path.join(repo_root(), "src") if root is None else root
    return {"violations": [str(v).replace(repo_root() + os.sep, "")
                           for v in lint_paths([root])]}


def sharding_cell(packed: Any, *,
                  mesh_shape: tuple[int, int] = SHARDED_MESH) -> dict:
    """One compile of a demo forward on a (data, model) mesh: the
    ``analysis.collectives.check_mesh`` verdict with its collective
    kinds and wire bytes, plus the per-stage shard plan.  Requires
    ``prod(mesh_shape)`` devices."""
    import numpy as np

    from repro.analysis.collectives import check_mesh
    from repro.distributed import sharding as SH
    from repro.launch.mesh import make_mesh
    from repro.models import cnn

    mesh = make_mesh(mesh_shape, ("data", "model"))
    fwd = SH.make_sharded_forward(packed, mesh, backend="jnp")
    x = np.zeros((REPORT_BATCH, *cnn.packed_input_shape(packed)), np.uint8)
    hlo = fwd.lower(x).compile().as_text()
    return {**check_mesh(hlo, mesh_shape).to_json(),
            "kind": fwd.kind, "mesh": list(mesh_shape), "batch": REPORT_BATCH,
            "shard_plan": {k: list(v) for k, v in fwd.shard_plan.items()}}


def merged_report(*, sharded: bool = True) -> dict:
    """All passes over the canonical cells (see module docstring)."""
    cells: dict[str, Any] = {}
    for kind in ("bmlp", "bcnn", "transformer", "reactnet"):
        packed = demo_packed(kind)
        # ReActNet has no GEMV/GEMM route to switch: one batch covers it.
        batches = (REPORT_BATCH,) if kind == "reactnet" else FORWARD_BATCHES
        for batch in batches:
            cells[f"forward/{kind}_b{batch}"] = forward_cell(packed, batch)
        cells[f"packedness/{kind}"] = packedness_cell(packed)
        cells[f"vmem/{kind}_b{REPORT_BATCH}"] = vmem_cell(packed)
        if sharded and kind in ("bmlp", "bcnn"):
            cells[f"sharding/{kind}_"
                  f"{SHARDED_MESH[0]}x{SHARDED_MESH[1]}"] = \
                sharding_cell(packed)
    cells["lint"] = lint_cell()
    return {"schema": 1, "cells": cells}


def report_ok(report: dict) -> list[str]:
    """Hard invariant failures in a merged report (independent of any
    baseline): packedness escapes, incomplete dataflow coverage,
    over-budget launches, lint or sharding violations."""
    bad: list[str] = []
    for key, cell in report["cells"].items():
        if key.startswith("packedness/"):
            bad += [f"{key}: {e}" for e in cell["escapes"]]
            if not cell["complete"]:
                bad.append(f"{key}: dataflow did not cover every launch")
        elif key.startswith("vmem/"):
            bad += [f"{key}: {c['kernel']} grid={c['grid']} "
                    f"needs {c['bytes']} B VMEM (over budget)"
                    for c in cell if not c["fits"]]
        elif key == "lint":
            bad += [f"lint: {v}" for v in cell["violations"]]
        elif key.startswith("sharding/"):
            bad += [f"{key}: {v}" for v in cell["violations"]]
    return bad


def diff_reports(baseline: Any, current: Any, path: str = "") -> list[str]:
    """Recursive structural diff, one human-readable line per drift."""
    out: list[str] = []
    if isinstance(baseline, dict) and isinstance(current, dict):
        for k in sorted(set(baseline) | set(current)):
            p = f"{path}/{k}" if path else str(k)
            if k not in baseline:
                out.append(f"{p}: NEW (not in baseline)")
            elif k not in current:
                out.append(f"{p}: MISSING (in baseline only)")
            else:
                out += diff_reports(baseline[k], current[k], p)
        return out
    if isinstance(baseline, list) and isinstance(current, list):
        if len(baseline) != len(current):
            out.append(f"{path}: length {len(baseline)} -> {len(current)}")
        for i, (b, c) in enumerate(zip(baseline, current)):
            out += diff_reports(b, c, f"{path}[{i}]")
        return out
    if baseline != current:
        out.append(f"{path}: {baseline!r} -> {current!r}")
    return out
