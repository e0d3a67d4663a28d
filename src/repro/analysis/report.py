"""Merged static-analysis report: every pass over the canonical demo
models, keyed like ``PROBES_baseline.json`` and drift-gated in CI.

Cells (stable keys — they ARE the baseline diff surface):

* ``packedness/{bmlp,bcnn,transformer,reactnet}`` — the packedness dataflow
  verdict for each packed forward at the serving batch (8): HBM
  crossing classification, max live unpacked bytes, escapes (must stay
  empty);
* ``vmem/{kind}_b8`` — the traced per-launch VMEM estimates (kernel,
  grid, bytes, fits) for the same forwards;
* ``lint`` — the repo lint pass over ``src/`` (violations must stay
  empty);
* ``sharding/{bmlp,bcnn}_4x2`` — the collective-rule verdict for the
  model-parallel mesh the probes exercise (all-gather-only).

CI runs ``PYTHONPATH=src python -m repro.analysis --check`` and fails
on ANY drift against ``experiments/ANALYSIS_baseline.json``; after an
intentional kernel/model change, regenerate with ``--write`` and
commit the diff (see ``docs/analysis.md``).  The sharding cells need 8
devices — the CLI re-execs itself with forced host devices, same
pattern as ``telemetry/probes.py``.

``diff_reports`` lives here (moved from ``telemetry/probes.py``, which
now re-exports it): one structural differ serves both baselines.
"""
from __future__ import annotations

import os
from typing import Any

BASELINE_PATH = os.path.join("experiments", "ANALYSIS_baseline.json")
SHARDED_MESH = (4, 2)
SHARDED_DEVICES = SHARDED_MESH[0] * SHARDED_MESH[1]
REPORT_BATCH = 8


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))))


def demo_packed(kind: str) -> Any:
    """The shared demo configs every standing gate probes: the three
    smoke-sized demo image networks and the reduced gemma2 binary LM
    (the same builders ``telemetry/probes.py`` records baselines
    for)."""
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


def packedness_cell(kind: str, *, batch: int = REPORT_BATCH) -> dict:
    """Packedness verdict for one demo forward (pallas backend)."""
    from repro.analysis.packedness import analyze_packedness, model_policy

    fn, x = _forward_and_input(demo_packed(kind), batch)
    return analyze_packedness(fn, x, policy=model_policy(kind)).to_json()


def vmem_cell(kind: str, *, batch: int = REPORT_BATCH) -> list[dict]:
    """Traced per-launch VMEM estimates for one demo forward."""
    from repro.analysis.vmem import estimate_forward

    fn, x = _forward_and_input(demo_packed(kind), batch)
    return [est.to_json() for est in estimate_forward(fn, x)]


def lint_cell(root: str | None = None) -> dict:
    """The repo lint pass over ``src/`` as a report cell."""
    from repro.analysis.lint import lint_paths

    root = os.path.join(repo_root(), "src") if root is None else root
    return {"violations": [str(v).replace(repo_root() + os.sep, "")
                           for v in lint_paths([root])]}


def sharding_cell(kind: str, *,
                  mesh_shape: tuple[int, int] = SHARDED_MESH) -> dict:
    """Collective-rule verdict for one demo forward on a (data, model)
    mesh — the compiled-HLO path ``probe_sharded`` records bytes for,
    run through ``analysis.collectives.check_mesh``.  Requires
    ``prod(mesh_shape)`` devices."""
    import numpy as np

    from repro.analysis.collectives import check_mesh
    from repro.distributed import sharding as SH
    from repro.launch.mesh import make_mesh
    from repro.models import cnn

    packed = demo_packed(kind)
    mesh = make_mesh(mesh_shape, ("data", "model"))
    fwd = SH.make_sharded_forward(packed, mesh, backend="jnp")
    x = np.zeros((REPORT_BATCH, *cnn.packed_input_shape(packed)), np.uint8)
    hlo = fwd.lower(x).compile().as_text()
    return check_mesh(hlo, mesh_shape).to_json()


def merged_report(*, sharded: bool = True) -> dict:
    """All four passes over the canonical cells (see module docstring)."""
    cells: dict[str, Any] = {}
    for kind in ("bmlp", "bcnn", "transformer", "reactnet"):
        cells[f"packedness/{kind}"] = packedness_cell(kind)
        cells[f"vmem/{kind}_b{REPORT_BATCH}"] = vmem_cell(kind)
    cells["lint"] = lint_cell()
    if sharded:
        for kind in ("bmlp", "bcnn"):
            cells[f"sharding/{kind}_"
                  f"{SHARDED_MESH[0]}x{SHARDED_MESH[1]}"] = \
                sharding_cell(kind)
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
    """Recursive structural diff, one human-readable line per drift.

    Shared by this module's ``--check`` gate and the telemetry probes'
    (``telemetry/probes.py`` re-exports it).
    """
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
