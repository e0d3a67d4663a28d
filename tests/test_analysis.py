"""Static-analysis subsystem (``repro.analysis`` — docs/analysis.md):
the four passes must each PASS on the repo's healthy code paths and
CATCH a seeded instance of its target defect — an unpacked HBM escape,
a VMEM over-budget launch, an off-plan collective, an unvalidated
block knob — and the merged report's unsharded cells must match the
committed baseline."""
import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from repro.analysis import (PallasLaunch, count_pallas_calls,
                            estimate_forward, gemm_estimate,
                            pallas_launches, preflight, vmem_budget)
from repro.analysis import vmem as VM
from repro.analysis.collectives import (check_data_parallel, check_mesh,
                                        check_model_parallel)
from repro.analysis.lint import lint_paths, lint_source
from repro.analysis.packedness import analyze_packedness, model_policy
from repro.analysis import report as R
from repro.analysis.report import report_ok
from repro.kernels import ops as kops
from repro.kernels.binary_matmul import (STACK_VMEM_BUDGET,
                                         dense_stack_fits_vmem,
                                         dense_stack_vmem_bytes)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _gemm(a, b):
    return kops.binary_matmul_packed(a, b, k_true=256, backend="pallas")


def _packed(m, n, kw=8):
    return (np.zeros((m, kw), np.uint32), np.zeros((n, kw), np.uint32))


# ---------------------------------------------------------------------------
# graph traversal (shared core)
# ---------------------------------------------------------------------------

def test_pallas_launches_one_gemm():
    a, b = _packed(64, 128)
    launches = pallas_launches(_gemm, a, b)
    assert len(launches) == 1 and isinstance(launches[0], PallasLaunch)
    assert launches[0].kernel == "_gemm_kernel"
    assert len(launches[0].grid) == 3
    assert count_pallas_calls(_gemm, a, b) == 1


# ---------------------------------------------------------------------------
# packedness dataflow pass
# ---------------------------------------------------------------------------

def test_packedness_clean_on_epilogue_bridge():
    # int32 GEMM output bridging into the standalone BN-sign-repack is
    # the sanctioned unpacked crossing — no escape.
    def legal(a, b, tau, flip):
        y = _gemm(a, b)
        return kops.bn_sign_pack(y, tau, flip, backend="pallas")

    a, b = _packed(16, 128)
    tau = np.zeros(128, np.float32)
    flip = np.ones(128, np.float32)
    rep = analyze_packedness(legal, a, b, tau, flip, policy="strict")
    assert rep.complete and not rep.escapes
    assert rep.launch_count == 2
    assert rep.hbm_values.get("unpacked", 0) >= 1   # the bridge itself
    # Peak = the (16, 128) bridge alone: 128 channels are already a
    # whole 32-bit word multiple, so the repack stages no padded copy.
    assert rep.max_live_unpacked_bytes == 16 * 128 * 4
    assert rep.max_unpacked_shape == (16, 128)


def test_packedness_catches_seeded_escape():
    # Host-side re-binarization of a kernel's int32 output, fed back
    # through the generic bitpack kernel: the classic silent leak.
    def leaky(a, b):
        y = _gemm(a, b)
        s = jnp.where(y >= 0, 1.0, -1.0).astype(jnp.float32)
        return kops.bitpack(s, backend="pallas")

    rep = analyze_packedness(leaky, *_packed(16, 128), policy="strict")
    assert rep.escapes, "seeded unpacked HBM escape not flagged"
    esc = rep.escapes[0]
    assert esc.producer == "_gemm_kernel"
    assert esc.consumer == "_bitpack_kernel"
    assert not rep.ok


def test_packedness_float_residual_policy_launders():
    # The binary LM's residual stream is float by design: int -> float
    # ends the taint under 'float-residual' but not under 'strict'.
    def residual(a, b):
        y = _gemm(a, b).astype(jnp.float32)
        return kops.bitpack(y, backend="pallas")

    args = _packed(16, 128)
    assert analyze_packedness(residual, *args, policy="strict").escapes
    rep = analyze_packedness(residual, *args, policy="float-residual")
    assert not rep.escapes and rep.complete
    assert model_policy("transformer") == "float-residual"
    assert model_policy("bcnn") == model_policy("bmlp") == "strict"


def test_packedness_rejects_unknown_policy():
    with pytest.raises(ValueError, match="policy"):
        analyze_packedness(_gemm, *_packed(8, 128), policy="lenient")


# ---------------------------------------------------------------------------
# VMEM preflight pass
# ---------------------------------------------------------------------------

def test_dense_stack_bytes_delegate_exact():
    # The legacy hand-rolled arithmetic and the shared estimator must
    # agree byte-for-byte (the estimator IS the old formula now).
    weights = [np.zeros((128, 25), np.uint32), np.zeros((10, 4), np.uint32)]
    est = VM.dense_stack_estimate([w.shape for w in weights])
    assert dense_stack_vmem_bytes(weights) == est.total == 55712


def test_dense_stack_crossover_pinned():
    # Regression-pin the residency crossover at the 8 MiB stack budget:
    # a (4096, 128)-word stage fits, an (8192, 256) stage does not.
    fits = [np.zeros((4096, 128), np.uint32)]
    over = [np.zeros((8192, 256), np.uint32)]
    assert dense_stack_fits_vmem(fits)
    assert not dense_stack_fits_vmem(over)
    assert dense_stack_vmem_bytes(fits) <= STACK_VMEM_BUDGET
    assert dense_stack_vmem_bytes(over) > STACK_VMEM_BUDGET


def test_preflight_raises_with_breakdown():
    est = gemm_estimate(1024, 8192, 4096, block_n=1024, block_kw=4096)
    assert not est.fits()
    with pytest.raises(VM.VmemBudgetError) as ei:
        preflight(est)
    msg = str(ei.value)
    assert "b_block" in msg and "REPRO_VMEM_BUDGET_BYTES" in msg


def test_ops_preflight_catches_seeded_over_budget(monkeypatch):
    # The dispatcher must refuse the launch BEFORE tracing when the
    # budget (env-overridable) is exceeded.
    monkeypatch.setenv("REPRO_VMEM_BUDGET_BYTES", "4096")
    assert vmem_budget() == 4096
    with pytest.raises(VM.VmemBudgetError):
        kops.bitpack(np.zeros((256, 512), np.float32), backend="pallas")
    monkeypatch.delenv("REPRO_VMEM_BUDGET_BYTES")
    # Same call is fine under the default 16 MiB budget.
    out = kops.bitpack(np.zeros((256, 512), np.float32), backend="pallas")
    assert out.shape == (256, 16)


@pytest.mark.parametrize("m", [1, 4, 16, 256])
def test_bitplane_dense_estimate_matches_traced_launch(m):
    """The closed-form bit-plane estimate stages exactly the blocks the
    traced launch does (bytes and buffers), on the same grid, and adds
    the accumulator and loop-step transients on top."""
    from repro.core import binary_layers as L
    packed = L.pack_bitplane_dense(
        {"w": np.ones((4096, 784), np.float32)}, nbits=8)
    x = np.zeros((m, 784), np.uint8)
    closed = VM.bitplane_dense_estimate(m, 4096, 25, nbits=8)
    (traced,) = estimate_forward(
        lambda v: kops.bitplane_dense_packed(packed, v, backend="pallas"),
        x)
    assert traced.kernel == "_bitplane_dense_kernel"
    assert closed.grid == traced.grid
    staged = [(t.bytes, t.buffers) for t in closed.terms[:4]]
    assert staged == [(t.bytes, t.buffers) for t in traced.terms]
    assert closed.total > traced.total and closed.fits()


def test_gemm_estimate_tracks_dispatch_route():
    assert gemm_estimate(1, 1000, 64).kernel == "gemv"
    assert gemm_estimate(64, 1000, 64).kernel == "gemm"
    # GEMV pins the activation block (1 buffer), GEMM streams it (2).
    gv = {t.name: t for t in gemm_estimate(1, 1000, 64).terms}
    gm = {t.name: t for t in gemm_estimate(64, 1000, 64).terms}
    assert gv["a_block"].buffers == 1 and gm["a_block"].buffers == 2
    assert "acc_scratch" in gm and "acc_scratch" not in gv


def test_traced_estimator_matches_launch():
    a, b = _packed(64, 128)
    ests = estimate_forward(_gemm, a, b)
    assert len(ests) == 1
    est = ests[0]
    assert est.kernel == "_gemm_kernel" and len(est.grid) == 3
    assert est.fits() and est.total > 0
    assert any(t.name.startswith("scratch") for t in est.terms)
    cell = est.to_json()
    assert cell["bytes"] == est.total and cell["fits"] is True


# ---------------------------------------------------------------------------
# sharding (collectives) pass
# ---------------------------------------------------------------------------

_AG = ('  %ag = u32[8,16]{1,0} all-gather(u32[2,16]{1,0} %p), '
       'replica_groups={{0,1,2,3}}\n')
_AR = ('  %ar = f32[128]{0} all-reduce(f32[128]{0} %x), '
       'to_apply=%add\n')


def test_collectives_model_parallel_allows_all_gather_only():
    rep = check_model_parallel(_AG)
    assert rep.ok and rep.kinds == {"all-gather": 1}
    rep = check_model_parallel(_AG + _AR)
    assert not rep.ok
    assert any("all-reduce" in v for v in rep.violations)
    assert rep.kinds == {"all-gather": 1, "all-reduce": 1}


def test_collectives_data_parallel_must_be_silent():
    assert check_data_parallel("ENTRY %main { ROOT %x = f32[] }").ok
    rep = check_data_parallel(_AG)
    assert not rep.ok and "collective-free" in rep.violations[0]


def test_check_mesh_dispatches_on_model_degree():
    assert not check_mesh(_AG, (8, 1)).ok      # data mesh: any = bad
    assert check_mesh(_AG, (4, 2)).ok          # model mesh: AG fine
    assert not check_mesh(_AR, (4, 2)).ok      # off-plan collective


# ---------------------------------------------------------------------------
# repo lint pass
# ---------------------------------------------------------------------------

def test_lint_repo_clean():
    assert lint_paths([os.path.join(REPO, "src")]) == []


def test_lint_catches_unrouted_backend():
    src = ("def run(x, backend='auto'):\n"
           "    if backend == 'pallas':\n"
           "        return x + 1\n"
           "    return x\n")
    rules = {v.rule for v in lint_source(src, "src/repro/kernels/fake.py")}
    assert "R001" in rules          # backend neither resolved nor forwarded
    assert "R004" in rules          # string-matching backend outside ops.py


def test_lint_catches_unvalidated_knob():
    src = ("def conv(x, *, block_n=128):\n"
           "    return x[:block_n]\n")
    out = lint_source(src, "src/repro/kernels/fake.py")
    assert any(v.rule == "R002" and "block_n" in v.message for v in out)
    # Validated spelling passes.
    good = ("def conv(x, *, block_n=128):\n"
            "    check_block_lanes('block_n', block_n)\n"
            "    return x[:block_n]\n")
    assert not [v for v in lint_source(good, "src/repro/kernels/fake.py")
                if v.rule == "R002"]


def test_lint_catches_hardcoded_interpret():
    src = "def f(x):\n    return pl.pallas_call(k, interpret=True)(x)\n"
    out = lint_source(src, "src/repro/models/fake.py")
    assert any(v.rule == "R003" for v in out)
    # Outside kernels/, R001/R002 don't apply but R003 still does.
    assert not any(v.rule in ("R001", "R002") for v in out)


# ---------------------------------------------------------------------------
# merged report invariants
# ---------------------------------------------------------------------------

def test_report_ok_flags_each_cell_kind():
    report = {"cells": {
        "packedness/bmlp": {"escapes": ["k -> k2: leak"], "complete": True},
        "vmem/bmlp_b8": [{"kernel": "gemm", "grid": [1], "bytes": 99,
                          "fits": False}],
        "lint": {"violations": ["x.py:1: R003 bad"]},
        "sharding/bmlp_4x2": {"violations": ["off-plan"], "kinds": {}},
    }}
    bad = report_ok(report)
    assert len(bad) == 4
    clean = {"cells": {
        "packedness/bmlp": {"escapes": [], "complete": True},
        "vmem/bmlp_b8": [{"kernel": "gemm", "grid": [1], "bytes": 9,
                          "fits": True}],
        "lint": {"violations": []},
        "sharding/bmlp_4x2": {"violations": [], "kinds": {}},
    }}
    assert report_ok(clean) == []


def test_forward_cell_report_shape():
    packed = R.demo_packed("bmlp")
    cell = R.forward_cell(packed, 1)
    assert cell["kind"] == "bmlp" and cell["batch"] == 1
    assert cell["launch_count"] == len(cell["launches"]) > 0
    assert cell["route"] == "gemv"
    assert cell["max_intermediate_bytes"] > 0
    big = R.forward_cell(packed, 32)
    assert big["route"] == "gemm"
    assert json.loads(json.dumps(cell)) == cell


def test_diff_reports_drift():
    base = {"schema": 1, "cells": {"a": {"launch_count": 3,
                                         "launches": [1, 2, 3]}}}
    same = json.loads(json.dumps(base))
    assert R.diff_reports(base, same) == []
    drifted = json.loads(json.dumps(base))
    drifted["cells"]["a"]["launch_count"] = 4
    drifted["cells"]["b"] = {}
    lines = R.diff_reports(base, drifted)
    assert any("launch_count" in l for l in lines)
    assert any("NEW" in l for l in lines)


def test_report_matches_committed_baseline_unsharded():
    """Every unsharded cell of the committed baseline (forward,
    packedness, VMEM, lint) must match a fresh report; the sharding
    cells need 8 devices and are CI's job."""
    with open(os.path.join(R.repo_root(), R.BASELINE_PATH)) as f:
        baseline = json.load(f)
    report = R.merged_report(sharded=False)
    assert report_ok(report) == []
    keep = {k: v for k, v in baseline["cells"].items()
            if not k.startswith("sharding/")}
    drift = R.diff_reports({"schema": baseline["schema"], "cells": keep},
                           report)
    assert not drift, "\n".join(drift)


_FIXED_REPORT = {"schema": 1, "cells": {
    "forward/bmlp_b1": {"launch_count": 4, "route": "gemv"},
    "lint": {"violations": []},
}}


@pytest.mark.parametrize("altered, rc", [(False, 0), (True, 1)])
def test_cli_check_exit_code(tmp_path, monkeypatch, altered, rc):
    """``--check`` exits 0 on a matching baseline and 1 on drift."""
    from repro.analysis import __main__ as cli
    monkeypatch.setattr(R, "merged_report",
                        lambda *, sharded: json.loads(
                            json.dumps(_FIXED_REPORT)))
    baseline = json.loads(json.dumps(_FIXED_REPORT))
    if altered:
        baseline["cells"]["forward/bmlp_b1"]["launch_count"] = 5
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps(baseline))
    assert cli.main(["--check", "--no-sharded",
                     "--baseline", str(path)]) == rc
