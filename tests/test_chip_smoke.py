"""``chip_smoke.py``: it refuses to run off a TPU, and the checks it makes
on the chip hold here on the CPU (Pallas in interpret mode) at reduced
widths."""
from __future__ import annotations

import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.models import cnn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")

REDUCED = {
    "bmlp": cnn.BMLPSpec(sizes=(96, 128, 64, 10)),
    "bcnn": cnn.BCNNSpec(input_hw=(8, 8), c_in=3,
                         stages=(cnn.ConvStage(32),
                                 cnn.ConvStage(64, pool=True)),
                         dense=(96, 10)),
}


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, SCRIPT], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


@pytest.mark.parametrize("kind", sorted(REDUCED))
def test_check_network_passes_on_cpu(smoke, kind):
    rep = smoke.check_network(kind, REDUCED[kind], seed=3)
    assert rep["routes"] == ["gemm", "gemv"]
    assert sorted(rep["compile_s"]) == [1, smoke.BURST]
    assert len(rep["single_ms"]) == smoke.SINGLES
    assert len(rep["burst_ms"]) == smoke.BURST
    assert rep["max_abs_diff"] <= 1e-4


def test_check_mesh_passes_on_four_host_devices():
    # The device count is fixed when JAX starts, so the 2x2 mesh check
    # runs in a child with four forced host devices.
    code = ("import chip_smoke; from repro.models import cnn; "
            "spec = cnn.BCNNSpec(input_hw=(8, 8), c_in=3, stages=("
            "cnn.ConvStage(32), cnn.ConvStage(64, pool=True)), "
            "dense=(128, 10)); "
            "rep = chip_smoke.check_mesh(spec, seed=5); "
            "print('split', rep['split'], rep['routes'])")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    # 64 output channels split into whole words over model=2 (the conv
    # weights, correction, tau, flip and pool mask), as does the 128-wide
    # hidden dense layer (weights, tau, flip); 32 do not.
    assert "split 8 ['gemm', 'gemv']" in proc.stdout


def test_compare_to_reference_rejects_wrong_logits(smoke):
    rng = np.random.default_rng(0)
    ref = rng.normal(size=(6, 10)).astype(np.float32)
    assert smoke.compare_to_reference(ref.copy(), ref) == 0.0
    off = ref.copy()
    off[2] *= 1 + 1e-5                 # same argmax, outside rtol
    with pytest.raises(smoke.SmokeFailure, match="logits differ"):
        smoke.compare_to_reference(off, ref)
    swapped = ref.copy()
    top = ref[4].argmax()
    swapped[4, top] = ref[4].min() - 1.0
    with pytest.raises(smoke.SmokeFailure, match="argmax differs on rows"):
        smoke.compare_to_reference(swapped, ref)
    bad = ref.copy()
    bad[0, 0] = np.nan
    with pytest.raises(smoke.SmokeFailure, match="finite"):
        smoke.compare_to_reference(bad, ref)
