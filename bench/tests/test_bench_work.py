"""Work counts, peaks and the program spec of the benchmark's
configurations."""
import json
import os

import pytest

import _paths  # noqa: F401
import peaks
from reference import bcnn, bmlp


def _cfg(name):
    with open(os.path.join(_paths.BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_macs_per_image():
    assert bmlp.macs_per_image(_cfg("bmlp")) == 36_806_656
    assert bcnn.macs_per_image(_cfg("bcnn")) == 616_966_144


@pytest.mark.parametrize("name,ref", [("bmlp", bmlp), ("bcnn", bcnn)])
def test_work_matches_config(name, ref):
    cfg = _cfg(name)
    work = ref.work(cfg, 3)
    assert sorted(work) == sorted(cfg["kernel_families"])
    assert sorted(work) == sorted(cfg["macs_per_image"])
    for fam, (ops, nbytes) in work.items():
        assert ops == 2 * 3 * cfg["macs_per_image"][fam]
        assert nbytes > 0
    assert sum(cfg["macs_per_image"].values()) == ref.macs_per_image(cfg)


def test_bcnn_conv_share():
    m = _cfg("bcnn")["macs_per_image"]
    assert m["conv"] == 607_518_720
    assert round(m["conv"] / (m["conv"] + m["dense"]), 3) == 0.985


def test_bmlp_least_bytes_one_row():
    # 1-bit weights + uint8 input + packed hidden outputs + f32 logits
    w = (784 * 4096 + 2 * 4096 * 4096 + 4096 * 10) // 8
    x = 784 + 3 * 4096 // 8
    out = 3 * 4096 // 8 + 10 * 4
    assert bmlp.work(_cfg("bmlp"), 1)["dense"][1] == w + x + out


def test_least_time_takes_the_larger_bound():
    kind = "TPU v5 lite"
    p = peaks.peak(kind)
    compute = peaks.least_time(p["ops_per_s"], 1.0, kind)
    memory = peaks.least_time(1.0, p["hbm_bytes_per_s"], kind)
    assert compute == pytest.approx(1.0) and memory == pytest.approx(1.0)
    assert peaks.least_time(2 * p["ops_per_s"], p["hbm_bytes_per_s"],
                            kind) == pytest.approx(2.0)
    assert peaks.least_time(p["ops_per_s"], 3 * p["hbm_bytes_per_s"],
                            kind) == pytest.approx(3.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.least_time(1.0, 1.0, "cpu")


@pytest.mark.parametrize("name", ["bmlp", "bcnn"])
def test_program_spec_is_published_width(name):
    import serving
    from repro.models import cnn

    want = {"bmlp": cnn.BMLPSpec(), "bcnn": cnn.BCNNSpec()}[name]
    assert serving.program_spec(_cfg(name)) == want
