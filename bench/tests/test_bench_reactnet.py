"""ReActNet-A in the benchmark: its work counts, its configuration at
the published widths, and the comparison that decides ``correct`` on a
small run (the reference passes; its bfloat16 control does not).

The runs go through the harness on the CPU at a small size, with the
look for a chip skipped (Pallas kernels in interpret mode)."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import _paths
import run
from reference import reactnet

SEED = 2**33 + 1017


def _cfg(name):
    with open(os.path.join(_paths.BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_macs_per_image():
    cfg = _cfg("reactnet")
    assert reactnet.macs_per_image(cfg) == 4_828_758_016
    m = cfg["macs_per_image"]
    assert m == {"residual_conv": 4_288_241_664, "pointwise": 540_516_352}
    # 3x3 binary convs 4,277,403,648 + stem 10,838,016; 1x1 binary convs
    # 539,492,352 + classifier 1,024,000.
    assert m["residual_conv"] - 112 * 112 * 9 * 3 * 32 == 4_277_403_648
    assert m["pointwise"] - 1024 * 1000 == 539_492_352


@pytest.mark.parametrize("name", ["reactnet", "small"])
def test_work_matches_config(name):
    cfg = (_cfg("reactnet") if name == "reactnet" else
           json.load(open(os.path.join(_paths.DATA, "reactnet_small.json"))))
    work = reactnet.work(cfg, 3)
    assert sorted(work) == sorted(cfg["kernel_families"])
    assert sorted(work) == sorted(cfg["macs_per_image"])
    for fam, (ops, nbytes) in work.items():
        assert ops == 2 * 3 * cfg["macs_per_image"][fam]
        assert nbytes > 0
    assert sum(cfg["macs_per_image"].values()) == reactnet.macs_per_image(cfg)


def test_least_bytes_one_row():
    """Block 13 alone (7x7, 1024 channels, stride 1): its 3x3 conv reads
    1-bit weights, the packed input and the float shortcut, and writes
    the float stream and its packed sign."""
    cfg = _cfg("reactnet")
    one = dict(cfg, stage_out_channel=[1024, 1024], input_hw=[14, 14],
               c_in=3)
    conv = reactnet.work(one, 1)["residual_conv"][1]
    stem = 14 * 14 * 3 + 9 * 3 * 1024 * 4 + 7 * 7 * 1024 * (4 + 1 / 8)
    block = (9 * 1024 * 1024 / 8 + 49 * 1024 * (1 / 8 + 4)
             + 49 * 1024 * (4 + 1 / 8))
    assert conv == int(stem + block)


def test_program_spec_is_published_width():
    import serving
    from repro.models import cnn

    spec = serving.program_spec(_cfg("reactnet"))
    assert spec == cnn.ReActNetSpec()
    assert spec.stage_out_channel == (32, 64, 128, 128, 256, 256, 512, 512,
                                      512, 512, 512, 512, 1024, 1024)
    assert (spec.input_hw, spec.c_in, spec.num_classes) == ((224, 224), 3,
                                                             1000)
    from repro.models import reactnet as R
    strides = [b.stride for b in R.block_shapes(spec)]
    assert [i + 1 for i, s in enumerate(strides) if s == 2] == [2, 4, 6, 12]
    assert R.block_shapes(spec)[-1].in_hw == (7, 7)


def _run(on_server=None):
    bench, cell, _, mix = run.load_cell("reactnet.offline")
    with open(os.path.join(_paths.DATA, "reactnet_small.json")) as f:
        cfg = json.load(f)
    mix = dict(mix, server=dict(mix["server"]), batch=4, pool_batches=2,
               check_batches=2)
    mix["server"].update(max_batch=4, buckets=[4])
    line = run.run(bench, cell, cfg, mix, seed=SEED, seconds=0.5,
                   trace=False, require_tpu=False, on_server=on_server)
    return json.loads(line), cfg


def test_sound_run_is_correct():
    out, _ = _run()
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["checks"]["logit_gap"]["value"] <= 1e-5
    assert set(out["metrics"]) == {"images_per_s", "setup_s"}


def test_control_in_bfloat16_is_not_correct():
    """The whole reference in bfloat16, served in the program's place."""
    with open(os.path.join(_paths.DATA, "reactnet_small.json")) as f:
        config = json.load(f)
    params = reactnet.init_params(config, config["weights_seed"])

    def hook(eng, buf, reqs, default):
        default()
        return jnp.asarray(np.asarray(reactnet.forward(
            params, jnp.asarray(buf), config, jnp.bfloat16), np.float32))

    out, _ = _run(on_server=lambda srv: setattr(srv, "flush_hook", hook))
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]
