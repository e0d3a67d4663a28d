"""The comparison that decides ``correct``: the reference passes, its
control (the reference in bfloat16 put in the program's place) fails,
and a run whose timed path is broken underneath reads ``correct`` false.

Runs the harness on the CPU at a small size, with the look for a chip
skipped (Pallas kernels in interpret mode)."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import _paths
import checks
import run

SEED = 2**33 + 77


def _small(cell_name: str, cfg_name: str):
    bench, cell, _, mix = run.load_cell(cell_name)
    with open(os.path.join(_paths.DATA, cfg_name + ".json")) as f:
        cfg = json.load(f)
    mix = dict(mix, server=dict(mix["server"]))
    if mix["arrivals"] == "closed_batch":
        mix.update(batch=8, pool_batches=2, check_batches=2)
        mix["server"].update(max_batch=8, buckets=[8])
    else:
        mix.update(rate_per_s=30)
    return bench, cell, cfg, mix


def _run(cell_name, cfg_name, on_server=None, seconds=1.0):
    bench, cell, cfg, mix = _small(cell_name, cfg_name)
    line = run.run(bench, cell, cfg, mix, seed=SEED, seconds=seconds,
                   trace=False, require_tpu=False, on_server=on_server)
    return json.loads(line), cfg


def _hook(change):
    """A flush hook that alters what the forward produced."""
    def hook(eng, buf, reqs, default):
        return jnp.asarray(change(np.array(default()), buf, len(reqs)))
    return hook


@pytest.mark.parametrize("cell,cfg", [("bmlp.interactive", "bmlp_small"),
                                      ("bcnn.offline", "bcnn_small")])
def test_sound_run_is_correct(cell, cfg):
    out, _ = _run(cell, cfg)
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["checks"]["logit_gap"]["value"] <= \
        out["checks"]["logit_gap"]["limit"]
    assert list(out)[-1] == "checks"


def _alter_one(out, buf, n):
    out[0, 0] += 0.05 * max(1.0, float(np.abs(out[0]).max()))
    return out


def _drop_half(out, buf, n):
    out[n // 2:] = 0.0 if n > 1 else out[n // 2:]
    if n == 1:
        out[0] = out[0][::-1]
    return out


@pytest.mark.parametrize("fault", [_alter_one, _drop_half],
                         ids=["answer_altered", "half_batch_left_out"])
@pytest.mark.parametrize("cell,cfg", [("bmlp.interactive", "bmlp_small"),
                                      ("bcnn.offline", "bcnn_small")])
def test_broken_timed_path_is_not_correct(cell, cfg, fault):
    out, _ = _run(cell, cfg, on_server=lambda srv: setattr(
        srv, "flush_hook", _hook(fault)))
    assert out["correct"] is False
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("cell,cfg", [("bmlp.interactive", "bmlp_small"),
                                      ("bcnn.offline", "bcnn_small")])
def test_control_in_bfloat16_is_not_correct(cell, cfg):
    """The control: the float reference computed in bfloat16, served in
    the program's place."""
    _, _, config, _ = _small(cell, cfg)
    refmod = run.reference_module(config)
    params = refmod.init_params(config, config["weights_seed"])

    def control(out, buf, n):
        return np.asarray(refmod.forward(params, jnp.asarray(buf), config,
                                         jnp.bfloat16), np.float32)

    out, _ = _run(cell, cfg, on_server=lambda srv: setattr(
        srv, "flush_hook", _hook(control)))
    assert out["correct"] is False


def test_logit_gap_scale_and_nonfinite():
    ref = np.array([[10.0, -20.0], [0.1, 0.2]])
    assert checks.logit_gap(ref, ref) == 0.0
    got = ref + np.array([[0.2, 0.0], [0.0, 0.05]])
    # row 0: 0.2 / 20; row 1: 0.05 / max(1, 0.2)
    assert checks.logit_gap(got, ref) == pytest.approx(0.05)
    got[1, 1] = np.nan
    assert checks.logit_gap(got, ref) == float("inf")
    ok, c = checks.judge(0.0, 1)
    assert not ok and c["missing"]["value"] == 1
