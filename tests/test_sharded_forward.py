"""Sharded packed BCNN/BMLP forward: spec rules, shard plans, and the
single-device-equivalence plumbing.

Rule/plan tests resolve specs on an abstract mesh (no placement).  The
real multi-device sweep — bit-exactness vs the single-device forward on
an 8-way forced-CPU mesh for (data, model) in {(8,1), (4,2), (2,4)},
zero collectives on the data-parallel path — needs its own process
(device count is fixed at jax init), so it runs
`repro.distributed.verify_sharded` as a subprocess, exactly like the CI
sharding job does.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as SH
from repro.launch.mesh import make_mesh
from repro.models import cnn

from test_sharding import fake_mesh


def small_bcnn(c0=64, c1=48, dense=(128, 10)):
    spec = cnn.BCNNSpec(input_hw=(8, 8), c_in=3,
                        stages=(cnn.ConvStage(c0),
                                cnn.ConvStage(c1, pool=True)),
                        dense=dense)
    params = cnn.init_bcnn(jax.random.PRNGKey(0), spec)
    return cnn.pack_bcnn(params, spec), spec


def small_bmlp(sizes=(784, 128, 96, 10)):
    spec = cnn.BMLPSpec(sizes=sizes)
    params = cnn.init_bmlp(jax.random.PRNGKey(0), spec)
    return cnn.pack_bmlp(params, spec), spec


def test_packed_stage_shards_word_seam():
    """The C_out -> packed-word seam: shard only when every model shard
    owns whole 32-bit words."""
    mesh2 = fake_mesh((4, 2))
    mesh4 = fake_mesh((2, 4))
    assert SH.packed_stage_shards(64, mesh2) == 2     # 64 % 64 == 0
    assert SH.packed_stage_shards(48, mesh2) == 1     # 48 % 64 != 0
    assert SH.packed_stage_shards(64, mesh4) == 1     # 64 % 128 != 0
    assert SH.packed_stage_shards(128, mesh4) == 4
    assert SH.packed_stage_shards(64, fake_mesh((8, 1))) == 1


def test_bcnn_shard_plan_and_specs():
    packed, _ = small_bcnn()
    mesh = fake_mesh((4, 2))
    plan = SH.bcnn_shard_plan(packed, mesh)
    assert plan["conv"] == (2, 1)        # 48-channel stage falls back
    assert plan["dense"] == (2, 1)       # output layer always replicated
    specs = SH.packed_param_specs(packed, mesh)
    assert specs["convs/0/w_packed"] == P("model")
    assert specs["convs/0/rowsum"] == P("model")       # bit-plane stage 0
    assert specs["convs/1/w_packed"] == P()            # fallback
    assert specs["convs/1/correction"] == P()
    assert specs["folded_conv/0/tau"] == P("model")
    assert specs["folded_conv/1/tau"] == P()
    assert specs["denses/0/w_packed"] == P("model")
    assert specs["denses/1/w_packed"] == P()           # logits layer
    assert specs["bn_out/inv"] == P()
    # statics (plan ints, pads, the spec dataclass) get no spec at all
    assert "convs/0/k_true" not in specs
    assert "spec" not in specs


def test_bcnn_pool_mask_spec_follows_stage():
    packed, _ = small_bcnn(c0=64, c1=64)
    specs = SH.packed_param_specs(packed, fake_mesh((4, 2)))
    assert specs["pool_masks/1"] == P("model")
    packed48, _ = small_bcnn(c0=64, c1=48)
    specs48 = SH.packed_param_specs(packed48, fake_mesh((4, 2)))
    assert specs48["pool_masks/1"] == P()


def test_bmlp_shard_plan_and_specs():
    packed, _ = small_bmlp()
    mesh = fake_mesh((4, 2))
    plan = SH.bmlp_shard_plan(packed, mesh)
    assert plan["layer"] == (2, 1, 1)    # 96 falls back, 10 replicated
    specs = SH.packed_param_specs(packed, mesh)
    assert specs["layers/0/w_words"] == P(None, "model")   # (Kw, N)
    assert specs["layers/0/w_rowsum"] == P("model")
    assert specs["layers/1/w_packed"] == P()
    assert specs["folded/0/tau"] == P("model")
    assert specs["folded/1/flip"] == P()


def test_packed_kind_rejects_other_trees():
    with pytest.raises(ValueError):
        SH._packed_kind({"not": "a packed tree"})


@pytest.mark.parametrize("kind", ["bcnn", "bmlp"])
def test_sharded_forward_1x1_mesh_equals_unsharded(kind):
    """End-to-end plumbing (partition/rebuild, shard_map, NamedSharding
    placement) on the in-process single-device mesh."""
    mesh = make_mesh((1, 1), ("data", "model"))
    if kind == "bcnn":
        packed, spec = small_bcnn()
        x = jax.random.randint(jax.random.PRNGKey(1), (2, 8, 8, 3), 0,
                               256).astype(jnp.uint8)
        want = cnn.bcnn_forward_packed(packed, x, backend="jnp")
    else:
        packed, spec = small_bmlp()
        x = jax.random.randint(jax.random.PRNGKey(1), (2, 784), 0,
                               256).astype(jnp.uint8)
        want = cnn.bmlp_forward_packed(packed, x, backend="jnp")
    fwd = SH.make_sharded_forward(packed, mesh, backend="jnp")
    np.testing.assert_array_equal(np.asarray(fwd(x)), np.asarray(want))


def test_forward_rejects_sharded_output_layer():
    packed, _ = small_bcnn()
    x = jnp.zeros((1, 8, 8, 3), jnp.uint8)
    with pytest.raises(AssertionError):
        cnn.bcnn_forward_packed(packed, x, backend="jnp",
                                dense_shards=(1, 2))


@pytest.mark.skipif(bool(os.environ.get("REPRO_SKIP_SHARDED_SWEEP")),
                    reason="sweep already run directly (CI sharding job)")
def test_sharded_forward_8dev_sweep_bit_exact():
    """The real thing: 8 forced CPU devices in a fresh process, all three
    mesh shapes, both networks, jnp + pallas backends — bit-identical to
    the single-device forward, collective-free on the data-parallel path,
    all-gather-of-packed-words only on the model-parallel path."""
    from repro.distributed.subproc import run_verifier
    results = run_verifier()
    meshes = {(tuple(r["mesh"]), r["kind"], r["backend"]) for r in results}
    for shape in ((8, 1), (4, 2), (2, 4)):
        assert (shape, "bcnn", "jnp") in meshes
        assert (shape, "bmlp", "jnp") in meshes
    assert any(r["backend"] == "pallas" for r in results)
    for r in results:
        assert r["bitexact"], r
        assert r["ok"], r
        if r["mesh"][1] == 1:
            assert r["collective_bytes"] == 0.0, r
    # the fallback stage really fell back (48 not word-divisible at 2)
    bcnn42 = next(r for r in results
                  if r["kind"] == "bcnn" and r["mesh"] == [4, 2])
    assert bcnn42["shard_plan"]["conv"][1] == 1
    assert bcnn42["shard_plan"]["conv"][0] == 2
