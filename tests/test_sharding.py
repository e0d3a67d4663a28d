"""Sharding resolver unit tests + small-mesh lower/compile integration.

Here we verify the RULES on a small in-process mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import get_config
from repro.distributed import sharding as SH
from repro.models import model as M


def mesh_1x1():
    return jax.make_mesh((1, 1), ("data", "model"))


def fake_mesh(shape=(2, 4), axes=("data", "model")):
    # abstract mesh for spec resolution only (no device placement needed)
    import numpy as np
    devs = np.array(jax.devices() * (int(np.prod(shape)) //
                                     len(jax.devices()) + 1))
    return Mesh(devs[:int(np.prod(shape))].reshape(shape), axes)


def test_param_specs_rules():
    cfg = get_config("gemma2-9b", reduced=True)
    params = jax.eval_shape(lambda: M.init_model(jax.random.PRNGKey(0),
                                                 cfg))
    mesh = fake_mesh((2, 2))
    specs = SH.param_specs(params, mesh)
    # column-parallel qkv: (stack, d_in, d_out) -> (None, data, model)
    seg0 = specs["stack"][0]
    wq_spec = seg0[0]["attn"]["wq"]["w"]
    assert wq_spec == P(None, "data", "model")
    wo_spec = seg0[0]["attn"]["wo"]["w"]
    assert wo_spec == P(None, "model", "data")
    # embeddings: vocab over model
    assert specs["embed"]["table"] == P("model", "data")
    # norms replicated
    assert specs["ln_out"]["scale"] == P()


def test_param_specs_moe_expert_parallel():
    cfg = get_config("qwen3-moe-30b-a3b", reduced=True)
    params = jax.eval_shape(lambda: M.init_model(jax.random.PRNGKey(0),
                                                 cfg))
    mesh = fake_mesh((2, 2))
    specs = SH.param_specs(params, mesh)
    we = specs["stack"][0][0]["mlp"]["we_up"]["we"]
    assert we == P(None, "model", "data", None)    # (L, E, D, F)
    wd = specs["stack"][0][0]["mlp"]["we_down"]["we"]
    assert wd == P(None, "model", None, "data")


def test_divisibility_fallback():
    """whisper-base vocab 51865 % 16 != 0 -> vocab axis dropped."""
    cfg = get_config("whisper-base", reduced=False)
    params = jax.eval_shape(lambda: M.init_model(jax.random.PRNGKey(0),
                                                 cfg))
    mesh = fake_mesh((16, 16))
    specs = SH.param_specs(params, mesh)
    assert specs["embed"]["table"][0] is None      # 51865 not divisible


def test_batch_specs_seq_sharding():
    mesh = fake_mesh((2, 2))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 64), jnp.int32)}
    bs = SH.batch_specs(batch, mesh)
    assert bs["tokens"] == P("data", None)         # pod absent -> data only
    bs_seq = SH.batch_specs(batch, mesh, shard_seq=True)
    assert bs_seq["tokens"] == P(None, "data")


def test_cache_specs():
    mesh = fake_mesh((2, 2))
    cache = {"k": jax.ShapeDtypeStruct((4, 2, 64, 2, 16), jnp.bfloat16),
             "v": jax.ShapeDtypeStruct((4, 2, 64, 2, 16), jnp.bfloat16)}
    cs = SH.cache_specs(cache, mesh)
    assert cs["k"] == P(None, "data", None, "model", None)
    cs_seq = SH.cache_specs(cache, mesh, shard_seq=True)
    assert cs_seq["k"] == P(None, None, "data", "model", None)


def test_lower_compile_small_mesh():
    """End-to-end lower+compile of a sharded train step on a 1x1 mesh
    (in-process analogue of the dry-run)."""
    from repro.train import trainer as TR
    cfg = get_config("starcoder2-3b", reduced=True)
    tc = TR.TrainConfig()
    mesh = mesh_1x1()
    state = jax.eval_shape(
        lambda: TR.init_train_state(jax.random.PRNGKey(0), cfg, tc))
    pspecs = SH.param_specs(state["params"], mesh)
    state_specs = {"params": pspecs,
                   "opt": {"mu": pspecs, "nu": pspecs, "step": P()}}
    batch = {"tokens": jax.ShapeDtypeStruct((2, 16), jnp.int32),
             "labels": jax.ShapeDtypeStruct((2, 16), jnp.int32)}
    ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                is_leaf=lambda x: isinstance(x, P))
    step = jax.jit(TR.make_train_step(cfg, tc),
                   in_shardings=(ns(state_specs),
                                 ns(SH.batch_specs(batch, mesh))))
    with mesh:
        compiled = step.lower(state, batch).compile()
    assert compiled.cost_analysis() is not None
    mem = compiled.memory_analysis()
    assert mem is not None

