"""The paper's central correctness claim (§6): the optimized binary path

is numerically equivalent to the non-optimized binary reference — for
both the MLP (Table 2) and the CNN (Table 3) networks.
"""
from _hypothesis_compat import hypothesis, st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import cnn

settings = hypothesis.settings(max_examples=8, deadline=None)


def _randomize_bn(params, key):
    bns = params.get("bns", []) + params.get("conv_bns", []) \
        + params.get("dense_bns", [])
    for i, bn in enumerate(bns):
        c = bn["gamma"].shape[0]
        k = jax.random.fold_in(key, i)
        ks = jax.random.split(k, 5)
        bn["gamma"] = jax.random.uniform(ks[0], (c,), minval=0.3,
                                         maxval=1.5) * jnp.where(
            jax.random.bernoulli(ks[4], 0.3, (c,)), -1.0, 1.0)
        bn["beta"] = jax.random.normal(ks[1], (c,))
        bn["mean"] = jax.random.normal(ks[2], (c,)) * 3
        bn["var"] = jax.random.uniform(ks[3], (c,), minval=0.5, maxval=2.0)
    return params


@settings
@hypothesis.given(seed=st.integers(0, 2**31 - 1), b=st.integers(1, 5),
                  d_in=st.integers(8, 64), width=st.integers(16, 96))
def test_bmlp_packed_equals_reference(seed, b, d_in, width):
    key = jax.random.PRNGKey(seed)
    spec = cnn.BMLPSpec(sizes=(d_in, width, width // 2, 10))
    params = _randomize_bn(cnn.init_bmlp(key, spec), key)
    x = jax.random.randint(jax.random.fold_in(key, 1), (b, d_in), 0,
                           256).astype(jnp.uint8)
    want = cnn.bmlp_forward_float(params, x)
    got = cnn.bmlp_forward_packed(cnn.pack_bmlp(params, spec), x,
                                  backend="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@settings
@hypothesis.given(seed=st.integers(0, 2**31 - 1))
def test_bcnn_packed_equals_reference(seed):
    key = jax.random.PRNGKey(seed)
    spec = cnn.BCNNSpec(
        input_hw=(8, 8), c_in=3,
        stages=(cnn.ConvStage(16), cnn.ConvStage(16, pool=True),
                cnn.ConvStage(32, pool=True)),
        dense=(48, 10))
    params = _randomize_bn(cnn.init_bcnn(key, spec), key)
    x = jax.random.randint(jax.random.fold_in(key, 1), (2, 8, 8, 3), 0,
                           256).astype(jnp.uint8)
    want = cnn.bcnn_forward_float(params, x, spec)
    got = cnn.bcnn_forward_packed(cnn.pack_bcnn(params, spec), x,
                                  backend="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)


def test_bcnn_pallas_backend_matches_jnp():
    """The pallas (interpret) and jnp backends agree bit-for-bit."""
    key = jax.random.PRNGKey(7)
    spec = cnn.BCNNSpec(input_hw=(8, 8), c_in=3,
                        stages=(cnn.ConvStage(16, pool=True),),
                        dense=(32, 10))
    params = _randomize_bn(cnn.init_bcnn(key, spec), key)
    x = jax.random.randint(jax.random.fold_in(key, 1), (2, 8, 8, 3), 0,
                           256).astype(jnp.uint8)
    packed = cnn.pack_bcnn(params, spec)
    a = cnn.bcnn_forward_packed(packed, x, backend="jnp")
    b = cnn.bcnn_forward_packed(packed, x, backend="pallas")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("h,c_in,c_out,k,stride,padding", [
    (8, 20, 8, 3, 1, "SAME"),     # C_in not a multiple of 32
    (9, 3, 12, 3, 2, "SAME"),     # stride 2, odd spatial
    (8, 40, 8, 3, 1, "VALID"),    # VALID, multi-word ragged C_in
    (6, 33, 8, 1, 1, "SAME"),     # 1x1 kernel
    (7, 16, 8, 3, 2, "VALID"),    # stride 2 + VALID
])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_conv_packed_equals_float_awkward_shapes(h, c_in, c_out, k, stride,
                                                 padding, backend):
    """Layer-level claim on awkward shapes (batch 1 included): the packed

    conv path matches apply_binary_conv2d_float exactly on integer dots."""
    from repro.core import binarize as B
    from repro.core import binary_layers as L
    from repro.kernels import ops as kops
    key = jax.random.PRNGKey(h * 31 + c_in * 7 + c_out)
    x = jax.random.normal(key, (1, h, h, c_in))
    params = L.init_binary_conv2d(jax.random.fold_in(key, 1), k, k, c_in,
                                  c_out)
    want = L.apply_binary_conv2d_float(params, x, stride=stride,
                                       padding=padding)
    packed = L.pack_binary_conv2d(params, input_hw=(h, h), stride=stride,
                                  padding=padding)
    x_p = kops.bitpack(B.sign_pm1(x).reshape(-1, c_in), backend="jnp"
                       ).reshape(1, h, h, -1)
    got = L.apply_binary_conv2d_packed(packed, x_p, backend=backend)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want).astype(np.int32))


def test_bcnn_fused_path_ragged_channels():
    """Full fused pipeline with channel counts that are NOT multiples of

    32: the fused epilogue's zero-bit tails, the bit-domain pooling, and
    the grouped conv->dense boundary packing must all stay exact."""
    key = jax.random.PRNGKey(11)
    spec = cnn.BCNNSpec(
        input_hw=(8, 8), c_in=3,
        stages=(cnn.ConvStage(20), cnn.ConvStage(24, pool=True),
                cnn.ConvStage(40, pool=True)),
        dense=(33, 10))
    params = _randomize_bn(cnn.init_bcnn(key, spec), key)
    x = jax.random.randint(jax.random.fold_in(key, 1), (3, 8, 8, 3), 0,
                           256).astype(jnp.uint8)
    want = cnn.bcnn_forward_float(params, x, spec)
    packed = cnn.pack_bcnn(params, spec)
    for backend in ("jnp", "pallas"):
        got = cnn.bcnn_forward_packed(packed, x, backend=backend)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-3)


def test_paper_architectures_instantiate():
    """The full paper architectures (Table 2/3) build and pack."""
    mlp_spec = cnn.BMLPSpec()            # 784-4096^3-10
    assert mlp_spec.sizes == (784, 4096, 4096, 4096, 10)
    cnn_spec = cnn.BCNNSpec()            # 2x128C3-MP2-...-1024FC-10
    assert cnn_spec.stages[-1].c_out == 512
    # memory: packed vs float parameter bytes (paper reports ~31x)
    key = jax.random.PRNGKey(0)
    spec = cnn.BMLPSpec(sizes=(784, 512, 10))
    params = cnn.init_bmlp(key, spec)
    packed = cnn.pack_bmlp(params, spec)
    fp_bytes = sum(p["w"].size * 4 for p in params["layers"])
    # Layer 0 (bit-plane) keeps its words word-major, as ``w_words``.
    bin_bytes = sum(p.get("w_packed", p.get("w_words")).size * 4
                    for p in packed["layers"])
    assert fp_bytes / bin_bytes > 28     # ~32x less (padding overhead)
