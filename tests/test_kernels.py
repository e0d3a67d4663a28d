"""Pallas kernel sweeps vs the pure-jnp oracles (interpret mode on CPU).

Every kernel: shapes x dtypes, bit-exact against ref.py.
"""
from _hypothesis_compat import hypothesis, st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import binarize as B
from repro.kernels import binary_matmul as BMM
from repro.kernels import bitpack as BP
from repro.kernels import ops, ref

settings = hypothesis.settings(max_examples=20, deadline=None)


@pytest.mark.parametrize("m,k,n", [
    (1, 64, 128),        # GEMV specialization (paper §6.2 batch-1 swap)
    (8, 256, 256),
    (16, 1000, 100),     # non-aligned K and N -> padding path
    (33, 4096, 65),
    (128, 8192, 128),    # one full tile in every dim
    (130, 131, 257),     # everything ragged
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_binary_matmul_shapes(m, k, n, dtype):
    key = jax.random.PRNGKey(m * 7 + k * 3 + n)
    a = jax.random.normal(key, (m, k)).astype(dtype)
    b = jax.random.normal(jax.random.fold_in(key, 1), (n, k)).astype(dtype)
    want = ref.binary_matmul_ref(a, b)
    got = ops.binary_matmul(a, b, backend="pallas")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings
@hypothesis.given(m=st.integers(1, 40), kw_mult=st.integers(1, 6),
                  n=st.integers(1, 40), seed=st.integers(0, 2**31 - 1))
def test_binary_matmul_property(m, kw_mult, n, seed):
    k = kw_mult * 32 + (seed % 31)
    key = jax.random.PRNGKey(seed)
    a = jax.random.normal(key, (m, k))
    b = jax.random.normal(jax.random.fold_in(key, 1), (n, k))
    got = BMM.binary_matmul_packed(B.pack_bits(a), B.pack_bits(b),
                                   k_true=k, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.binary_matmul_ref(a, b)))


@pytest.mark.parametrize("blocks", [(8, 128, 128), (16, 256, 128),
                                    (128, 128, 256)])
def test_binary_matmul_block_shape_invariance(blocks):
    """Output must not depend on the BlockSpec tiling."""
    bm, bn, bkw = blocks
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (50, 5000))
    b = jax.random.normal(jax.random.fold_in(key, 1), (70, 5000))
    want = ref.binary_matmul_ref(a, b)
    got = BMM.binary_matmul_packed(B.pack_bits(a), B.pack_bits(b),
                                   k_true=5000, block_m=bm, block_n=bn,
                                   block_kw=bkw, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,k", [(1, 32), (8, 4096), (20, 100), (256, 8192),
                                 (3, 33)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bitpack_shapes(m, k, dtype):
    x = jax.random.normal(jax.random.PRNGKey(k + m), (m, k)).astype(dtype)
    got = BP.bitpack(x, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.bitpack_ref(x)))


@settings
@hypothesis.given(m=st.integers(1, 30), k=st.integers(1, 500),
                  seed=st.integers(0, 2**31 - 1))
def test_bitpack_property(m, k, seed):
    x = jax.random.normal(jax.random.PRNGKey(seed), (m, k))
    got = BP.bitpack(x, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref.bitpack_ref(x)))


def test_every_public_op_rejects_unknown_backend():
    """Regression: dispatchers used to re-implement 'auto' resolution
    inline, so a typo like backend='pallsa' silently ran the jnp path.
    Every public op must now raise through _resolve."""
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (4, 64))
    b = jax.random.normal(jax.random.fold_in(key, 1), (4, 64))
    x = jax.random.normal(jax.random.fold_in(key, 2), (1, 6, 6, 8))
    w = jax.random.normal(jax.random.fold_in(key, 3), (16, 3, 3, 8))
    xu = jax.random.randint(jax.random.fold_in(key, 4), (1, 6, 6, 3), 0,
                            256).astype(jnp.uint8)
    wu = jax.random.normal(jax.random.fold_in(key, 5), (16, 3, 3, 3))
    from repro.kernels import binary_conv as BC
    plan = BC.make_conv_plan(w, input_hw=(6, 6))
    bplan = BC.make_bitplane_conv_plan(wu, input_hw=(6, 6))
    x_p = B.pack_bits(x)
    folded = {"tau": jnp.zeros((16,)), "flip": jnp.ones((16,))}
    dense_stages = [{"w_packed": B.pack_bits(b), "k_true": 64,
                     "tau": jnp.zeros((4,)), "flip": jnp.ones((4,))}]
    calls = [
        lambda be: ops.binary_matmul(a, b, backend=be),
        lambda be: ops.binary_matmul_packed(B.pack_bits(a), B.pack_bits(b),
                                            k_true=64, backend=be),
        lambda be: ops.binary_matmul_bn_sign_packed(
            B.pack_bits(a), B.pack_bits(b), jnp.zeros((4,)),
            jnp.ones((4,)), k_true=64, backend=be),
        lambda be: ops.binary_dense_stack_packed(dense_stages,
                                                 B.pack_bits(a), backend=be),
        lambda be: ops.bitpack(a, backend=be),
        lambda be: ops.binary_conv2d_packed(plan, x_p, backend=be),
        lambda be: ops.binary_conv2d_bn_sign_packed(plan, folded, x_p,
                                                    backend=be),
        lambda be: ops.bitplane_conv2d_packed(bplan, xu, backend=be),
        lambda be: ops.bn_sign_pack(jnp.zeros((2, 16), jnp.int32),
                                    folded["tau"], folded["flip"],
                                    backend=be),
        lambda be: ops.binary_conv2d(x, w, backend=be),
    ]
    for call in calls:
        with pytest.raises(ValueError, match="unknown backend"):
            call("pallsa")


def test_binary_matmul_pallas_packs_in_kernel():
    """Regression: ops.binary_matmul used to pack both operands with the
    host-side pack_bits even on backend='pallas'.  Routed through the
    bitpack dispatcher, the traced fn now launches 3 kernels (two packs
    + the GEMM) instead of one."""
    from repro.analysis import count_pallas_calls
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (16, 64))
    b = jax.random.normal(jax.random.fold_in(key, 1), (32, 64))
    n = count_pallas_calls(
        lambda u, v: ops.binary_matmul(u, v, backend="pallas"), a, b)
    assert n == 3, f"expected pack+pack+GEMM kernel launches, traced {n}"
    n_jnp = count_pallas_calls(
        lambda u, v: ops.binary_matmul(u, v, backend="jnp"), a, b)
    assert n_jnp == 0, n_jnp


def test_binary_conv2d_wrapper_forwards_block_knobs():
    """The convenience wrapper must reach the same tiling validation as
    the packed entry points: an off-lane block_n raises, a valid pair
    changes nothing."""
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (1, 6, 6, 8))
    w = jax.random.normal(jax.random.fold_in(key, 1), (16, 3, 3, 8))
    with pytest.raises(ValueError):
        ops.binary_conv2d(x, w, backend="pallas", block_n=64)
    want = ops.binary_conv2d(x, w, backend="pallas")
    got = ops.binary_conv2d(x, w, backend="pallas", block_oh=2, block_n=128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ops_auto_backend_cpu_is_jnp():
    a = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
    b = jax.random.normal(jax.random.PRNGKey(1), (4, 64))
    np.testing.assert_array_equal(
        np.asarray(ops.binary_matmul(a, b, backend="auto")),
        np.asarray(ref.binary_matmul_ref(a, b)))


# ---------------------------------------------------------------------------
# Binary conv2d kernel (kernels/binary_conv.py) + fused epilogue
# ---------------------------------------------------------------------------

# Awkward geometries the packed path must get bit-exact: C_in not a
# multiple of 32 (sub-word and multi-word), stride 2, VALID, 1x1 kernels,
# even kernels, batch 1.
CONV_CASES = [
    (1, 7, 7, 3, 8, 3, 1, "SAME"),       # batch 1, tiny C_in
    (2, 8, 8, 20, 33, 3, 2, "SAME"),     # stride 2, ragged C_out
    (2, 9, 9, 40, 16, 3, 2, "VALID"),    # C_in > 32, not a multiple
    (1, 5, 5, 32, 10, 1, 1, "SAME"),     # 1x1 kernel
    (2, 6, 6, 64, 24, 2, 2, "VALID"),    # even kernel, stride 2
]


def _conv_float_int(x, w, stride, padding):
    """Integer dots of conv(sign(x), sign(w)) with true zero padding."""
    xb = B.sign_pm1(x)
    wb = B.sign_pm1(w)
    out = jax.lax.conv_general_dilated(
        xb, jnp.transpose(wb, (1, 2, 3, 0)), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return np.asarray(out).astype(np.int32)


@pytest.mark.parametrize("b,h,w,c_in,c_out,k,stride,padding", CONV_CASES)
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_binary_conv2d_matches_float(b, h, w, c_in, c_out, k, stride,
                                     padding, backend):
    key = jax.random.PRNGKey(b * 131 + h * 17 + c_in)
    x = jax.random.normal(key, (b, h, w, c_in))
    wt = jax.random.normal(jax.random.fold_in(key, 1), (c_out, k, k, c_in))
    want = _conv_float_int(x, wt, stride, padding)
    got = ops.binary_conv2d(x, wt, stride=stride, padding=padding,
                            backend=backend)
    np.testing.assert_array_equal(np.asarray(got), want)


def _rand_folded(key, c):
    tau = jax.random.normal(key, (c,)) * 3
    flip = jnp.where(jax.random.bernoulli(jax.random.fold_in(key, 1),
                                          0.4, (c,)), -1.0, 1.0)
    return {"tau": tau, "flip": flip}


@pytest.mark.parametrize("b,h,w,c_in,c_out,k,stride,padding", CONV_CASES)
def test_binary_conv2d_fused_epilogue_matches_ref(b, h, w, c_in, c_out, k,
                                                  stride, padding):
    """Fused conv+BN-sign+repack == conv, then reference threshold+pack."""
    from repro.kernels import binary_conv as BC
    key = jax.random.PRNGKey(b * 7 + c_out)
    x = jax.random.normal(key, (b, h, w, c_in))
    wt = jax.random.normal(jax.random.fold_in(key, 1), (c_out, k, k, c_in))
    plan = BC.make_conv_plan(wt, input_hw=(h, w), stride=stride,
                             padding=padding)
    x_p = ops.bitpack(x.reshape(-1, c_in), backend="jnp"
                      ).reshape(b, h, w, -1)
    folded = _rand_folded(jax.random.fold_in(key, 2), c_out)
    conv = ops.binary_conv2d_packed(plan, x_p, backend="jnp")
    want = ref.bn_sign_pack_ref(conv, folded["tau"], folded["flip"])
    for backend in ("jnp", "pallas"):
        got = ops.binary_conv2d_bn_sign_packed(plan, folded, x_p,
                                               backend=backend)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,c", [(1, 16), (13, 33), (40, 128), (5, 100)])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
def test_bn_sign_pack_shapes(m, c, backend):
    key = jax.random.PRNGKey(m * 3 + c)
    x = jax.random.randint(key, (m, c), -200, 200)
    folded = _rand_folded(jax.random.fold_in(key, 1), c)
    got = ops.bn_sign_pack(x, folded["tau"], folded["flip"],
                           backend=backend)
    want = ref.bn_sign_pack_ref(x, folded["tau"], folded["flip"])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_bn_sign_pack_threshold_boundary():
    """x == tau must take the >= branch, matching apply_bn_sign_folded."""
    x = jnp.array([[5, -5, 0]], dtype=jnp.int32)
    tau = jnp.array([5.0, -5.0, 0.0])
    flip = jnp.array([1.0, -1.0, 1.0])
    for backend in ("jnp", "pallas"):
        got = ops.bn_sign_pack(x, tau, flip, backend=backend)
        # ge = [T, T, T]; flip>0 = [T, F, T] -> bits [1, 0, 1] -> 0b101
        np.testing.assert_array_equal(np.asarray(got),
                                      np.array([[0b101]], dtype=np.uint32))


def test_maxpool_packed_equals_pool_then_threshold():
    """Bit-domain pooling == maxpool(int32) then threshold, both flips."""
    from repro.core import binary_layers as L
    key = jax.random.PRNGKey(3)
    z = jax.random.randint(key, (2, 6, 6, 40), -100, 100)
    folded = _rand_folded(jax.random.fold_in(key, 1), 40)
    want = ref.bn_sign_pack_ref(L.maxpool2d(z), folded["tau"],
                                folded["flip"])
    pooled = L.maxpool2d_packed(
        ops.bn_sign_pack(z, folded["tau"], folded["flip"], backend="jnp"),
        L.pool_flip_mask(folded))
    np.testing.assert_array_equal(np.asarray(pooled), np.asarray(want))
