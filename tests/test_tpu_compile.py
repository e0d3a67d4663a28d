"""Compile every kernel of the serving path for a TPU v5e that is
described, not attached: ``jit(...).lower(ShapeDtypeStructs).compile()``
runs the chip's own compiler (Mosaic for the Pallas kernels) here on the
CPU host, so a kernel the chip would refuse fails this file instead of a
chip run.  Nothing executes; results are covered by the interpret-mode
tests.

Widths are the published networks: the BMLP 784-4096x3-10, the
CIFAR-10 BCNN (32x32x3 input, 128/256/512-channel conv stages, FC 1024)
and the blocks of ReActNet-A (112x112 to 7x7, 32 to 1024 channels).
The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and every test worker imports
this file.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import binarize as B
from repro.kernels import binary_attention as batt
from repro.kernels import binary_conv as bconv
from repro.kernels import binary_matmul as bmm
from repro.kernels import bitpack as bp
from repro.kernels import fused_epilogue as fe

U32, I32, F32 = jnp.uint32, jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; returns (compiled, seconds)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


def _assert_kernel(compiled, seconds, request):
    assert "tpu_custom_call" in compiled.as_text()
    request.node.user_properties.append(("compile_s", round(seconds, 2)))


def _gemm_case(m, k, n):
    kw = B.packed_width(k)
    return (lambda a, w: bmm.binary_matmul_packed(a, w, k_true=k),
            [((m, kw), U32), ((n, kw), U32)])


def _fused_gemm_case(m, k, n):
    kw = B.packed_width(k)
    return (lambda a, w, t, f: bmm.binary_matmul_bn_sign_packed(
        a, w, t, f, k_true=k),
            [((m, kw), U32), ((n, kw), U32), ((n,), F32), ((n,), F32)])


def _stack_case(m, width, layers):
    kw = B.packed_width(width)

    def fn(x, *ops):
        ws, ts, fs = ops[0::3], ops[1::3], ops[2::3]
        return bmm.binary_dense_stack_packed(
            x, list(ws), list(ts), list(fs), k_trues=(width,) * layers)
    stage = [((width, kw), U32), ((width,), F32), ((width,), F32)]
    return fn, [((m, kw), U32)] + stage * layers


def _bitplane_dense_case(m, k, n, nbits=8):
    kw = B.packed_width(k)
    return (lambda x, w, rs: bmm.bitplane_dense_packed(
        x, w, rs, k_true=k, nbits=nbits),
            [((nbits, m, kw), U32), ((kw, n), U32), ((n,), I32)])


CASES = {
    # BMLP: the single-launch bit-plane layer 0 at every served bucket,
    # then the hidden layers and the output GEMV.
    "bitplane_dense_b1_784x4096": lambda: _bitplane_dense_case(1, 784, 4096),
    "bitplane_dense_b4_784x4096": lambda: _bitplane_dense_case(4, 784, 4096),
    "bitplane_dense_b16_784x4096": lambda: _bitplane_dense_case(
        16, 784, 4096),
    "bitplane_dense_b256_784x4096": lambda: _bitplane_dense_case(
        256, 784, 4096),
    "gemv_b1_784x4096": lambda: _gemm_case(1, 784, 4096),
    "gemv_b1_4096x4096": lambda: _gemm_case(1, 4096, 4096),
    "gemm_b256_4096x4096": lambda: _gemm_case(256, 4096, 4096),
    "gemm_bn_sign_b256_4096x4096": lambda: _fused_gemm_case(256, 4096, 4096),
    "gemv_bn_sign_b1_4096x4096": lambda: _fused_gemm_case(1, 4096, 4096),
    "dense_stack_b1_3x4096": lambda: _stack_case(1, 4096, 3),
    "dense_stack_b256_2x4096": lambda: _stack_case(256, 4096, 2),
    "bitpack_b256_784": lambda: (
        lambda x: bp.bitpack(x), [((256, 784), F32)]),
    "bn_sign_pack_b256_4096": lambda: (
        lambda x, t, f: fe.bn_sign_pack(x, t, f),
        [((256, 4096), I32), ((4096,), F32), ((4096,), F32)]),
    # BCNN stage 0 output, flattened: (B*32*32, 128).
    "bn_sign_pack_bcnn_stage0_b8": lambda: (
        lambda x, t, f: fe.bn_sign_pack(x, t, f),
        [((8 * 32 * 32, 128), I32), ((128,), F32), ((128,), F32)]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_dense_and_pack_kernels_compile(case, one_chip, request):
    fn, shapes = CASES[case]()
    compiled, seconds = _compile(fn, one_chip, *shapes)
    _assert_kernel(compiled, seconds, request)


def test_bitplane_conv_compiles(one_chip, request):
    # BCNN stage 0: 32x32x3 uint8 -> 128 channels, 8 bit planes.
    cw = B.packed_width(3)

    def fn(x, w, rs):
        return bconv.bitplane_conv2d_packed(
            x, w, rs, kh=3, kw=3, stride=1, pads=((1, 1), (1, 1)),
            out_hw=(32, 32), c_out=128, k_true=27, nbits=8)
    compiled, seconds = _compile(
        fn, one_chip, ((8, 1, 32, 32, cw), U32),
        ((128, 9 * cw), U32), ((128,), I32))
    _assert_kernel(compiled, seconds, request)


def test_conv_bn_sign_compiles_with_several_cout_blocks(one_chip, request):
    # BCNN stage 5: 8x8x512 -> 512, four 128-channel C_out blocks.
    cw = B.packed_width(512)

    def fn(x, w, corr, t, f):
        return bconv.binary_conv2d_bn_sign_packed(
            x, w, corr, t, f, kh=3, kw=3, stride=1, pads=((1, 1), (1, 1)),
            out_hw=(8, 8), c_out=512, k_true=9 * 512, block_n=128)
    compiled, seconds = _compile(
        fn, one_chip, ((1, 8, 8, cw), U32), ((512, 9 * cw), U32),
        ((8, 8, 512), I32), ((512,), F32), ((512,), F32))
    _assert_kernel(compiled, seconds, request)


def test_binary_attention_compiles(one_chip, request):
    # Shares the dense contraction: one causal prefill tile set.
    b, s, h, d = 1, 256, 4, 128

    def fn(q, k, v):
        return batt.binary_attention_packed(q, k, v, d_true=d, causal=True)
    dw = B.packed_width(d)
    compiled, seconds = _compile(
        fn, one_chip, ((b, s, h, dw), U32), ((b, s, h, dw), U32),
        ((b, s, h, d), F32))
    _assert_kernel(compiled, seconds, request)


# ReActNet-A blocks at published widths: (input size, C_in, stride) of
# the 3x3 residual conv; (rows, C, N) of the residual 1x1 conv.
REACTNET_CONVS = {
    "b1_112_c32_s1": (112, 32, 1),
    "b2_112_c64_s2": (112, 64, 2),
    "b3_56_c128_s1": (56, 128, 1),
    "b4_56_c128_s2": (56, 128, 2),
    "b5_28_c256_s1": (28, 256, 1),
    "b6_28_c256_s2": (28, 256, 2),
    "b7_14_c512_s1": (14, 512, 1),
    "b12_14_c512_s2": (14, 512, 2),
    "b13_7_c1024_s1": (7, 1024, 1),
}
REACTNET_POINTWISE = {
    "b1_112_32x64": (2 * 112 * 112, 32, 64),
    "b12_7_512x1024": (2 * 7 * 7, 512, 1024),
    "b13_7_1024x1024": (2 * 7 * 7, 1024, 1024),
}


@pytest.mark.parametrize("case", sorted(REACTNET_CONVS))
def test_reactnet_conv_residual_compiles(case, one_chip, request):
    hw, c, stride = REACTNET_CONVS[case]
    oh, cw = hw // stride, B.packed_width(c)

    def fn(x, w, corr, epi, sc):
        return bconv.binary_conv2d_residual_packed(
            x, w, corr, epi, sc, kh=3, kw=3, stride=stride,
            pads=((1, 1), (1, 1)), out_hw=(oh, oh), c_out=c, k_true=9 * c)
    compiled, seconds = _compile(
        fn, one_chip, ((2, hw, hw, cw), U32), ((9, cw, c), U32),
        ((oh, oh, c), I32), ((8, c), F32), ((2, hw, hw, c), F32))
    _assert_kernel(compiled, seconds, request)


@pytest.mark.parametrize("case", sorted(REACTNET_POINTWISE))
def test_reactnet_pointwise_residual_compiles(case, one_chip, request):
    m, c, n = REACTNET_POINTWISE[case]
    kw = B.packed_width(c)

    def fn(x, w, epi, sc):
        return bmm.pointwise_residual_packed(x, w, epi, sc, k_true=c)
    compiled, seconds = _compile(
        fn, one_chip, ((m, kw), U32), ((kw, n), U32), ((8, n), F32),
        ((m, c), F32))
    _assert_kernel(compiled, seconds, request)
