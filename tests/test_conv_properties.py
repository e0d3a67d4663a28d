"""Property suite locking down the M-tiled conv grid + fused bit-plane
conv (kernels/binary_conv.py).

Invariants, sampled over the awkward-shape grid in ``strategies.py``:

* packed conv == float-sign conv reference, every backend (pallas
  interpret / jnp / ref),
* the (batch, M tiles, C_out blocks) grid is invariant to the tiling:
  any block_oh/block_n == the untiled single-tile grid, for both the
  int32 kernel and the fused BN-sign-repack kernel,
* fused single-launch bit-plane conv == the 8-plane sequential
  reference == the float path on raw fixed-precision input, including
  the uint8 edge values 0 and 255,
* ``_bitplane_conv_packed`` issues exactly ONE pallas_call,
* invalid block sizes raise instead of being silently clamped.
"""
from _hypothesis_compat import hypothesis, st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import strategies as S

from repro.core import binarize as B
from repro.kernels import binary_conv as BC
from repro.kernels import ops, ref
from repro.models import cnn
from repro.analysis import count_pallas_calls

settings = hypothesis.settings(max_examples=8, deadline=None)


def _conv_float_int(x, w, stride, padding):
    """Integer dots of conv(sign(x), sign(w)) with true zero padding."""
    out = jax.lax.conv_general_dilated(
        B.sign_pm1(x), jnp.transpose(B.sign_pm1(w), (1, 2, 3, 0)),
        (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return np.asarray(out).astype(np.int32)


def _bitplane_float_int(x_uint8, w, stride, padding):
    """Integer conv of the RAW fixed-precision input against sign(w)."""
    out = jax.lax.conv_general_dilated(
        x_uint8.astype(jnp.float32),
        jnp.transpose(B.sign_pm1(w), (1, 2, 3, 0)), (stride, stride),
        padding, dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return np.asarray(out).astype(np.int32)


def _draw_uint8(key, shape, fill):
    if fill == "zeros":
        return jnp.zeros(shape, jnp.uint8)
    if fill == "max255":
        return jnp.full(shape, 255, jnp.uint8)
    return jax.random.randint(key, shape, 0, 256).astype(jnp.uint8)


# ---------------------------------------------------------------------------
# Packed conv == float reference, every backend
# ---------------------------------------------------------------------------

@settings
@hypothesis.given(case=S.conv_cases(), seed=S.seeds())
def test_packed_conv_matches_float_all_backends(case, seed):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (case.batch, case.h, case.w, case.c_in))
    wt = jax.random.normal(jax.random.fold_in(key, 1),
                           (case.c_out, case.k, case.k, case.c_in))
    want = _conv_float_int(x, wt, case.stride, case.padding)
    for backend in ("pallas", "jnp", "ref"):
        got = ops.binary_conv2d(x, wt, stride=case.stride,
                                padding=case.padding, backend=backend)
        np.testing.assert_array_equal(
            np.asarray(got), want,
            err_msg=f"{backend} backend diverged on {case}")


# ---------------------------------------------------------------------------
# The M-tiled grid is invariant to the tiling
# ---------------------------------------------------------------------------

@settings
@hypothesis.given(case=S.conv_cases(), block_oh=S.m_tilings(),
                  block_n=st.sampled_from((None, 128, 256)),
                  seed=S.seeds())
def test_m_tiled_grid_equals_untiled(case, block_oh, block_n, seed):
    """Any (block_oh, block_n) == the single-M-tile (pre-refactor) grid."""
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (case.batch, case.h, case.w, case.c_in))
    wt = jax.random.normal(jax.random.fold_in(key, 1),
                           (case.c_out, case.k, case.k, case.c_in))
    plan = BC.make_conv_plan(wt, input_hw=(case.h, case.w),
                             stride=case.stride, padding=case.padding)
    x_p = B.pack_bits(x).reshape(case.batch, case.h, case.w, -1)
    kw = dict(kh=case.k, kw=case.k, stride=case.stride, pads=plan["pads"],
              out_hw=plan["out_hw"], c_out=case.c_out,
              k_true=plan["k_true"], interpret=True)
    untiled = BC.binary_conv2d_packed(
        x_p, plan["w_packed"], plan["correction"],
        block_oh=plan["out_hw"][0], **kw)
    tiled = BC.binary_conv2d_packed(
        x_p, plan["w_packed"], plan["correction"], block_oh=block_oh,
        block_n=block_n, **kw)
    np.testing.assert_array_equal(np.asarray(tiled), np.asarray(untiled))


@settings
@hypothesis.given(case=S.conv_cases(max_hw=8), block_oh=S.m_tilings(),
                  seed=S.seeds())
def test_m_tiled_fused_epilogue_equals_untiled(case, block_oh, seed):
    """Tiling invariance holds through the fused BN-sign-repack epilogue
    (per-tile correction blocks + per-tile re-bitpack)."""
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (case.batch, case.h, case.w, case.c_in))
    wt = jax.random.normal(jax.random.fold_in(key, 1),
                           (case.c_out, case.k, case.k, case.c_in))
    tau = jax.random.normal(jax.random.fold_in(key, 2), (case.c_out,)) * 3
    flip = jnp.where(jax.random.bernoulli(jax.random.fold_in(key, 3), 0.4,
                                          (case.c_out,)), -1.0, 1.0)
    plan = BC.make_conv_plan(wt, input_hw=(case.h, case.w),
                             stride=case.stride, padding=case.padding)
    x_p = B.pack_bits(x).reshape(case.batch, case.h, case.w, -1)
    conv = ref.binary_conv2d_packed_ref(
        x_p, plan["w_packed"], plan["correction"], kh=case.k, kw=case.k,
        stride=case.stride, pads=plan["pads"], c_out=case.c_out,
        k_true=plan["k_true"])
    want = ref.bn_sign_pack_ref(conv, tau, flip)
    got = BC.binary_conv2d_bn_sign_packed(
        x_p, plan["w_packed"], plan["correction"], tau, flip, kh=case.k,
        kw=case.k, stride=case.stride, pads=plan["pads"],
        out_hw=plan["out_hw"], c_out=case.c_out, k_true=plan["k_true"],
        block_oh=block_oh, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Bit-plane first layer: fused single launch == sequential == float
# ---------------------------------------------------------------------------

@settings
@hypothesis.given(case=S.bitplane_conv_cases(), fill=S.uint8_fill(),
                  block_oh=S.m_tilings(), seed=S.seeds())
def test_bitplane_fused_equals_sequential_equals_float(case, fill, block_oh,
                                                       seed):
    key = jax.random.PRNGKey(seed)
    xu = _draw_uint8(key, (case.batch, case.h, case.w, case.c_in), fill)
    wt = jax.random.normal(jax.random.fold_in(key, 1),
                           (case.c_out, case.k, case.k, case.c_in))
    plan = BC.make_bitplane_conv_plan(wt, input_hw=(case.h, case.w),
                                      stride=case.stride,
                                      padding=case.padding)
    want = _bitplane_float_int(xu, wt, case.stride, case.padding)
    # 8-plane sequential reference (the pre-fusion model path == the
    # 'jnp'/'ref' backend of the dispatch).
    seq = ops.bitplane_conv2d_packed(plan, xu, backend="jnp")
    np.testing.assert_array_equal(np.asarray(seq), want,
                                  err_msg=f"sequential ref != float {case}")
    # Fused single-launch kernel, any M tiling.
    fused = ops.bitplane_conv2d_packed(plan, xu, backend="pallas",
                                       block_oh=block_oh)
    np.testing.assert_array_equal(np.asarray(fused), want,
                                  err_msg=f"fused kernel != float {case}")


def test_bitplane_uint8_edges_exact():
    """Constant 0 and 255 images: every plane all-(−1) / all-(+1)."""
    wt = jax.random.normal(jax.random.PRNGKey(0), (16, 3, 3, 5))
    plan = BC.make_bitplane_conv_plan(wt, input_hw=(6, 6))
    for fill in ("zeros", "max255"):
        xu = _draw_uint8(None, (1, 6, 6, 5), fill)
        want = _bitplane_float_int(xu, wt, 1, "SAME")
        for backend in ("jnp", "pallas"):
            got = ops.bitplane_conv2d_packed(plan, xu, backend=backend)
            np.testing.assert_array_equal(np.asarray(got), want)


@settings
@hypothesis.given(seed=S.seeds(), nbits=st.sampled_from((1, 4, 8)))
def test_pack_bitplanes_matches_per_plane_pack_bits(seed, nbits):
    """Plane packing == pack_bits of the ±1-shifted plane, every plane."""
    key = jax.random.PRNGKey(seed)
    x = jax.random.randint(key, (3, 4, 37), 0, 1 << nbits
                           ).astype(jnp.uint8)
    got = B.pack_bitplanes_uint8(x, nbits)
    planes = B.bitplanes_uint8(x, nbits)
    for i in range(nbits):
        want = B.pack_bits(2.0 * planes[i].astype(jnp.float32) - 1.0)
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want))


def test_bitplane_conv_is_single_kernel_launch():
    """The model's stage-0 conv traces to exactly ONE pallas_call (the
    acceptance criterion: plane loop fused into the kernel, plane
    extraction/packing pure jnp)."""
    key = jax.random.PRNGKey(3)
    wt = jax.random.normal(key, (16, 3, 3, 3))
    plan = BC.make_bitplane_conv_plan(wt, input_hw=(8, 8))
    xu = jax.random.randint(jax.random.fold_in(key, 1), (2, 8, 8, 3), 0,
                            256).astype(jnp.uint8)
    n = count_pallas_calls(
        lambda v: cnn._bitplane_conv_packed(plan, v, 8, backend="pallas"),
        xu)
    assert n == 1, f"expected 1 kernel launch, traced {n}"
    # And it still computes the right thing through the model entry point.
    want = _bitplane_float_int(xu, wt, 1, "SAME")
    got = cnn._bitplane_conv_packed(plan, xu, 8, backend="pallas")
    np.testing.assert_array_equal(np.asarray(got), want)


def test_tiled_conv_launch_count_is_one():
    """M tiling multiplies grid steps, not kernel launches."""
    key = jax.random.PRNGKey(4)
    wt = jax.random.normal(key, (8, 3, 3, 4))
    plan = BC.make_conv_plan(wt, input_hw=(8, 8))
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, 8, 8, 4))
    x_p = B.pack_bits(x).reshape(1, 8, 8, -1)
    n = count_pallas_calls(
        lambda v: BC.binary_conv2d_packed(
            v, plan["w_packed"], plan["correction"], kh=3, kw=3, stride=1,
            pads=plan["pads"], out_hw=plan["out_hw"], c_out=8,
            k_true=plan["k_true"], block_oh=2, interpret=True), x_p)
    assert n == 1


# ---------------------------------------------------------------------------
# Block-size knob validation (regression: silent clamp-up)
# ---------------------------------------------------------------------------

def _tiny_conv_setup():
    key = jax.random.PRNGKey(5)
    wt = jax.random.normal(key, (8, 3, 3, 4))
    plan = BC.make_conv_plan(wt, input_hw=(6, 6))
    x = jax.random.normal(jax.random.fold_in(key, 1), (1, 6, 6, 4))
    x_p = B.pack_bits(x).reshape(1, 6, 6, -1)
    return plan, x_p


@pytest.mark.parametrize("bad_block_n", [1, 64, 130, 127])
def test_block_n_below_lane_raises(bad_block_n):
    """block_n < 128 (or non-multiple) used to be silently clamped up to
    128, making the knob a no-op — it must raise."""
    plan, x_p = _tiny_conv_setup()
    with pytest.raises(ValueError, match="block_n"):
        ops.binary_conv2d_packed(plan, x_p, backend="pallas",
                                 block_n=bad_block_n)


def test_block_n_valid_values_still_work():
    plan, x_p = _tiny_conv_setup()
    want = ops.binary_conv2d_packed(plan, x_p, backend="jnp")
    for block_n in (None, 128, 256):
        got = ops.binary_conv2d_packed(plan, x_p, backend="pallas",
                                       block_n=block_n)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_block_oh_invalid_raises():
    plan, x_p = _tiny_conv_setup()
    with pytest.raises(ValueError, match="block_oh"):
        ops.binary_conv2d_packed(plan, x_p, backend="pallas", block_oh=0)


def test_bn_sign_pack_block_cw_raises():
    """The standalone epilogue kernel validates its lane-axis block the
    same way (shared check_block_lanes)."""
    from repro.kernels import fused_epilogue as FE
    x = jnp.ones((4, 64), jnp.int32)
    tau = jnp.zeros((64,))
    flip = jnp.ones((64,))
    with pytest.raises(ValueError, match="block_cw"):
        FE.bn_sign_pack(x, tau, flip, block_cw=64, interpret=True)
    with pytest.raises(ValueError, match="block_m"):
        FE.bn_sign_pack(x, tau, flip, block_m=4, interpret=True)


def test_bitpack_block_knobs_raise():
    """bitpack validates both block axes (no silent clamp-up)."""
    from repro.kernels import bitpack as BP
    x = jnp.ones((4, 64))
    with pytest.raises(ValueError, match="block_m"):
        BP.bitpack(x, block_m=3, interpret=True)
    with pytest.raises(ValueError, match="block_kw"):
        BP.bitpack(x, block_kw=64, interpret=True)


def test_bitplane_plan_carries_no_correction():
    """The bitplane plan's pad handling lives entirely in the rowsum —
    a dead zero correction array must not ride along in packed params."""
    wt = jax.random.normal(jax.random.PRNGKey(0), (8, 3, 3, 3))
    plan = BC.make_bitplane_conv_plan(wt, input_hw=(6, 6))
    assert "correction" not in plan
    assert plan["rowsum"].shape == (8,)
