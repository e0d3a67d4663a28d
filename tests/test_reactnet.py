"""ReActNet-A: the packed forward against the plain float reference, the
two residual kernels against their oracles, explicit conv pads, and the
serving seams.

Small shapes on the CPU, Pallas kernels in interpret mode.  The spec
(16x16 input, widths 32, 32, 64, 128) has a same-width block, the
stride-1 32 -> 64 doubling block and a stride-2 doubling block.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import vmem
from repro.core import binarize as B
from repro.kernels import binary_conv as BC
from repro.kernels import fused_epilogue as FE
from repro.kernels import ops as kops
from repro.models import cnn
from repro.models import reactnet as R
from repro.train.serve import PackedInferenceServer

SPEC = R.ReActNetSpec(input_hw=(16, 16), stage_out_channel=(32, 32, 64, 128),
                      num_classes=10)


@pytest.fixture(scope="module")
def model():
    params = R.init_reactnet(jax.random.PRNGKey(11), SPEC)
    x = jax.random.randint(jax.random.PRNGKey(12), (2, 16, 16, 3), 0,
                           256).astype(jnp.uint8)
    return params, R.pack_reactnet(params, SPEC), x


def test_spec_strides_follow_the_published_rule():
    shapes = R.block_shapes(SPEC)
    assert [(b.c_in, b.c_out, b.stride, b.in_hw) for b in shapes] == [
        (32, 32, 1, (8, 8)), (32, 64, 1, (8, 8)), (64, 128, 2, (8, 8))]
    full = R.block_shapes(R.ReActNetSpec())
    assert [i + 1 for i, b in enumerate(full) if b.stride == 2] == \
        [2, 4, 6, 12]
    with pytest.raises(ValueError, match="doubles"):
        R.ReActNetSpec(stage_out_channel=(32, 96))
    with pytest.raises(ValueError, match="even input"):
        R.ReActNetSpec(input_hw=(26, 26), stage_out_channel=(32, 64, 128))


@pytest.mark.parametrize("backend", ["pallas", "jnp"])
def test_packed_forward_matches_float_reference(model, backend):
    """Every block's float stream is bit-identical to the reference's,
    every packed sign bit is the sign of stream + the next bias, and the
    logits agree to the head's rounding."""
    params, packed, x = model
    h = R.stem_float(params["stem"], x)
    hs, hp = R.stem_packed(packed["stem"], x)
    np.testing.assert_array_equal(np.asarray(hs), np.asarray(h))
    nxt = [p["move11"] for p in params["blocks"]]
    np.testing.assert_array_equal(np.asarray(hp),
                                  np.asarray(B.pack_bits(h + nxt[0])))
    for i, (p, bs, blk) in enumerate(zip(params["blocks"],
                                         R.block_shapes(SPEC),
                                         packed["res_blocks"])):
        h = R.block_float(p, h, bs.stride)
        hs, hp = R.block_packed(blk, hs, hp, backend=backend)
        np.testing.assert_array_equal(np.asarray(hs), np.asarray(h))
        if i + 1 < len(nxt):
            np.testing.assert_array_equal(
                np.asarray(hp), np.asarray(B.pack_bits(h + nxt[i + 1])))
    ref = R.reactnet_forward_float(params, x, SPEC)
    out = R.reactnet_forward_packed(packed, x, backend=backend)
    scale = max(1.0, float(jnp.abs(ref).max()))
    assert float(jnp.abs(out - ref).max()) / scale <= 1e-6


def _epi(key, c):
    """Epilogue rows whose scale has at most 8 significant bits, so that
    z·scale is exact and the kernel and oracle agree bit for bit."""
    k = jax.random.split(key, 6)
    scale = jnp.round(jax.random.normal(k[0], (c,)) * 128) / 2 ** 13
    rows = [scale, jax.random.normal(k[1], (c,)),
            0.2 * jax.random.normal(k[2], (c,)),
            jnp.exp2(-jax.random.randint(k[3], (c,), 1, 4).astype(jnp.float32)),
            0.2 * jax.random.normal(k[4], (c,)),
            0.2 * jax.random.normal(k[5], (c,))]
    return jnp.concatenate([jnp.stack(rows), jnp.zeros((2, c))]
                           ).astype(jnp.float32)


# (images a tile, M tiles an image, C_out blocks) each case reaches; C
# below 128 takes the VPU route, 128 and up the MXU route.
CONV_CASES = [
    # whole images folded into one M tile
    (8, 32, 1, 2, (2, 1, 1)), (8, 64, 2, 2, (2, 1, 1)),
    (16, 128, 2, 3, (3, 1, 1)),
    (4, 256, 1, 1, (1, 1, 2)), (6, 32, 2, 1, (1, 1, 1)),
    # several M tiles, each reading its own input row band
    (40, 32, 1, 1, (1, 2, 1)), (48, 512, 2, 1, (1, 2, 4)),
    (40, 512, 2, 1, (1, 1, 4)),
    # C of 128 at stride 2; C of 1,024 over several C_out blocks
    (8, 128, 2, 2, (2, 1, 1)), (4, 1024, 1, 2, (2, 1, 8))]


@pytest.mark.parametrize("h,c,stride,batch,tiles", CONV_CASES,
                         ids=["-".join(map(str, k[:4])) for k in CONV_CASES])
def test_conv_residual_kernel_matches_oracle(h, c, stride, batch, tiles):
    key = jax.random.PRNGKey(h * c + stride)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    w = jax.random.normal(k1, (c, 3, 3, c))
    plan = BC.make_residual_conv_plan(w, input_hw=(h, h), stride=stride)
    assert plan["pads"] == ((1, 1), (1, 1))
    oh = h // stride
    nb, block_oh, bn = vmem.residual_conv_blocks(batch, oh, oh, c // 32, c,
                                                 stride=stride)
    assert (nb, oh // block_oh, c // bn) == tiles
    x = jax.random.normal(k2, (batch, h, h, c))
    sc = jax.random.normal(k3, (batch, h, h, c))
    epi = _epi(k4, c)
    y, p = kops.binary_conv2d_residual(plan, epi, B.pack_bits(x), sc,
                                       backend="pallas")
    y_ref, p_ref = kops.binary_conv2d_residual(plan, epi, B.pack_bits(x), sc,
                                               backend="jnp")
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
    np.testing.assert_array_equal(np.asarray(p), np.asarray(p_ref))
    # ... and the oracle against a float conv with PyTorch's padding.
    z = jax.lax.conv_general_dilated(
        B.sign_pm1(x), jnp.transpose(B.sign_pm1(w), (1, 2, 3, 0)),
        (stride, stride), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST).astype(jnp.int32)
    if stride == 2:
        sc = FE.avg_pool2x2(sc[:, 0::2, 0::2], sc[:, 0::2, 1::2],
                            sc[:, 1::2, 0::2], sc[:, 1::2, 1::2])
    y_f, u_f = FE.residual_epilogue(z, epi, sc)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_f))
    np.testing.assert_array_equal(np.asarray(p), np.asarray(B.pack_bits(u_f)))


@pytest.mark.parametrize("sx,sw", [(1.0, 1.0), (1.0, -1.0)])
def test_conv_residual_largest_sums_are_exact(sx, sw):
    """All-+1 or all-−1 inputs and weights at C = 1,024: the interior
    sums reach ±K = ±9,216 and the border ones stop at the taps inside.
    With an identity epilogue and a zero shortcut the stream is z
    itself, so it shows that no sum rounds on the MXU."""
    c = 1024
    w = jnp.full((c, 3, 3, c), sw)
    plan = BC.make_residual_conv_plan(w, input_hw=(4, 4), stride=1)
    x = jnp.full((2, 4, 4, c), sx)
    epi = jnp.zeros((FE.EPI_ROWS, c)).at[FE.EPI_SCALE].set(1.0).at[
        FE.EPI_SLOPE].set(1.0)
    sc = jnp.zeros((2, 4, 4, c))
    y, p = kops.binary_conv2d_residual(plan, epi, B.pack_bits(x), sc,
                                       backend="pallas")
    taps = np.array([2, 3, 3, 2])          # 3x3 taps inside a 4-wide row
    want = sx * sw * c * np.multiply.outer(taps, taps)
    np.testing.assert_array_equal(np.asarray(y),
                                  np.broadcast_to(want[None, :, :, None],
                                                  y.shape))
    assert float(jnp.abs(y).max()) == 9 * c
    y_ref, p_ref = kops.binary_conv2d_residual(plan, epi, B.pack_bits(x),
                                               sc, backend="jnp")
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
    np.testing.assert_array_equal(np.asarray(p), np.asarray(p_ref))


def test_conv_residual_route_follows_the_shapes():
    """The MXU where C_in and C_out each fill a 128-lane group, the VPU
    below: at ReActNet-A's widths, its two 112² blocks on the VPU."""
    routes = [vmem.residual_conv_route(bs.c_in // 32, bs.c_in)
              for bs in R.block_shapes(R.ReActNetSpec())]
    assert routes == ["vpu", "vpu"] + ["mxu"] * 11
    assert vmem.residual_conv_route(2, 256) == "vpu"
    assert vmem.residual_conv_route(8, 64) == "vpu"


def test_conv_residual_tiles_fit_vmem_at_256_images():
    """Every 3x3 block of ReActNet-A at 256 images takes a tile whose
    estimate, ±1 scratch included, fits the VMEM budget: 7x7x1024 halves
    its tile from 8 images to 4 to get there."""
    for bs in R.block_shapes(R.ReActNetSpec()):
        oh = bs.in_hw[0] // bs.stride
        est = vmem.residual_conv_estimate(256, bs.in_hw, bs.c_in // 32, 3, 3,
                                          bs.c_in, (oh, oh), stride=bs.stride)
        assert est.fits(), est.breakdown()
    assert vmem.residual_conv_blocks(256, 7, 7, 32, 1024) == (4, 7, 128)


@pytest.mark.parametrize("c,route", [(32, "vpu"), (128, "mxu")])
def test_conv_residual_dispatch_counted(c, route):
    """Each pallas dispatch bumps ``ops.dispatch.residual_conv.<route>``
    once, and the other route's counter not at all; the jnp oracle bumps
    neither."""
    from repro import telemetry
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    plan = BC.make_residual_conv_plan(jax.random.normal(k1, (c, 3, 3, c)),
                                      input_hw=(4, 4), stride=1)
    x = B.pack_bits(jax.random.normal(k2, (1, 4, 4, c)))
    sc = jnp.zeros((1, 4, 4, c))
    epi = _epi(k2, c)
    counters = {r: telemetry.default().metrics.counter(
        f"ops.dispatch.residual_conv.{r}") for r in ("mxu", "vpu")}
    before = {r: k.value for r, k in counters.items()}
    kops.binary_conv2d_residual(plan, epi, x, sc, backend="pallas")
    kops.binary_conv2d_residual(plan, epi, x, sc, backend="jnp")
    assert {r: k.value - before[r] for r, k in counters.items()} == {
        r: int(r == route) for r in counters}


@pytest.mark.parametrize("m,c,n", [(40, 32, 64), (64, 64, 128),
                                   (48, 128, 128), (32, 128, 256),
                                   (24, 256, 256)])
def test_pointwise_residual_kernel_matches_oracle(m, c, n):
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(m + c + n), 4)
    w = jax.random.normal(k1, (n, c))
    w_words = B.pack_bits(w).T
    x = jax.random.normal(k2, (m, c))
    sc = jax.random.normal(k3, (m, c))
    epi = _epi(k4, n)
    y, p = kops.pointwise_residual(w_words, epi, B.pack_bits(x), sc,
                                   k_true=c, backend="pallas")
    y_ref, p_ref = kops.pointwise_residual(w_words, epi, B.pack_bits(x), sc,
                                           k_true=c, backend="jnp")
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
    np.testing.assert_array_equal(np.asarray(p), np.asarray(p_ref))
    z = (B.sign_pm1(x) @ B.sign_pm1(w).T).astype(jnp.int32)
    y_f, _ = FE.residual_epilogue(z, epi, jnp.tile(sc, (1, n // c)))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_f))


def test_conv_geometry_explicit_pads():
    (oh, ow), pads = BC.conv_geometry((16, 12), 3, 3, 2, ((1, 1), (1, 1)))
    assert (oh, ow) == (8, 6) and pads == ((1, 1), (1, 1))
    # 'SAME' puts the extra pad low-index-last: (0, 1) at stride 2.
    assert BC.conv_geometry((16, 12), 3, 3, 2, "SAME")[1] == ((0, 1), (0, 1))
    with pytest.raises(ValueError, match=">= 0"):
        BC.conv_geometry((8, 8), 3, 3, 1, ((-1, 1), (1, 1)))
    # The correction turns the pad-as-(-1) result into zero padding.
    k1, k2 = jax.random.split(jax.random.PRNGKey(5))
    w = jax.random.normal(k1, (64, 3, 3, 32))
    x = jax.random.normal(k2, (2, 16, 12, 32))
    plan = BC.make_conv_plan(w, input_hw=(16, 12), stride=2,
                             padding=((1, 1), (1, 1)))
    got = kops.binary_conv2d_packed(plan, B.pack_bits(x), backend="jnp")
    want = jax.lax.conv_general_dilated(
        B.sign_pm1(x), jnp.transpose(B.sign_pm1(w), (1, 2, 3, 0)), (2, 2),
        ((1, 1), (1, 1)), dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want).astype(np.int32))


def test_generic_weights_flip_only_near_zero():
    """A block with generic float weights (alpha, scales, slopes not
    dyadic): the program rounds z*(alpha*scale) where the reference
    rounds (alpha*z)*scale, so a sign may differ, but only where the
    value it is taken of is within 1e-5 of the channel's scale of 0."""
    spec = R.ReActNetSpec(input_hw=(16, 16), stage_out_channel=(32, 64),
                          num_classes=10)
    params = R.init_reactnet(jax.random.PRNGKey(21), spec)
    keys = iter(jax.random.split(jax.random.PRNGKey(22), 16))
    p = dict(params["blocks"][0])
    p["w3"] = jax.random.normal(next(keys), p["w3"].shape)
    p["pw"] = [jax.random.normal(next(keys), w.shape) for w in p["pw"]]
    p["bn1"] = {"scale": jax.random.normal(next(keys), (32,)) / 30,
                "shift": jax.random.normal(next(keys), (32,))}
    p["bn2"] = [{"scale": jax.random.normal(next(keys), (32,)) / 6,
                 "shift": jax.random.normal(next(keys), (32,))}
                for _ in p["bn2"]]
    p["prelu1"] = jax.random.uniform(next(keys), (32,), minval=0.05,
                                     maxval=0.5)
    p["prelu2"] = jax.random.uniform(next(keys), (64,), minval=0.05,
                                     maxval=0.5)
    params["blocks"][0] = p
    packed = R.pack_reactnet(params, spec)
    x = jax.random.normal(next(keys), (4, 8, 8, 32))
    hp = B.pack_bits(x + p["move11"])
    ref = R.block_float(p, x, 1)
    y, _ = R.block_packed(packed["res_blocks"][0], x, hp, backend="pallas")
    # The sign the next conv takes, of both streams (bias 0: last block).
    got = np.asarray(B.unpack_bits(B.pack_bits(y), 64))
    want = np.asarray(B.unpack_bits(B.pack_bits(ref), 64))
    ref = np.asarray(ref)
    scale = np.abs(ref).max(axis=(0, 1, 2))
    flips = got != want
    assert np.all(np.abs(ref)[flips] < 1e-5 * np.broadcast_to(
        scale, ref.shape)[flips])
    # ... and the streams themselves differ by float rounding only.
    np.testing.assert_allclose(np.asarray(y), ref, rtol=0, atol=1e-5 *
                               float(scale.max()))


def test_serving_path_bit_matches_direct_forward(model):
    params, packed, x = model
    srv = PackedInferenceServer(max_batch=4, buckets=(2, 4))
    srv.register("reactnet", params, SPEC, kind="reactnet",
                 backend="pallas")
    eng = srv.engine()
    assert eng.kind == "reactnet" and eng.example_shape == (16, 16, 3)
    got = np.stack(srv.serve(list(np.asarray(x)), deadline=0.0))
    fwd = cnn.make_packed_forward(packed, backend="pallas")
    np.testing.assert_array_equal(got, np.asarray(fwd(x)))
    # The head rounds in XLA, so a forward compiled apart may differ
    # there by float rounding, and nowhere before.
    ref = R.reactnet_forward_float(params, x, SPEC)
    assert np.abs(got - np.asarray(ref)).max() <= 1e-6 * max(
        1.0, float(np.abs(ref).max()))


def test_reactnet_refuses_a_mesh():
    params, spec, kind = cnn.demo_model("reactnet", smoke=True)
    assert kind == "reactnet" and spec.input_hw == (16, 16)
    srv = PackedInferenceServer(max_batch=4)
    with pytest.raises(ValueError, match="mesh serving is not supported "
                                         "for the reactnet"):
        srv.register("r", params, spec, kind="reactnet", mesh=object())


def test_packed_kind_names_every_kind():
    params, spec, _ = cnn.demo_model("reactnet", smoke=True)
    assert cnn.packed_kind(R.pack_reactnet(params, spec)) == "reactnet"
    with pytest.raises(ValueError) as e:
        cnn.packed_kind({"nothing": 1})
    for name in ("pack_reactnet", "pack_bcnn", "pack_bmlp",
                 "pack_transformer"):
        assert name in str(e.value)
    with pytest.raises(ValueError, match="'reactnet'"):
        cnn.demo_model("resnet")
