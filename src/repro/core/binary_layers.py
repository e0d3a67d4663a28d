"""Binary layers — the paper's §5.2 layer zoo as functional JAX modules.

Every layer is a pair of pure functions over pytree params:

* ``init_*``          -> params (latent fp weights, trainable)
* ``apply_*_float``   -> the float-sign reference path (numerically defines
                         the layer; used for training with STE)
* ``pack_*``          -> inference-time conversion: sign + bit-pack the
                         weights ONCE (paper C2), precompute the padding
                         correction (C5) and the folded BN threshold
* ``apply_*_packed``  -> the optimized path on packed params

The packed path is *exactly* integer-equivalent to the float-sign path
(the paper's "numerically equivalent to BinaryNet" claim) — enforced by
tests/test_paper_equivalence.py.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import binarize as B
from repro.kernels import binary_conv as bconv
from repro.kernels import ops as kops

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Dense (fully-connected) binary layer
# ---------------------------------------------------------------------------

def init_binary_dense(key: jax.Array, in_dim: int, out_dim: int) -> Params:
    w = jax.random.uniform(key, (out_dim, in_dim), jnp.float32, -1.0, 1.0)
    return {"w": w}


def apply_binary_dense_float(params: Params, x: jax.Array,
                             *, ste: bool = False) -> jax.Array:
    """Reference: y = sign(x) . sign(W)^T, computed in fp32.

    ``ste=True`` uses the straight-through estimator on both operands
    (training path, paper §4.4).
    """
    binarize = B.binarize_ste if ste else B.sign_pm1
    xb = binarize(x.astype(jnp.float32))
    wb = binarize(params["w"])
    return jnp.dot(xb, wb.T)


def pack_binary_dense(params: Params) -> Params:
    """One-time weight packing (paper C2)."""
    w = params["w"]
    return {"w_packed": B.pack_bits(w), "k_true": w.shape[1]}


def apply_binary_dense_packed(packed: Params, x: jax.Array, *,
                              backend: str = "auto") -> jax.Array:
    """Optimized: pack(sign(x)) then XNOR-popcount GEMM.  Returns int32."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    x_p = kops.bitpack(x2, backend=backend)
    out = kops.binary_matmul_packed(x_p, packed["w_packed"],
                                    k_true=packed["k_true"], backend=backend)
    return out.reshape(*lead, -1)


def pack_binary_dense_grouped(params: Params, group: int) -> Params:
    """Weight packing for *pre-packed* activations with per-group padding.

    A packed conv activation flattens to (…, G·Cw) words where each group
    of ``Cw = ceil(group/32)`` words covers ``group`` channels of one
    pixel, with zero-bit tails when ``group`` is not a multiple of 32.
    Packing W the same way ((out, G, group) -> pack -> (out, G·Cw)) keeps
    the tails zero on both operands, so they XOR to no mismatches and the
    K − 2·popcount identity stays exact.
    """
    w = params["w"]                                   # (out, G*group)
    out_dim, k = w.shape
    assert k % group == 0, (k, group)
    w_packed = B.pack_bits(w.reshape(out_dim, k // group, group)
                           ).reshape(out_dim, -1)
    return {"w_packed": w_packed, "k_true": k, "group": group}


def apply_binary_dense_prepacked(packed: Params, x_packed: jax.Array, *,
                                 backend: str = "auto") -> jax.Array:
    """XNOR-popcount GEMM on an activation that is *already* bit-packed

    (the fused-epilogue output) — no unpack/repack round trip."""
    lead = x_packed.shape[:-1]
    x2 = x_packed.reshape(-1, x_packed.shape[-1])
    out = kops.binary_matmul_packed(x2, packed["w_packed"],
                                    k_true=packed["k_true"], backend=backend)
    return out.reshape(*lead, -1)


def apply_binary_dense_bn_packed(packed: Params, folded: Params,
                                 x_packed: jax.Array, *,
                                 backend: str = "auto") -> jax.Array:
    """Fused dense GEMM + BN-sign threshold + re-bitpack: packed in,

    packed out (the dense analogue of ``apply_binary_conv2d_bn_packed``).
    The (…, N) int32 activation never appears un-packed in HBM.  Returns
    (…, ceil(N/32)) uint32.
    """
    lead = x_packed.shape[:-1]
    x2 = x_packed.reshape(-1, x_packed.shape[-1])
    out = kops.binary_matmul_bn_sign_packed(
        x2, packed["w_packed"], folded["tau"], folded["flip"],
        k_true=packed["k_true"], backend=backend)
    return out.reshape(*lead, -1)


def apply_binary_dense_stack_packed(packed_layers: list, foldeds: list,
                                    x_packed: jax.Array, *,
                                    backend: str = "auto",
                                    resident: bool | None = None
                                    ) -> jax.Array:
    """The whole hidden dense stack: each layer GEMM + folded-BN

    threshold + re-bitpack, chained without un-packed activations.  On
    the pallas backend a VMEM-resident stack runs as ONE kernel launch
    (``resident=None`` auto-decides by VMEM budget; see
    ``kernels.ops.binary_dense_stack_packed``)."""
    assert len(packed_layers) == len(foldeds), (len(packed_layers),
                                                len(foldeds))
    stages = [{"w_packed": p["w_packed"], "k_true": p["k_true"],
               "tau": f["tau"], "flip": f["flip"]}
              for p, f in zip(packed_layers, foldeds)]
    lead = x_packed.shape[:-1]
    x2 = x_packed.reshape(-1, x_packed.shape[-1])
    out = kops.binary_dense_stack_packed(stages, x2, backend=backend,
                                         resident=resident)
    return out.reshape(*lead, -1)


# ---------------------------------------------------------------------------
# First-layer bit-plane dense (paper §4.3 / C4)
# ---------------------------------------------------------------------------

def pack_bitplane_dense(params: Params, nbits: int = 8) -> Params:
    """One-time packing for the fixed-precision first layer (paper C2):

    the weights word-major, (Kw, N), so the single-launch bit-plane
    kernel reads each packed word as a sublane row with no transpose,
    and the eq.3 rowsum correction."""
    w = params["w"]
    wb = B.sign_pm1(w)
    return {
        "w_words": B.pack_bits(w).T,
        "k_true": w.shape[1],
        "w_rowsum": wb.sum(axis=1).astype(jnp.int32),   # the eq.3 correction
        "nbits": nbits,
    }


def apply_bitplane_dense_packed(packed: Params, x_uint8: jax.Array, *,
                                backend: str = "auto") -> jax.Array:
    """First layer on fixed-precision input, fully binary-optimized.

    Every bit plane is contracted against the SAME packed weights and
    recombined  y = 1/2 * sum_i 2^i (d_i + rowsum)  (exact integer
    identity; see ``core.binarize.bitplane_dot``) — on the pallas
    backend in ONE kernel launch (``kops.bitplane_dense_packed``).
    Returns (..., N) int32 == x.astype(int32) @ sign(W)^T.
    """
    lead = x_uint8.shape[:-1]
    x2 = x_uint8.reshape(-1, x_uint8.shape[-1])
    out = kops.bitplane_dense_packed(packed, x2, backend=backend)
    return out.reshape(*lead, -1)


def apply_bitplane_dense_float(params: Params, x_uint8: jax.Array
                               ) -> jax.Array:
    """Reference: integer GEMM of raw uint8 input against sign(W)."""
    wb = B.sign_pm1(params["w"])
    return jnp.dot(x_uint8.astype(jnp.float32), wb.T)


# ---------------------------------------------------------------------------
# Binary 2D convolution (paper C5/C6): im2col on packed words + correction
# ---------------------------------------------------------------------------

def init_binary_conv2d(key: jax.Array, kh: int, kw: int, c_in: int,
                       c_out: int) -> Params:
    w = jax.random.uniform(key, (c_out, kh, kw, c_in), jnp.float32, -1, 1)
    return {"w": w}


def apply_binary_conv2d_float(params: Params, x: jax.Array, *,
                              stride: int = 1, padding: str = "SAME",
                              ste: bool = False) -> jax.Array:
    """Reference: fp conv of sign(x) with sign(W), true zero padding."""
    binarize = B.binarize_ste if ste else B.sign_pm1
    xb = binarize(x.astype(jnp.float32))
    wb = binarize(params["w"])                        # (O, KH, KW, I)
    return jax.lax.conv_general_dilated(
        xb, jnp.transpose(wb, (1, 2, 3, 0)),          # HWIO
        window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def pack_binary_conv2d(params: Params, *, input_hw: tuple[int, int],
                       stride: int = 1, padding: str = "SAME") -> Params:
    """Pack weights along channels-per-tap (paper C3) and precompute the

    zero-padding correction matrix (paper C5) — delegated to the kernel
    subsystem's plan builder (``kernels.binary_conv.make_conv_plan``),
    which every conv backend consumes.
    """
    return bconv.make_conv_plan(params["w"], input_hw=input_hw,
                                stride=stride, padding=padding)


def apply_binary_conv2d_packed(packed: Params, x_packed: jax.Array, *,
                               backend: str = "auto") -> jax.Array:
    """Optimized conv: in-kernel im2col -> XNOR popcount -> +correction.

    ``x_packed``: (B, H, W, Cw) channel-packed input (pack C with
    ``kops.bitpack`` / previous layer's packed activation).  Returns
    (B, H', W', C_out) int32.  The 'pallas' backend gathers the KH·KW
    packed taps in VMEM — the patch matrix is never materialized in HBM
    ('jnp'/'ref' keep the old host-side im2col as the oracle).
    """
    return kops.binary_conv2d_packed(packed, x_packed, backend=backend)


def apply_binary_conv2d_bn_packed(packed: Params, folded: Params,
                                  x_packed: jax.Array, *,
                                  backend: str = "auto") -> jax.Array:
    """Fused conv + BN-sign threshold + re-bitpack: packed in, packed out.

    The inter-layer activation never appears un-packed in HBM.  Returns
    (B, H', W', ceil(C_out/32)) uint32.
    """
    return kops.binary_conv2d_bn_sign_packed(packed, folded, x_packed,
                                             backend=backend)


def localize_conv_plan(plan: Params, n_shards: int) -> Params:
    """Per-shard view of a conv plan whose C_out axis is split ``n_shards``
    ways (the C_out-parallel sharded forward, XNOR-Net-style decomposition).

    The array leaves (``w_packed``, ``correction``, ``rowsum``) arrive
    already sliced by the partitioner — only the static ``c_out`` needs
    rewriting so the kernel dispatch sees the LOCAL output-channel count.
    ``k_true``, geometry, and ``cw`` are contraction-side statics and stay
    global: every shard consumes the full input.
    """
    if n_shards == 1:
        return plan
    c_out = plan["c_out"]
    assert c_out % n_shards == 0, (c_out, n_shards)
    return {**plan, "c_out": c_out // n_shards}


# ---------------------------------------------------------------------------
# First-layer bit-plane conv (paper §4.3 / C4)
# ---------------------------------------------------------------------------

def pack_bitplane_conv2d(params: Params, *, input_hw: tuple[int, int],
                         stride: int = 1, padding: str = "SAME",
                         nbits: int = 8) -> Params:
    """Conv plan for the fixed-precision first layer: per-tap weight

    packing plus the all-taps rowsum that absorbs both the {0,1}->±1
    plane shift and the zero-pad correction (the C5 correction is
    identically zero, so the plan carries none — see
    ``kernels.binary_conv.make_bitplane_conv_plan``).
    """
    return bconv.make_bitplane_conv_plan(params["w"], input_hw=input_hw,
                                         stride=stride, padding=padding,
                                         nbits=nbits)


def apply_bitplane_conv2d_packed(packed: Params, x_uint8: jax.Array, *,
                                 backend: str = "auto") -> jax.Array:
    """First conv layer on raw fixed-precision input, fully binary.

    On the pallas backend this is ONE kernel launch — the plane loop runs
    in-kernel over a VMEM-resident plane stack (previously 8 sequential
    per-plane conv launches).  Returns (B, H', W', C_out) int32 ==
    integer conv of the raw input against sign(W), true zero padding.
    """
    return kops.bitplane_conv2d_packed(packed, x_uint8, backend=backend)


# ---------------------------------------------------------------------------
# Batch-norm (inference) + sign, and the folded threshold form
# ---------------------------------------------------------------------------

def init_batchnorm(c: int) -> Params:
    return {"gamma": jnp.ones((c,)), "beta": jnp.zeros((c,)),
            "mean": jnp.zeros((c,)), "var": jnp.ones((c,))}


def apply_batchnorm(params: Params, x: jax.Array, eps: float = 1e-5
                    ) -> jax.Array:
    return apply_bn_affine(fold_bn_affine(params, eps), x)


def fold_bn_affine(params: Params, eps: float = 1e-5) -> Params:
    """Inference BN as ``{"mean", "inv", "beta"}``, with the per-channel
    ``inv = gamma / sqrt(var + eps)`` evaluated once, at pack time.

    Left inside a jitted forward, that rsqrt of closed-over constants is
    constant-folded by the compiler, which may round it differently from
    the runtime op by one ulp, so the same logits would depend on whether
    the forward was jitted.
    """
    return {"mean": params["mean"],
            "inv": params["gamma"] * jax.lax.rsqrt(params["var"] + eps),
            "beta": params["beta"]}


def apply_bn_affine(folded: Params, x: jax.Array) -> jax.Array:
    return (x.astype(jnp.float32) - folded["mean"]) * folded["inv"] + \
        folded["beta"]


def fold_bn_sign(params: Params, eps: float = 1e-5) -> Params:
    """Fold BN + sign into a per-channel integer threshold compare.

    sign(gamma*(x-mu)*inv_sigma + beta) == flip * sign(x - tau) with
    tau = mu - beta*sigma/gamma,  flip = sign(gamma).  (Beyond-paper BCNN
    inference optimization — removes all fp math between binary GEMMs, so
    the GEMM epilogue emits packed bits directly.)
    """
    sigma = jnp.sqrt(params["var"] + eps)
    gamma = params["gamma"]
    tau = params["mean"] - params["beta"] * sigma / gamma
    flip = jnp.where(gamma >= 0, 1.0, -1.0)
    return {"tau": tau, "flip": flip}


def apply_bn_sign_folded(folded: Params, x_int: jax.Array) -> jax.Array:
    """±1 output of sign(BN(x)) computed as a threshold compare on the raw

    integer GEMM output — no fp normalization in the inference path."""
    ge = (x_int.astype(jnp.float32) >= folded["tau"])
    pm1 = jnp.where(ge, 1.0, -1.0) * folded["flip"]
    return pm1


def apply_bn_sign_folded_packed(folded: Params, x_int: jax.Array, *,
                                backend: str = "auto") -> jax.Array:
    """Fused sign(BN(x)) + bit-pack along the channel axis (one kernel).

    Bit-identical to ``pack_bits(apply_bn_sign_folded(folded, x))`` but
    the ±1 float activation is never materialized.  Returns
    (..., ceil(C/32)) uint32."""
    return kops.bn_sign_pack(x_int, folded["tau"], folded["flip"],
                             backend=backend)


# ---------------------------------------------------------------------------
# Pooling
# ---------------------------------------------------------------------------

def maxpool2d(x: jax.Array, window: int = 2, stride: int | None = None
              ) -> jax.Array:
    stride = stride or window
    if jnp.issubdtype(x.dtype, jnp.integer):
        init = jnp.iinfo(x.dtype).min
    else:
        init = -jnp.inf
    return jax.lax.reduce_window(
        x, init, jax.lax.max,
        window_dimensions=(1, window, window, 1),
        window_strides=(1, stride, stride, 1), padding="VALID")


def pool_flip_mask(folded: Params) -> jax.Array:
    """Packed per-channel mask of ``flip > 0`` for :func:`maxpool2d_packed`."""
    return B.pack_bits(folded["flip"])


def maxpool2d_packed(x_packed: jax.Array, flip_mask: jax.Array,
                     window: int = 2, stride: int | None = None) -> jax.Array:
    """Max-pool entirely in the packed bit domain.

    The forward order conv -> maxpool(int) -> sign(BN(·)) commutes with
    thresholding because BN-sign is monotone per channel:
    ``(max_i x_i >= tau) == OR_i (x_i >= tau)``.  After the fused epilogue
    each bit is ``(x >= tau) XNOR (flip > 0)``, so pooling the *bits* is
    OR where flip > 0 and AND where flip < 0 — two bitwise reduce_windows
    and a mask select, no unpacking.  Zero-bit channel tails stay zero
    through the AND branch because the mask is zero there too.
    """
    stride = stride or window
    dims = (1, window, window, 1)
    strides = (1, stride, stride, 1)
    any_set = jax.lax.reduce_window(x_packed, jnp.uint32(0),
                                    jax.lax.bitwise_or, dims, strides,
                                    "VALID")
    all_set = jax.lax.reduce_window(x_packed, jnp.uint32(0xFFFFFFFF),
                                    jax.lax.bitwise_and, dims, strides,
                                    "VALID")
    return (any_set & flip_mask) | (all_set & ~flip_mask)
