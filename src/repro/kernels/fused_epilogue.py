"""Pallas TPU kernel: fused BN-sign-fold + re-bitpack epilogue.

Between binary layers the inference path is  int32 GEMM/conv output ->
sign(BN(x)) -> ±1 -> bit-pack for the next packed layer.  Done naively
that round-trips every activation through HBM three times (int32 out,
float ±1, packed words).  This kernel fuses the folded-BN threshold
compare (``fold_bn_sign``: sign(BN(x)) == flip·sign(x − tau)) with the
re-bitpack, so one pass turns the raw int32 accumulator output into the
next layer's packed uint32 words.

Used standalone after layers whose producer can't fuse the epilogue
itself (the bit-plane first layer, whose int32 output accumulates over
8 plane convs, and the dense stack); the binary-conv kernel inlines the
same epilogue directly (``binary_conv.binary_conv2d_bn_sign_packed``).

Residual networks (ReActNet) keep a float32 stream beside the packed
sign: :func:`residual_epilogue` is the float math both residual kernels
(``binary_conv._conv_residual_kernel``,
``binary_matmul._pointwise_residual_kernel``) and their oracles run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import binarize as B

_LANE = 128


def check_block_lanes(name: str, value: int) -> None:
    """Reject channel-axis block sizes the TPU lane layout can't honor.

    Every channel-blocked kernel in this package tiles the minor axis in
    lane groups of 128; a user block below (or not a multiple of) that
    used to be silently clamped *up*, making the knob a no-op.  Raising
    keeps mis-tuned configs visible (tests/test_conv_properties.py).
    """
    if value < _LANE or value % _LANE != 0:
        raise ValueError(
            f"{name} must be a positive multiple of {_LANE} (TPU lane "
            f"granularity), got {value}")


def check_block_sublanes(name: str, value: int) -> None:
    """Same contract for sublane-axis (row) block sizes: multiples of 8."""
    if value < 8 or value % 8 != 0:
        raise ValueError(
            f"{name} must be a positive multiple of 8 (TPU sublane "
            f"granularity), got {value}")


def check_words_per_step(name: str, value: int) -> None:
    """Contraction-vectorization knob: packed words contracted per step.

    Must be a positive divisor of the 128-lane group so every lane-padded
    K block splits into whole steps (1, 2, 4, ..., 128).  Like the block
    knobs, invalid values raise instead of being silently adjusted
    (tests/test_dense_properties.py).
    """
    if value < 1 or _LANE % value != 0:
        raise ValueError(
            f"{name} must be a positive divisor of {_LANE} (TPU lane "
            f"granularity), got {value}")


def pack_lanes(bits: jax.Array) -> jax.Array:
    """Pack a (m, c) {0,1} tile LSB-first along the lane axis -> (m, c/32)
    uint32, inside a kernel.

    Splitting the lane axis into (c/32, 32) is a relayout Mosaic refuses,
    so the pack is two matmuls against a constant 0/2^k selection
    matrix: word j = Σ_k bit[32j+k]·2^k, low and high 16 bits apart so
    every partial sum stays below 2^16 — exact in bf16 operands with
    f32 accumulation.  ``c`` must be a multiple of 32.
    """
    c = bits.shape[1]
    cw = c // B.WORD_BITS
    row = jax.lax.broadcasted_iota(jnp.int32, (c, cw), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, cw), 1)
    bit = row % B.WORD_BITS
    own = (row // B.WORD_BITS) == col
    lhs = bits.astype(jnp.float32).astype(jnp.bfloat16)

    def half(lo: int) -> jax.Array:
        sel = own & (bit >= lo) & (bit < lo + 16)
        weights = jnp.where(sel, jnp.left_shift(1, bit - lo), 0)
        words = jnp.dot(lhs, weights.astype(jnp.float32).astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
        return words.astype(jnp.int32)

    words = half(0) | jnp.left_shift(half(16), 16)
    return jax.lax.bitcast_convert_type(words, jnp.uint32)


def bn_sign_bits_to_words(y: jax.Array, tau: jax.Array,
                          flip: jax.Array) -> jax.Array:
    """The epilogue contract, shared by every kernel that inlines it.

    bit = (y >= tau) XNOR (flip > 0): the bit encoding of
    sign(BN(y)) = flip * sign(y − tau)  (core.binary_layers.fold_bn_sign),
    packed LSB-first along the last axis.  ``y``: (m, c) with c a multiple
    of 32; ``tau``/``flip``: broadcastable (1, c).
    """
    ge = y.astype(jnp.float32) >= tau
    return pack_lanes((ge == (flip > 0)).astype(jnp.int32))


# Rows of the per-channel float32 table a residual epilogue reads
# ((8, C): one sublane group, the last two rows unused).
EPI_SCALE, EPI_SHIFT, EPI_BIAS, EPI_SLOPE, EPI_BIAS2, EPI_NEXT = range(6)
EPI_ROWS = 8


def residual_epilogue(z: jax.Array, epi: jax.Array,
                      shortcut: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The epilogue of a residual binary conv (ReActNet), shared by the
    kernels and their oracles so both do the same float32 operations in
    the same order, each rounding once:

        y  = z·scale + shift          (BN at inference, α folded in scale)
        y  = y + shortcut
        y  = y + bias                 (RPReLU: bias, PReLU, bias)
        y  = y if y > 0 else y·slope
        y  = y + bias2                -> the float stream
        u  = y + next                 -> its sign feeds the next binary conv

    ``z``: (..., C) int32 exact conv output; ``epi``: (8, C) rows
    ``EPI_*``; ``shortcut``: (..., C) float32.  Returns (y, u).
    """
    scale, shift, bias, slope, bias2, nxt = (
        epi[i:i + 1] for i in (EPI_SCALE, EPI_SHIFT, EPI_BIAS, EPI_SLOPE,
                               EPI_BIAS2, EPI_NEXT))
    y = z.astype(jnp.float32) * scale + shift
    y = y + shortcut
    y = y + bias
    y = jnp.where(y > 0, y, y * slope)
    y = y + bias2
    return y, y + nxt


def avg_pool2x2(x00: jax.Array, x01: jax.Array, x10: jax.Array,
                x11: jax.Array) -> jax.Array:
    """The stride-2 shortcut's 2x2 average in one fixed order
    (``xrc``: row r, column c of the window)."""
    return ((x00 + x01) + (x10 + x11)) * jnp.float32(0.25)


def unblock_packed(out: jax.Array) -> jax.Array:
    """(..., n_blocks, M, bw) blocked packed output -> (..., M, n_blocks·bw).

    Packed-output kernels write each C_out block's ``block_n/32`` words
    into its own slab: a (M, bw) block with bw < 128 is only legal on
    the chip when it spans the array's whole last dim.
    """
    out = jnp.moveaxis(out, -3, -2)
    return out.reshape(*out.shape[:-2], -1)


def pad_bn_params(tau: jax.Array, flip: jax.Array,
                  multiple: int) -> tuple[jax.Array, jax.Array]:
    """Pad per-channel tau/flip up to ``multiple`` so padded channels pack

    as 0-bits (the pack_bits tail convention): tau=+inf makes the compare
    False, flip=+1 makes the bit (False == True) == 0."""
    c = tau.shape[-1]
    tau_p = B.pad_to_multiple(tau.reshape(1, c).astype(jnp.float32),
                              multiple, 1, value=jnp.float32(jnp.inf))
    flip_p = B.pad_to_multiple(flip.reshape(1, c).astype(jnp.float32),
                               multiple, 1, value=1.0)
    return tau_p, flip_p


def _bn_sign_pack_kernel(x_ref, tau_ref, flip_ref, o_ref):
    o_ref[...] = bn_sign_bits_to_words(x_ref[...], tau_ref[...],
                                       flip_ref[...])


@functools.partial(jax.jit, static_argnames=("block_m", "block_cw",
                                             "interpret"))
def bn_sign_pack(x: jax.Array, tau: jax.Array, flip: jax.Array, *,
                 block_m: int = 256, block_cw: int = _LANE,
                 interpret: bool = False) -> jax.Array:
    """Fused sign(BN(x)) + bit-pack: (M, C) int32 -> (M, ceil(C/32)) uint32.

    ``tau``/``flip``: per-channel folded BN threshold and sign flip.
    Bit-identical to ``pack_bits(apply_bn_sign_folded({tau, flip}, x))``.
    Channels padded up to the block pack as 0-bits (tau=+inf, flip=+1),
    matching the ``pack_bits`` zero-bit tail convention.
    """
    m, c = x.shape
    cw = B.packed_width(c)

    check_block_sublanes("block_m", block_m)
    block_m = min(block_m, _ceil_mult(m, 8))
    check_block_lanes("block_cw", block_cw)
    # Trim to the packed width rounded up to one 128-lane input group:
    # the output block is then either the whole packed width or a
    # multiple of 128 words, both legal on the chip.
    block_cw = min(block_cw, _ceil_mult(cw, _LANE // B.WORD_BITS))
    block_c = block_cw * B.WORD_BITS

    x_p = B.pad_to_multiple(B.pad_to_multiple(x, block_c, 1), block_m, 0)
    tau_p, flip_p = pad_bn_params(tau, flip, block_c)
    mp, cp = x_p.shape
    grid = (mp // block_m, cp // block_c)

    out = pl.pallas_call(
        _bn_sign_pack_kernel,
        name="_bn_sign_pack_kernel",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_c), lambda i, j: (i, j)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_c), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_cw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, cp // B.WORD_BITS), jnp.uint32),
        interpret=interpret,
    )(x_p, tau_p, flip_p)
    return out[:m, :cw]


def _ceil_mult(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
