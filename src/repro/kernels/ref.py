"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``*_ref`` function defines the exact semantics a kernel must match
bit-for-bit (integer outputs) or to float tolerance (float outputs).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import binarize as B
from repro.kernels.fused_epilogue import avg_pool2x2, residual_epilogue


def binary_matmul_ref(a: jax.Array, b: jax.Array) -> jax.Array:
    """Reference binary GEMM on *real-valued* operands.

    ``a``: (M, K), ``b``: (N, K) — any real dtype.  Both are sign-binarized
    to ±1 and contracted exactly: out[m, n] = sign(a[m]) . sign(b[n]).
    Returns (M, N) int32.
    """
    a_b = B.sign_pm1(a.astype(jnp.float32))
    b_b = B.sign_pm1(b.astype(jnp.float32))
    return jnp.dot(a_b, b_b.T).astype(jnp.int32)


def binary_matmul_packed_ref(a_packed: jax.Array, b_packed: jax.Array,
                             k: int) -> jax.Array:
    """Reference packed binary GEMM (paper eq. 2) — XOR + popcount form."""
    return B.packed_matmul(a_packed, b_packed, k)


def bitpack_ref(x: jax.Array) -> jax.Array:
    """Reference sign-binarize + pack along last axis -> uint32 words."""
    return B.pack_bits(x)


def binary_matmul_bn_sign_packed_ref(a_packed: jax.Array,
                                     b_packed: jax.Array, tau: jax.Array,
                                     flip: jax.Array, k: int) -> jax.Array:
    """Reference fused dense epilogue: packed GEMM, then BN-sign + pack."""
    return bn_sign_pack_ref(B.packed_matmul(a_packed, b_packed, k), tau,
                            flip)


def binary_dense_stack_packed_ref(stages: list,
                                  x_packed: jax.Array) -> jax.Array:
    """Reference hidden dense stack: per-layer fused epilogue, chained.

    Defines the exact semantics of the single-launch stack kernel
    (``binary_matmul.binary_dense_stack_packed``) AND its per-layer
    fallback — both must match it bit-for-bit.
    """
    h = x_packed
    for s in stages:
        h = binary_matmul_bn_sign_packed_ref(h, s["w_packed"], s["tau"],
                                             s["flip"], s["k_true"])
    return h


def bitplane_dot_ref(x_uint8: jax.Array, w: jax.Array) -> jax.Array:
    """Reference first-layer bit-plane dot == exact integer GEMM."""
    return jnp.dot(x_uint8.astype(jnp.int32),
                   B.sign_pm1(w.astype(jnp.float32)).astype(jnp.int32).T)


# ---------------------------------------------------------------------------
# Binary conv2d (paper C5/C6) — the jnp backend AND the kernel oracle.
# This path im2cols *outside* the kernel, materializing the full
# (B·H'·W', KH·KW·Cw) patch matrix — exactly what the Pallas conv kernel
# (kernels/binary_conv.py) exists to avoid.
# ---------------------------------------------------------------------------

def extract_patches_packed(x_packed: jax.Array, kh: int, kw: int,
                           stride: int, pads) -> jax.Array:
    """im2col over channel-packed words (free-lift layout, paper C3/C6).

    ``x_packed``: (B, H, W, Cw) uint32.  Spatial zero-word padding encodes
    all-(−1) pixels — the paper's "treat pad as −1" convention.
    Returns (B, H', W', KH*KW*Cw).
    """
    xp = jnp.pad(x_packed, ((0, 0), pads[0], pads[1], (0, 0)),
                 constant_values=0)                    # 0-words == all -1
    bsz, hp, wp, cw = xp.shape
    out_h = (hp - kh) // stride + 1
    out_w = (wp - kw) // stride + 1
    cols = []
    for di in range(kh):
        for dj in range(kw):
            sl = xp[:, di:di + out_h * stride:stride,
                    dj:dj + out_w * stride:stride, :]
            cols.append(sl)
    return jnp.concatenate(cols, axis=-1)


def binary_conv2d_packed_ref(x_packed: jax.Array, w_packed: jax.Array,
                             correction: jax.Array, *, kh: int, kw: int,
                             stride: int, pads, c_out: int,
                             k_true: int) -> jax.Array:
    """Reference packed conv: im2col -> XNOR GEMM -> +correction (int32)."""
    patches = extract_patches_packed(x_packed, kh, kw, stride, pads)
    bsz, oh, ow, kcw = patches.shape
    flat = patches.reshape(bsz * oh * ow, kcw)
    out = B.packed_matmul(flat, w_packed, k_true)
    out = out.reshape(bsz, oh, ow, c_out)
    return out + correction[None]


def bitplane_dense_packed_ref(x_uint8: jax.Array, w_words: jax.Array,
                              rowsum: jax.Array, *, k_true: int,
                              nbits: int) -> jax.Array:
    """Reference first-layer dense (paper C4): the SEQUENTIAL plane loop.

    One packed GEMM per bit plane (plane bit b -> ±1 via 2b−1) against
    the word-major weights ``w_words`` (Kw, N), recombined with
    x·w = 1/2 Σ_i 2^i (p̂_i ⊙ w + rowsum).  The single-launch Pallas
    kernel (``binary_matmul.bitplane_dense_packed``) must match it
    bit-for-bit, and both equal x.int32 @ sign(W)^T.
    """
    acc = None
    for i in range(nbits):
        plane = ((x_uint8.astype(jnp.uint32) >> i) & 1)
        xp = B.pack_bits(2.0 * plane.astype(jnp.float32) - 1.0)
        d = B.packed_matmul(xp, w_words.T, k_true)
        term = (d + rowsum[None, :]) << i
        acc = term if acc is None else acc + term
    return acc >> 1


def bitplane_conv2d_packed_ref(x_uint8: jax.Array, w_packed: jax.Array,
                               rowsum: jax.Array, *, kh: int, kw: int,
                               stride: int, pads, c_out: int, k_true: int,
                               nbits: int) -> jax.Array:
    """Reference first-layer conv (paper C4): the 8-plane SEQUENTIAL path.

    One packed conv per bit plane (plane bit b -> ±1 via 2b−1), recombined
    with the plane identity  x·w = 1/2 Σ_i 2^i (p̂_i ⊛ w + rowsum)  where
    the all-taps ``rowsum`` absorbs both the {0,1}->±1 shift and the
    zero-pad correction (pad pixels have every plane bit 0 == −1).  This
    is exactly what the model ran pre-fusion — the single-launch Pallas
    kernel (``binary_conv.bitplane_conv2d_packed``) must match it
    bit-for-bit, and both equal the integer conv of the raw input.
    """
    acc = None
    zero_corr = None
    for i in range(nbits):
        plane = ((x_uint8.astype(jnp.uint32) >> i) & 1)
        plane_pm1 = 2.0 * plane.astype(jnp.float32) - 1.0
        xp = B.pack_bits(plane_pm1)
        if zero_corr is None:
            patches = extract_patches_packed(xp, kh, kw, stride, pads)
            zero_corr = jnp.zeros(patches.shape[1:3] + (c_out,), jnp.int32)
        d = binary_conv2d_packed_ref(xp, w_packed, zero_corr, kh=kh, kw=kw,
                                     stride=stride, pads=pads, c_out=c_out,
                                     k_true=k_true)
        term = (d + rowsum[None, None, None, :]) << i
        acc = term if acc is None else acc + term
    return acc >> 1


def bn_sign_pack_ref(x: jax.Array, tau: jax.Array,
                     flip: jax.Array) -> jax.Array:
    """Reference fused BN-sign + pack: threshold to ±1, then bit-pack."""
    ge = x.astype(jnp.float32) >= tau
    pm1 = jnp.where(ge, 1.0, -1.0) * flip
    return B.pack_bits(pm1)


def binary_conv2d_bn_sign_packed_ref(x_packed: jax.Array,
                                     w_packed: jax.Array,
                                     correction: jax.Array, tau: jax.Array,
                                     flip: jax.Array, *, kh: int, kw: int,
                                     stride: int, pads, c_out: int,
                                     k_true: int) -> jax.Array:
    """Reference fused conv epilogue: conv, then BN-sign + re-bitpack."""
    y = binary_conv2d_packed_ref(x_packed, w_packed, correction, kh=kh,
                                 kw=kw, stride=stride, pads=pads,
                                 c_out=c_out, k_true=k_true)
    return bn_sign_pack_ref(y, tau, flip)


def binary_conv2d_residual_ref(x_packed: jax.Array, w_taps: jax.Array,
                               correction: jax.Array, epi: jax.Array,
                               shortcut: jax.Array, *, kh: int, kw: int,
                               stride: int, pads, c_out: int,
                               k_true: int) -> tuple[jax.Array, jax.Array]:
    """Reference residual conv: im2col packed conv + correction, the
    stride-2 shortcut's 2x2 average, then the shared residual epilogue;
    returns (float32 stream, packed sign of stream + next bias)."""
    w_packed = w_taps.transpose(2, 0, 1).reshape(c_out, -1)
    z = binary_conv2d_packed_ref(x_packed, w_packed, correction, kh=kh,
                                 kw=kw, stride=stride, pads=pads,
                                 c_out=c_out, k_true=k_true)
    if stride == 2:
        s = shortcut
        shortcut = avg_pool2x2(s[:, 0::2, 0::2], s[:, 0::2, 1::2],
                               s[:, 1::2, 0::2], s[:, 1::2, 1::2])
    y, u = residual_epilogue(z, epi, shortcut)
    return y, B.pack_bits(u)


def pointwise_residual_ref(x_packed: jax.Array, w_words: jax.Array,
                           epi: jax.Array, shortcut: jax.Array, *,
                           k_true: int) -> tuple[jax.Array, jax.Array]:
    """Reference residual 1x1 conv over pixel rows: packed GEMM against
    the word-major weights, the shortcut added to every C-channel group
    of the output, the shared residual epilogue; returns (float32
    stream, packed sign of stream + next bias)."""
    z = B.packed_matmul(x_packed, w_words.T, k_true)
    sc = jnp.tile(shortcut, (1, w_words.shape[1] // shortcut.shape[1]))
    y, u = residual_epilogue(z, epi, sc)
    return y, B.pack_bits(u)


def binary_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: int | None = None,
                         attn_softcap: float | None = None,
                         q_offset: int = 0) -> jax.Array:
    """Reference binary attention (the ``binary_attention`` oracle).

    ``q``: (B, Sq, Hq, D), ``k``: (B, Skv, Hkv, D), ``v``:
    (B, Skv, Hkv, Dv) — real-valued.  Q and K are sign-binarized to ±1
    (so q·k == D − 2·mismatches, the XNOR-popcount identity the kernel
    computes on packed words), scaled by D^(−1/2), optionally
    soft-capped, masked (causal keeps qpos ≥ kpos with ``q_offset``
    aligning decode queries; ``window`` keeps qpos − kpos < window),
    softmaxed *exactly* (one pass, not the online recurrence), and
    averaged against the real-valued V.  GQA: query head h attends KV
    head h // (Hq // Hkv).  Returns (B, Sq, Hq, Dv) float32 — the
    kernel matches to float tolerance (the integer score path is
    bit-exact; only the softmax association order differs).
    """
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    assert hkv >= 1 and hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    qb = B.sign_pm1(q.astype(jnp.float32))
    kb = jnp.repeat(B.sign_pm1(k.astype(jnp.float32)), g, axis=2)
    vf = jnp.repeat(v.astype(jnp.float32), g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb) * jnp.float32(d) ** -0.5
    if attn_softcap is not None:
        s = attn_softcap * jnp.tanh(s / attn_softcap)
    qpos = q_offset + jnp.arange(sq)[:, None]
    kpos = jnp.arange(skv)[None, :]
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask = mask & (qpos >= kpos)
    if window is not None:
        mask = mask & (qpos - kpos < window)
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vf)
