"""ReActNet-A (Liu et al., ECCV 2020, arXiv:2003.03488) as a packed
binary network, layer for layer as the ``reactnet`` model of
github.com/liuzechun/ReActNet (``mobilenet/reactnet.py``):

* stem: a real-valued 3x3/2 conv ``c_in -> stage_out_channel[0]`` and
  batch norm, no activation;
* one block per later entry of ``stage_out_channel``, stride 2 where the
  width changes to anything but 64:

      out1 = RPReLU1(BN1(α·(sign(x + b11) ⊛3x3 sign W)) + shortcut(x))
      out  = RPReLU2(BN2(α·(sign(out1 + b21) ⊛1x1 sign W)) + out1)

  with the shortcut a 2x2 average pool at stride 2, α the mean of |W|
  per output channel, RPReLU bias -> PReLU -> bias per channel, and,
  where the width doubles, two 1x1 convs C -> C that each add ``out1``,
  concatenated;
* head: a global average pool and a real-valued fully connected layer.

Batch norm is written in its folded inference form (a per-channel scale
and shift).  ``reactnet_forward_float`` is the plain float32 reference;
``pack_reactnet`` / ``reactnet_forward_packed`` keep a float32 stream
beside the packed sign and run every block half as one kernel launch
(``kernels.ops.binary_conv2d_residual``, ``kernels.ops.
pointwise_residual``); the stem and head run in XLA in float32.

The two agree bit for bit in the stream when the weights keep every
product exact (``init_reactnet``): |W| a power of two per output channel
(so α is exact), binary-conv BN scales of at most 8 significant bits
(z·s exact for |z| < 2^14), PReLU slopes powers of two, stem weights on
a 2^-7 grid with a power-of-two BN scale.  Then the program and the
reference do the same float32 operations in the same order, each
rounding once (``kernels.fused_epilogue.residual_epilogue``).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import binarize as B
from repro.kernels import binary_conv as BC
from repro.kernels import fused_epilogue as FE
from repro.kernels import ops as kops

_HIGHEST = jax.lax.Precision.HIGHEST
_NHWC = ("NHWC", "HWIO", "NHWC")
_PADS = ((1, 1), (1, 1))


@dataclass(frozen=True)
class ReActNetSpec:
    input_hw: tuple[int, int] = (224, 224)
    c_in: int = 3
    stage_out_channel: tuple[int, ...] = (
        32, 64, 128, 128, 256, 256, 512, 512, 512, 512, 512, 512,
        1024, 1024)
    num_classes: int = 1000
    nbits_input: int = 8

    def __post_init__(self):
        c = self.stage_out_channel
        for a, b in zip(c[:-1], c[1:]):
            if b not in (a, 2 * a) or b % B.WORD_BITS:
                raise ValueError(
                    f"each block keeps or doubles a width that is a "
                    f"multiple of 32; got {a} -> {b}")
        for bs in block_shapes(self):
            if bs.stride == 2 and (bs.in_hw[0] % 2 or bs.in_hw[1] % 2):
                raise ValueError(f"a stride-2 block's 2x2 average needs an "
                                 f"even input, got {bs.in_hw}")


@dataclass(frozen=True)
class BlockShape:
    c_in: int
    c_out: int
    stride: int
    in_hw: tuple[int, int]


def block_shapes(spec) -> list[BlockShape]:
    """Each block's widths, stride and input size, by the published
    rule: stride 2 where the width changes to anything but 64."""
    h, w = (-(-x // 2) for x in spec.input_hw)       # after the 3x3/2 stem
    c = spec.stage_out_channel
    out = []
    for a, b in zip(c[:-1], c[1:]):
        stride = 2 if a != b and b != 64 else 1
        out.append(BlockShape(a, b, stride, (h, w)))
        h, w = h // stride, w // stride
    return out


# ---------------------------------------------------------------------------
# Weights that keep every product exact
# ---------------------------------------------------------------------------

def _pow2(key, shape, lo: int, hi: int) -> jax.Array:
    return jnp.exp2(jax.random.randint(key, shape, lo, hi + 1)
                    .astype(jnp.float32))


def _signs(key, shape) -> jax.Array:
    return jnp.where(jax.random.bernoulli(key, 0.5, shape), 1.0, -1.0)


def _binary_weight(key, shape) -> jax.Array:
    """±|W| with |W| a power of two per output channel (axis 0)."""
    k1, k2 = jax.random.split(key)
    mag = _pow2(k2, (shape[0],), -3, 0)
    return _signs(k1, shape) * mag.reshape(-1, *(1,) * (len(shape) - 1))


def _bn8(key, c: int, k_true: int) -> dict:
    """Folded batch norm: a scale of 8 significant bits near 1/sqrt(K)
    and a generic float32 shift."""
    k1, k2, k3 = jax.random.split(key, 3)
    mant = jax.random.randint(k1, (c,), 128, 256).astype(jnp.float32)
    e = -8 - int(np.ceil(np.log2(np.sqrt(k_true))))
    return {"scale": _signs(k2, (c,)) * mant * jnp.float32(2.0 ** e),
            "shift": 0.5 * jax.random.normal(k3, (c,))}


def _bias(key, c: int) -> jax.Array:
    return 0.2 * jax.random.normal(key, (c,))


def init_reactnet(key: jax.Array, spec: ReActNetSpec) -> dict:
    """Seeded weights in the layout ``pack_reactnet`` takes; conv weights
    (C_out, KH, KW, C_in), 1x1 weights (C_out, C_in), one per conv of a
    block (two where the width doubles)."""
    c0 = spec.stage_out_channel[0]
    ks = jax.random.split(key, 4)
    stem_w = jnp.round(jax.random.uniform(
        ks[0], (c0, 3, 3, spec.c_in), minval=-1.0, maxval=1.0) * 128) / 128
    stem = {"w": stem_w, "scale": _signs(ks[1], (c0,)) * _pow2(
        ks[2], (c0,), -10, -9), "shift": 0.5 * jax.random.normal(ks[3], (c0,))}
    blocks = []
    for i, bs in enumerate(block_shapes(spec)):
        c, p = bs.c_in, bs.c_out
        k = jax.random.split(jax.random.fold_in(key, 1 + i), 16)
        halves = p // c
        blocks.append({
            "move11": _bias(k[0], c),
            "w3": _binary_weight(k[1], (c, 3, 3, c)),
            "bn1": _bn8(k[2], c, 9 * c),
            "move12": _bias(k[3], c),
            "prelu1": _pow2(k[4], (c,), -3, -1),
            "move13": _bias(k[5], c),
            "move21": _bias(k[6], c),
            "pw": [_binary_weight(k[7 + h], (c, c)) for h in range(halves)],
            "bn2": [_bn8(k[9 + h], c, c) for h in range(halves)],
            "move22": _bias(k[11], p),
            "prelu2": _pow2(k[12], (p,), -3, -1),
            "move23": _bias(k[13], p),
        })
    kf = jax.random.split(jax.random.fold_in(key, 0), 2)
    c_last = spec.stage_out_channel[-1]
    fc = {"w": jax.random.uniform(kf[0], (spec.num_classes, c_last),
                                  minval=-1.0, maxval=1.0) / 32,
          "b": 0.1 * jax.random.normal(kf[1], (spec.num_classes,))}
    return {"stem": stem, "blocks": blocks, "fc": fc}


# ---------------------------------------------------------------------------
# Plain float32 reference
# ---------------------------------------------------------------------------

def _sign(x: jax.Array) -> jax.Array:
    return jnp.where(x >= 0, 1.0, -1.0).astype(x.dtype)


def _alpha(w: jax.Array) -> jax.Array:
    """HardBinaryConv's scale: mean |W| per output channel (axis 0)."""
    return jnp.mean(jnp.abs(w), axis=tuple(range(1, w.ndim)))


def _alpha_sign(w: jax.Array) -> jax.Array:
    """HardBinaryConv's weights: α x sign W."""
    return _alpha(w).reshape(-1, *(1,) * (w.ndim - 1)) * _sign(w)


def _rprelu(x, b1, a, b2):
    y = x + b1
    return jnp.where(y > 0, y, y * a) + b2


def _avg_pool2(x):
    return ((x[:, 0::2, 0::2] + x[:, 0::2, 1::2]) +
            (x[:, 1::2, 0::2] + x[:, 1::2, 1::2])) * jnp.float32(0.25)


def stem_float(p: dict, x_uint8: jax.Array) -> jax.Array:
    """The real-valued 3x3/2 stem conv and its batch norm (the packed
    forward runs it too)."""
    z = jax.lax.conv_general_dilated(
        x_uint8.astype(jnp.float32), jnp.transpose(p["w"], (1, 2, 3, 0)),
        (2, 2), _PADS, dimension_numbers=_NHWC, precision=_HIGHEST)
    return z * p["scale"] + p["shift"]


def block_float(p: dict, x: jax.Array, stride: int) -> jax.Array:
    """One ReActNet block on the float stream ``x`` (B, H, W, C)."""
    out1 = jax.lax.conv_general_dilated(
        _sign(x + p["move11"]),
        jnp.transpose(_alpha_sign(p["w3"]), (1, 2, 3, 0)),
        (stride, stride), _PADS, dimension_numbers=_NHWC,
        precision=_HIGHEST)
    out1 = out1 * p["bn1"]["scale"] + p["bn1"]["shift"]
    if stride == 2:
        x = _avg_pool2(x)
    out1 = _rprelu(out1 + x, p["move12"], p["prelu1"], p["move13"])
    s = _sign(out1 + p["move21"])
    outs = [jnp.dot(s, _alpha_sign(w).T, precision=_HIGHEST) * bn["scale"]
            + bn["shift"] + out1 for w, bn in zip(p["pw"], p["bn2"])]
    return _rprelu(jnp.concatenate(outs, axis=-1), p["move22"],
                   p["prelu2"], p["move23"])


def head_float(p: dict, x: jax.Array) -> jax.Array:
    """Global average pool, then the real-valued classifier (the packed
    forward runs it too)."""
    return jnp.dot(jnp.mean(x, axis=(1, 2)), p["w"].T,
                   precision=_HIGHEST) + p["b"]


def reactnet_forward_float(params: dict, x_uint8: jax.Array,
                           spec: ReActNetSpec) -> jax.Array:
    """Reference forward: logits of ``x_uint8`` (B, H, W, C_in)."""
    h = stem_float(params["stem"], x_uint8)
    for p, bs in zip(params["blocks"], block_shapes(spec)):
        h = block_float(p, h, bs.stride)
    return head_float(params["fc"], h)


# ---------------------------------------------------------------------------
# Packed inference
# ---------------------------------------------------------------------------

def _epilogue_rows(scale, shift, bias, slope, bias2, nxt) -> jax.Array:
    """The (8, C) float32 table of ``fused_epilogue.residual_epilogue``."""
    rows = jnp.stack([scale, shift, bias, slope, bias2, nxt])
    pad = jnp.zeros((FE.EPI_ROWS - rows.shape[0], rows.shape[1]))
    return jnp.concatenate([rows, pad]).astype(jnp.float32)


def pack_reactnet(params: dict, spec: ReActNetSpec) -> dict:
    """One-time packing: per block a residual conv plan and a
    word-major 1x1 weight matrix (the two convs of a doubling block
    stacked along N), each with its epilogue rows; α folded into the BN
    scales; each block's last epilogue adds the next block's sign bias
    (zero after the last block)."""
    shapes = block_shapes(spec)
    blocks = params["blocks"]
    nxt = [b["move11"] for b in blocks[1:]]
    nxt.append(jnp.zeros((shapes[-1].c_out,)))
    res_blocks = []
    for p, bs, b_next in zip(blocks, shapes, nxt):
        plan = BC.make_residual_conv_plan(p["w3"], input_hw=bs.in_hw,
                                          stride=bs.stride)
        epi1 = _epilogue_rows(_alpha(p["w3"]) * p["bn1"]["scale"],
                              p["bn1"]["shift"], p["move12"], p["prelu1"],
                              p["move13"], p["move21"])
        w_pw = jnp.concatenate(p["pw"])                     # (N, C)
        scale2 = jnp.concatenate([_alpha(w) * bn["scale"]
                                  for w, bn in zip(p["pw"], p["bn2"])])
        shift2 = jnp.concatenate([bn["shift"] for bn in p["bn2"]])
        epi2 = _epilogue_rows(scale2, shift2, p["move22"], p["prelu2"],
                              p["move23"], b_next)
        res_blocks.append({
            "conv": plan, "epi1": epi1,
            "w_pw": B.pack_bits(B.sign_pm1(w_pw)).T, "k_pw": bs.c_in,
            "epi2": epi2})
    return {"stem": dict(params["stem"], next=blocks[0]["move11"]),
            "res_blocks": res_blocks, "head": params["fc"], "spec": spec}


def stem_packed(st: dict, x_uint8: jax.Array) -> tuple[jax.Array,
                                                       jax.Array]:
    """The stem in XLA: the float stream and its packed sign plus the
    first block's bias.  The barrier keeps XLA from folding the BN shift
    and that bias into one constant."""
    h = jax.lax.optimization_barrier(stem_float(st, x_uint8))
    return h, B.pack_bits(h + st["next"])


def block_packed(blk: dict, h: jax.Array, hp: jax.Array, *,
                 backend: str = "auto") -> tuple[jax.Array, jax.Array]:
    """One block on the float stream ``h`` and its packed sign ``hp``:
    two kernel launches, each writing both."""
    out1, p1 = kops.binary_conv2d_residual(blk["conv"], blk["epi1"], hp, h,
                                           backend=backend)
    bsz, oh, ow, c = out1.shape
    y, yp = kops.pointwise_residual(
        blk["w_pw"], blk["epi2"], p1.reshape(bsz * oh * ow, -1),
        out1.reshape(bsz * oh * ow, c), k_true=blk["k_pw"], backend=backend)
    return (y.reshape(bsz, oh, ow, -1), yp.reshape(bsz, oh, ow, -1))


def reactnet_forward_packed(packed: dict, x_uint8: jax.Array, *,
                            backend: str = "auto") -> jax.Array:
    """Optimized forward: stem in XLA, then per block one residual conv
    launch and one residual 1x1 launch, each reading the float stream
    and the packed sign and writing both; the head in XLA."""
    h, hp = stem_packed(packed["stem"], x_uint8)
    for blk in packed["res_blocks"]:
        h, hp = block_packed(blk, h, hp, backend=backend)
    return head_float(packed["head"], h)
