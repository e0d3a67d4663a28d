"""Plain float reference of ReActNet-A (Liu et al., ECCV 2020,
arXiv:2003.03488), layer for layer as the ``reactnet`` model of
github.com/liuzechun/ReActNet, ``mobilenet/reactnet.py``.

224x224x3 -> firstconv3x3 (3x3/2 conv 3 -> 32, BN) -> 13 BasicBlocks
over ``stage_out_channel`` -> AdaptiveAvgPool2d(1) -> Linear(1024, 1000).
A block (stride 2 where the width changes to anything but 64):

    out1 = move13(prelu1(move12(bn1(binary_3x3(sign(move11(x)))) + pool(x))))
    out2 = sign(move21(out1))
    out2 = bn2(binary_pw(out2)) + out1                      (same width)
         | cat(bn2_1(pw_down1(out2)) + out1,
               bn2_2(pw_down2(out2)) + out1)                (width doubles)
    out  = move23(prelu2(move22(out2)))

with ``binary_*`` HardBinaryConv (weights mean|W| per output channel x
sign W, no bias), ``pool`` a 2x2 average at stride 2, ``move*`` a
per-channel bias and ``prelu*`` a per-channel PReLU.  Plain
``jax.numpy``; imports nothing of the program under test.

Departures from the source, in form only: batch norm is written in its
folded inference form (a per-channel scale and shift); the 2x2 average
adds ((x00 + x01) + (x10 + x11)) and then multiplies by 0.25, one fixed
order; input normalization is folded into the stem's weights, which act
on the raw uint8 pixels; sign(0) is +1, where torch.sign(0) is 0 (the
biases are generic floats, so no sign input is exactly 0).

Also here: the weights the benchmark serves (``init_params``, in the
layout the program's packer takes) and the work of one flush by kernel
family (``work``), both computed from the configuration's sizes.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NHWC = ("NHWC", "HWIO", "NHWC")
PADS = ((1, 1), (1, 1))


def _blocks(cfg: dict) -> list[tuple[int, int, int, int, int]]:
    """(c_in, c_out, stride, h, w) of each block, h and w its input."""
    h, w = ((x + 1) // 2 for x in cfg["input_hw"])
    c = cfg["stage_out_channel"]
    out = []
    for a, b in zip(c[:-1], c[1:]):
        stride = 2 if a != b and b != 64 else 1
        out.append((a, b, stride, h, w))
        h, w = h // stride, w // stride
    return out


# -- the weights the benchmark serves ----------------------------------------

def _pow2(key, shape, lo, hi):
    return jnp.exp2(jax.random.randint(key, shape, lo, hi + 1)
                    .astype(jnp.float32))


def _pm(key, shape):
    return jnp.where(jax.random.bernoulli(key, 0.5, shape), 1.0, -1.0)


def _binary_w(key, shape):
    """sign x |W|, |W| a power of two per output channel: alpha exact."""
    k1, k2 = jax.random.split(key)
    mag = _pow2(k2, (shape[0],), -3, 0).reshape(-1, *(1,) * (len(shape) - 1))
    return _pm(k1, shape) * mag


def _bn(key, c, k_true):
    """Folded BN: scales of 8 significant bits near 1/sqrt(K), so that
    alpha x integer conv x scale is exact; generic float32 shifts."""
    k1, k2, k3 = jax.random.split(key, 3)
    mant = jax.random.randint(k1, (c,), 128, 256).astype(jnp.float32)
    e = -8 - math.ceil(math.log2(math.sqrt(k_true)))
    return {"scale": _pm(k2, (c,)) * mant * 2.0 ** e,
            "shift": 0.5 * jax.random.normal(k3, (c,))}


def init_params(cfg: dict, seed: int) -> dict:
    """Weights on the device, in one jitted call: binary conv weights
    (C_out, KH, KW, C_in) and 1x1 weights (C_out, C_in), one per conv of
    a block (two where the width doubles); the stem's on a 2^-7 grid
    with a power-of-two BN scale; PReLU slopes powers of two; biases
    and shifts generic."""
    c0 = cfg["stage_out_channel"][0]
    blocks = _blocks(cfg)

    def build(key):
        ks = jax.random.split(jax.random.fold_in(key, 0), 6)
        stem = {"w": jnp.round(jax.random.uniform(
                    ks[0], (c0, 3, 3, cfg["c_in"]), minval=-1.0,
                    maxval=1.0) * 128) / 128,
                "scale": _pm(ks[1], (c0,)) * _pow2(ks[2], (c0,), -10, -9),
                "shift": 0.5 * jax.random.normal(ks[3], (c0,))}
        out = []
        for i, (c, p, _, _, _) in enumerate(blocks):
            k = jax.random.split(jax.random.fold_in(key, 1 + i), 16)
            halves = p // c
            bias = [0.2 * jax.random.normal(k[j], (c if j < 7 else p,))
                    for j in (0, 3, 5, 6, 11, 13)]
            out.append({
                "move11": bias[0], "move12": bias[1], "move13": bias[2],
                "move21": bias[3], "move22": bias[4], "move23": bias[5],
                "w3": _binary_w(k[1], (c, 3, 3, c)),
                "bn1": _bn(k[2], c, 9 * c),
                "prelu1": _pow2(k[4], (c,), -3, -1),
                "pw": [_binary_w(k[7 + h], (c, c)) for h in range(halves)],
                "bn2": [_bn(k[9 + h], c, c) for h in range(halves)],
                "prelu2": _pow2(k[12], (p,), -3, -1)})
        c_last = cfg["stage_out_channel"][-1]
        fc = {"w": jax.random.uniform(ks[4], (cfg["num_classes"], c_last),
                                      minval=-1.0, maxval=1.0) / 32,
              "b": 0.1 * jax.random.normal(ks[5], (cfg["num_classes"],))}
        return {"stem": stem, "blocks": out, "fc": fc}

    return jax.jit(build)(jax.random.PRNGKey(seed))


# -- the forward ----------------------------------------------------------------

def _sign(x):
    return jnp.where(x >= 0, 1.0, -1.0).astype(x.dtype)


def _hard_binary(w, dtype):
    alpha = jnp.mean(jnp.abs(w), axis=tuple(range(1, w.ndim)), keepdims=True)
    return (alpha * _sign(w)).astype(dtype)


def _conv(x, w_ohwi, stride, dtype):
    return jax.lax.conv_general_dilated(
        x, jnp.transpose(w_ohwi, (1, 2, 3, 0)), (stride, stride), PADS,
        dimension_numbers=NHWC, preferred_element_type=dtype)


def _affine(z, bn, dtype):
    return z * bn["scale"].astype(dtype) + bn["shift"].astype(dtype)


def _rprelu(x, b1, a, b2, dtype):
    y = x + b1.astype(dtype)
    return jnp.where(y > 0, y, y * a.astype(dtype)) + b2.astype(dtype)


def _pool(x):
    return ((x[:, 0::2, 0::2] + x[:, 0::2, 1::2]) +
            (x[:, 1::2, 0::2] + x[:, 1::2, 1::2])) * jnp.asarray(0.25, x.dtype)


def forward(params: dict, x_uint8, cfg: dict, dtype=jnp.float32):
    """Logits of ``x_uint8`` (B, H, W, C).  Every product, sum and batch
    norm is computed in ``dtype``; the caller sets the matmul
    precision."""
    st = params["stem"]
    x = _affine(_conv(x_uint8.astype(dtype), st["w"].astype(dtype), 2,
                      dtype), st, dtype)
    for p, (_, _, stride, _, _) in zip(params["blocks"], _blocks(cfg)):
        out1 = _affine(_conv(_sign(x + p["move11"].astype(dtype)),
                             _hard_binary(p["w3"], dtype), stride, dtype),
                       p["bn1"], dtype)
        if stride == 2:
            x = _pool(x)
        out1 = _rprelu(out1 + x, p["move12"], p["prelu1"], p["move13"],
                       dtype)
        s = _sign(out1 + p["move21"].astype(dtype))
        outs = [_affine(jnp.dot(s, _hard_binary(w, dtype).T,
                                preferred_element_type=dtype), bn, dtype)
                + out1 for w, bn in zip(p["pw"], p["bn2"])]
        x = _rprelu(jnp.concatenate(outs, axis=-1), p["move22"],
                    p["prelu2"], p["move23"], dtype)
    fc = params["fc"]
    return jnp.dot(jnp.mean(x, axis=(1, 2)), fc["w"].T.astype(dtype),
                   preferred_element_type=dtype) + fc["b"].astype(dtype)


# -- the work of a flush ------------------------------------------------------

def _family_macs(cfg: dict) -> dict[str, int]:
    c0 = cfg["stage_out_channel"][0]
    h0, w0 = ((x + 1) // 2 for x in cfg["input_hw"])
    conv = h0 * w0 * 9 * cfg["c_in"] * c0
    pw = cfg["stage_out_channel"][-1] * cfg["num_classes"]
    for c, p, stride, h, w in _blocks(cfg):
        oh, ow = h // stride, w // stride
        conv += oh * ow * 9 * c * c
        pw += oh * ow * c * p
    return {"residual_conv": conv, "pointwise": pw}


def macs_per_image(cfg: dict) -> int:
    return sum(_family_macs(cfg).values())


def work(cfg: dict, rows: int) -> dict[str, tuple[int, int]]:
    """(operations, least bytes) of one flush of ``rows`` rows, by
    kernel family.  Operations are 2 x the float network's MACs.  Bytes
    are the least any implementation moves: 1-bit binary weights and
    4-byte real-valued ones (stem, classifier), the packed input of
    each binary conv (uint8 pixels for the stem), the float32 shortcut
    each residual conv reads, the float32 stream and the packed sign
    each writes, and 4-byte logits.  The stem goes with the residual
    convs, the classifier with the 1x1 convs."""
    c0 = cfg["stage_out_channel"][0]
    h, w = cfg["input_hw"]
    h0, w0 = (h + 1) // 2, (w + 1) // 2
    conv_b = (rows * h * w * cfg["c_in"] + 9 * cfg["c_in"] * c0 * 4
              + rows * h0 * w0 * c0 * (4 + 1 / 8))
    n = cfg["num_classes"]
    pw_b = (cfg["stage_out_channel"][-1] + 1) * n * 4 + rows * n * 4
    for c, p, stride, h, w in _blocks(cfg):
        oh, ow = h // stride, w // stride
        conv_b += (9 * c * c / 8 + rows * h * w * (c / 8 + 4 * c)
                   + rows * oh * ow * c * (4 + 1 / 8))
        pw_b += (c * p / 8 + rows * oh * ow * (c / 8 + 4 * c)
                 + rows * oh * ow * p * (4 + 1 / 8))
    macs = _family_macs(cfg)
    return {"residual_conv": (2 * rows * macs["residual_conv"],
                              int(conv_b)),
            "pointwise": (2 * rows * macs["pointwise"], int(pw_b))}
