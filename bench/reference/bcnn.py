"""Plain float reference of the BinaryNet CIFAR-10 CNN (Espresso §6.3).

32x32x3 -> 2x128C3-MP2 -> 2x256C3-MP2 -> 2x512C3-MP2 -> FC 1024-1024-10
on 8-bit input.  Each conv is SAME with zero padding, followed by a 2x2
max-pool where the stage pools, then batch norm; the next layer takes
sign() of that.  The first conv multiplies the raw uint8 pixels by
sign(W).  Plain ``jax.numpy`` in float32, imports nothing of the
program under test.

Also here: the weights the benchmark serves (``init_params``, in the
layout the program's packer takes) and the work of one flush by kernel
family (``work``), both computed from the configuration's sizes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .bmlp import _bn, _bn_stats, _sign


def _stage_hw(cfg: dict) -> list[tuple[int, int, int, int, bool]]:
    """(h, w, c_in, c_out, pool) of each conv stage."""
    h, w = cfg["input_hw"]
    c = cfg["c_in"]
    out = []
    for st in cfg["stages"]:
        out.append((h, w, c, st["c_out"], st["pool"]))
        c = st["c_out"]
        if st["pool"]:
            h, w = h // 2, w // 2
    return out


def _dense_sizes(cfg: dict) -> list[int]:
    h, w = cfg["input_hw"]
    for st in cfg["stages"]:
        if st["pool"]:
            h, w = h // 2, w // 2
    return [h * w * cfg["stages"][-1]["c_out"], *cfg["dense"]]


def init_params(cfg: dict, seed: int) -> dict:
    """Latent weights uniform in [-1, 1] and batch norms, on the device,
    in one jitted call."""
    k = cfg["ksize"]
    stages = _stage_hw(cfg)
    dense = _dense_sizes(cfg)

    def build(key):
        convs, conv_bns, denses, dense_bns = [], [], [], []
        for i, (_, _, c_in, c_out, _) in enumerate(stages):
            kw, kb = jax.random.split(jax.random.fold_in(key, i))
            convs.append({"w": jax.random.uniform(
                kw, (c_out, k, k, c_in), jnp.float32, -1.0, 1.0)})
            conv_bns.append(_bn_stats(kb, c_out, False))
        n = len(dense) - 1
        for i, (d_in, d_out) in enumerate(zip(dense[:-1], dense[1:])):
            kw, kb = jax.random.split(jax.random.fold_in(key, 100 + i))
            denses.append({"w": jax.random.uniform(
                kw, (d_out, d_in), jnp.float32, -1.0, 1.0)})
            dense_bns.append(_bn_stats(kb, d_out, i == n - 1))
        return {"convs": convs, "conv_bns": conv_bns, "denses": denses,
                "dense_bns": dense_bns}

    return jax.jit(build)(jax.random.PRNGKey(seed))


def _maxpool2(z):
    return jax.lax.reduce_window(z, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


def forward(params: dict, x_uint8, cfg: dict, dtype=jnp.float32):
    """Logits of ``x_uint8`` (B, H, W, C).  Every product, sum and batch
    norm is computed in ``dtype``; the caller sets the matmul
    precision."""
    h = x_uint8.astype(dtype)
    for i, (_, _, _, _, pool) in enumerate(_stage_hw(cfg)):
        w = _sign(params["convs"][i]["w"]).astype(dtype)
        z = jax.lax.conv_general_dilated(
            h if i == 0 else _sign(h), jnp.transpose(w, (1, 2, 3, 0)),
            (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            preferred_element_type=dtype)
        if pool:
            z = _maxpool2(z)
        h = _bn(params["conv_bns"][i], z, dtype)
    h = h.reshape(h.shape[0], -1)
    z = None
    for i, (layer, bn) in enumerate(zip(params["denses"],
                                        params["dense_bns"])):
        w = _sign(layer["w"]).astype(dtype)
        z = _bn(bn, jnp.dot(_sign(h), w.T, preferred_element_type=dtype),
                dtype)
        h = z
    return z


def macs_per_image(cfg: dict) -> int:
    return sum(_family_macs(cfg).values())


def _family_macs(cfg: dict) -> dict[str, int]:
    kk = cfg["ksize"] ** 2
    conv = sum(h * w * kk * c_in * c_out
               for h, w, c_in, c_out, _ in _stage_hw(cfg))
    d = _dense_sizes(cfg)
    return {"conv": conv, "dense": sum(a * b for a, b in zip(d[:-1], d[1:]))}


def work(cfg: dict, rows: int) -> dict[str, tuple[int, int]]:
    """(operations, least bytes) of one flush of ``rows`` rows, by
    kernel family.  Operations are 2 x the float network's MACs.  Bytes
    are the least any implementation moves: 1-bit weights, the packed
    input (uint8 for the first conv), each layer's packed output after
    pooling, and 4-byte logits for the last layer."""
    kk = cfg["ksize"] ** 2
    conv_bytes = 0
    for i, (h, w, c_in, c_out, pool) in enumerate(_stage_hw(cfg)):
        x_bytes = rows * h * w * c_in * (cfg["nbits_input"] if i == 0
                                         else 1) // 8
        oh, ow = (h // 2, w // 2) if pool else (h, w)
        conv_bytes += kk * c_in * c_out // 8 + x_bytes + \
            rows * oh * ow * c_out // 8
    d = _dense_sizes(cfg)
    n = len(d) - 1
    dense_bytes = sum(k * m // 8 + rows * k // 8 +
                      (rows * m * 4 if i == n - 1 else rows * m // 8)
                      for i, (k, m) in enumerate(zip(d[:-1], d[1:])))
    macs = _family_macs(cfg)
    return {"conv": (2 * rows * macs["conv"], conv_bytes),
            "dense": (2 * rows * macs["dense"], dense_bytes)}
