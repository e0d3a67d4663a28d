"""Plain float reference of the BinaryNet MNIST MLP (Espresso §6.2).

784 -> 3 x [4096 dense, BN, sign] -> 10 dense, BN, on 8-bit input: the
first layer multiplies the raw uint8 pixels by sign(W); every later
layer multiplies sign(activation) by sign(W).  Plain ``jax.numpy`` in
float32, imports nothing of the program under test.

Also here: the weights the benchmark serves (``init_params``, in the
layout the program's packer takes) and the work of one flush by kernel
family (``work``), both computed from the configuration's sizes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _bn_stats(key, c: int, last: bool) -> dict:
    """Batch norm whose hidden thresholds never tie an integer
    pre-activation: a per-channel sign flip, a half-integer mean and no
    shift, so sign(BN(z)) is decided exactly.  The output batch norm
    gets continuous statistics."""
    k = jax.random.split(key, 5)
    sign = jnp.where(jax.random.bernoulli(k[0], 0.3, (c,)), -1.0, 1.0)
    gamma = sign * jax.random.uniform(k[1], (c,), minval=0.3, maxval=1.5)
    var = jax.random.uniform(k[2], (c,), minval=0.5, maxval=2.0)
    mean = 3.0 * jax.random.normal(k[3], (c,))
    if last:
        return {"gamma": gamma, "beta": jax.random.normal(k[4], (c,)),
                "mean": mean, "var": var}
    return {"gamma": gamma, "beta": jnp.zeros((c,)),
            "mean": jnp.floor(mean) + 0.5, "var": var}


def init_params(cfg: dict, seed: int) -> dict:
    """Latent weights uniform in [-1, 1] and batch norms, on the device,
    in one jitted call."""
    sizes = cfg["sizes"]

    def build(key):
        layers, bns = [], []
        n = len(sizes) - 1
        for i, (d_in, d_out) in enumerate(zip(sizes[:-1], sizes[1:])):
            kw, kb = jax.random.split(jax.random.fold_in(key, i))
            layers.append({"w": jax.random.uniform(
                kw, (d_out, d_in), jnp.float32, -1.0, 1.0)})
            bns.append(_bn_stats(kb, d_out, i == n - 1))
        return {"layers": layers, "bns": bns}

    return jax.jit(build)(jax.random.PRNGKey(seed))


def _sign(x):
    return jnp.where(x >= 0, 1.0, -1.0).astype(x.dtype)


def _bn(p: dict, z, dtype):
    inv = p["gamma"].astype(dtype) * jax.lax.rsqrt(
        p["var"].astype(dtype) + jnp.asarray(1e-5, dtype))
    return (z - p["mean"].astype(dtype)) * inv + p["beta"].astype(dtype)


def forward(params: dict, x_uint8, cfg: dict, dtype=jnp.float32):
    """Logits of ``x_uint8`` (B, 784).  Every product, sum and batch norm
    is computed in ``dtype``; the caller sets the matmul precision."""
    layers, bns = params["layers"], params["bns"]
    h = x_uint8.astype(dtype)
    z = None
    for i, (layer, bn) in enumerate(zip(layers, bns)):
        w = _sign(layer["w"]).astype(dtype)
        a = h if i == 0 else _sign(h)
        z = _bn(bn, jnp.dot(a, w.T, preferred_element_type=dtype), dtype)
        h = z
    return z


def macs_per_image(cfg: dict) -> int:
    s = cfg["sizes"]
    return sum(a * b for a, b in zip(s[:-1], s[1:]))


def work(cfg: dict, rows: int) -> dict[str, tuple[int, int]]:
    """(operations, least bytes) of one flush of ``rows`` rows, by
    kernel family.  Operations are 2 x the float network's MACs.  Bytes
    are the least any implementation moves: 1-bit weights, the packed
    input (uint8 for the first layer), packed outputs, and 4-byte
    logits for the last layer."""
    s = cfg["sizes"]
    n = len(s) - 1
    byts = 0
    for i, (k, m) in enumerate(zip(s[:-1], s[1:])):
        x_bytes = rows * k * cfg["nbits_input"] // 8 if i == 0 \
            else rows * k // 8
        out_bytes = rows * m * 4 if i == n - 1 else rows * m // 8
        byts += k * m // 8 + x_bytes + out_bytes
    return {"dense": (2 * rows * macs_per_image(cfg), byts)}
