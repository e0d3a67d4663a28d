"""Pallas TPU kernel: sign-binarize + bit-pack along the last axis (C1/C3).

Turns a real-valued (M, K) tensor into (M, K/32) uint32 words, LSB-first —
the activation-packing step between binary layers (the paper packs weights
once at load; *activations* must be packed every layer, so this is the
recurring packing cost the kernel optimizes; paper §6.3 notes it).

TPU layout note (paper C3 adapted): we pack the **last (feature/channel)
axis**, which is the lane axis on TPU and the axis jnp keeps contiguous —
the same "pack along channels" choice the paper makes so im2col unrolling
needs no re-layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import binarize as B
from repro.kernels.fused_epilogue import (_LANE, check_block_lanes,
                                          check_block_sublanes, pack_lanes)


def _bitpack_kernel(x_ref, o_ref):
    o_ref[...] = pack_lanes((x_ref[...] >= 0).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("block_m", "block_kw",
                                             "interpret"))
def bitpack(x: jax.Array, *, block_m: int = 256, block_kw: int = 128,
            interpret: bool = False) -> jax.Array:
    """Sign-binarize + pack ``x``: (M, K) real -> (M, ceil(K/32)) uint32.

    Padded tail elements pack as 0-bits (they are materialized as -1.0,
    which encodes to bit 0 — matching ``core.binarize.pack_bits`` on the
    zero-padded bit tensor).
    """
    m, k = x.shape
    kw = B.packed_width(k)

    check_block_sublanes("block_m", block_m)
    block_m = min(block_m, _ceil_mult(m, 8))
    check_block_lanes("block_kw", block_kw)
    # Trim to the packed width rounded up to one 128-lane input group:
    # the output block is then either the whole packed width or a
    # multiple of 128 words, both legal on the chip.
    block_kw = min(block_kw, _ceil_mult(kw, _LANE // B.WORD_BITS))
    block_k = block_kw * B.WORD_BITS

    # Pad K with -1.0 so padded positions encode to bit 0.
    x_p = B.pad_to_multiple(x, block_k, axis=1, value=-1.0)
    x_p = B.pad_to_multiple(x_p, block_m, axis=0, value=-1.0)
    mp, kp = x_p.shape
    grid = (mp // block_m, kp // block_k)

    out = pl.pallas_call(
        _bitpack_kernel,
        name="_bitpack_kernel",
        grid=grid,
        in_specs=[pl.BlockSpec((block_m, block_k), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((block_m, block_kw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, kp // B.WORD_BITS), jnp.uint32),
        interpret=interpret,
    )(x_p)
    return out[:m, :kw]


def _ceil_mult(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
