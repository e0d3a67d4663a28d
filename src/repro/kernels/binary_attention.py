"""Pallas TPU kernel: flash-style blocked binary attention.

The LM analogue of the dense megakernel suite (``binary_matmul.py``):
the QKᵀ inner product of every attention score is the XNOR-popcount
identity  s = (D − 2·popcount(XOR(q_packed, k_packed))) · D^(−1/2)
over sign-binarized Q/K packed 32-per-uint32-word along head_dim, and
the softmax runs in the FlashAttention online form — a per-q-row
(m, l, acc) carry in VMEM scratch walked over KV tiles by the last grid
dimension — so the (Sq, Skv) score matrix is never materialized in HBM.
V stays real-valued and accumulates in float32 (the paper binarizes the
*projections*; the attention average must keep magnitude information).

Layout and masking:

* ``q_packed``: (B, Sq, Hq, Dw) uint32, ``k_packed``: (B, Skv, Hkv, Dw)
  uint32 — packed along head_dim by the ``kernels.ops.bitpack``
  dispatcher (bit 1 ⇔ value ≥ 0, LSB-first, zero-bit tails when
  head_dim % 32 ≠ 0 — exact under the XOR-popcount identity because
  both operands pad identically). ``v``: (B, Skv, Hkv, Dv) real.
* GQA/MQA: ``Hq % Hkv == 0``; query head h reads KV head ``h // g``
  (g = Hq // Hkv) via BlockSpec index-map arithmetic — KV blocks are
  never replicated in HBM.
* Masks mirror ``models.attention.chunked_attention``: ``causal`` keeps
  qpos ≥ kpos (with ``q_offset`` aligning decode queries), ``window``
  keeps qpos − kpos < window (the sliding-window local-layer form), and
  masked lanes score ``NEG_INF`` *after* the optional logit softcap.

Grid: (B·Hq, Sq tiles, KV tiles) — KV innermost so the scratch carry is
sequential per q tile, exactly like the K-block walk of the GEMM
accumulator.  ``block_q`` is sublane-granular (multiple of 8),
``block_kv`` lane-granular (multiple of 128); both validate by RAISING,
like ``block_oh``/``block_n``/``words_per_step`` everywhere else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import binarize as B
from repro.kernels.binary_matmul import (_LANE, _SUBLANE, _ceil_mult,
                                         _mismatch_counts,
                                         contraction_scratch,
                                         DEFAULT_WORDS_PER_STEP)
from repro.kernels.fused_epilogue import (check_block_lanes,
                                          check_block_sublanes,
                                          check_words_per_step)

# Additive mask value: finite (so NEG_INF − NEG_INF == 0 and fully-masked
# rows degrade to a uniform average instead of NaN), same constant as
# models.attention.
NEG_INF = -1e30

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 128


def _attention_kernel(qp_ref, kp_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
                      qt_ref, kt_ref, *,
                      d_true: int, skv_true: int, causal: bool,
                      window: int | None, softcap: float | None,
                      q_offset: int, n_kv_blocks: int, block_q: int,
                      block_kv: int, words_per_step: int):
    """One (block_q, Dv) output tile; grid dim 2 walks the KV tiles."""
    iq = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Scores: XNOR-popcount identity, then scale (and optional softcap)
    # in f32.  Packed-word tails are zero on both operands, so they XOR
    # to no mismatches and d_true keeps the identity exact.
    mism = _mismatch_counts(qp_ref[0], kp_ref[0], qt_ref, kt_ref,
                            words_per_step=words_per_step)
    s = (jnp.int32(d_true) - 2 * mism).astype(jnp.float32)
    s = s * jnp.float32(d_true) ** -0.5
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    qpos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)
    kpos = kb * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    mask = kpos < skv_true                      # KV padding rows
    if causal:
        mask = mask & (qpos >= kpos)
    if window is not None:
        mask = mask & (qpos - kpos < window)
    s = jnp.where(mask, s, NEG_INF)

    # Online-softmax carry (m, l, acc), FlashAttention recurrence.  The
    # scalars live lane-broadcast in (block_q, 128) scratch; column 0 is
    # the value.
    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p, v_ref[0].astype(jnp.float32),
        preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(kb == n_kv_blocks - 1)
    def _flush():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)


@functools.partial(jax.jit, static_argnames=(
    "d_true", "causal", "window", "attn_softcap", "q_offset", "block_q",
    "block_kv", "words_per_step", "interpret"))
def binary_attention_packed(q_packed: jax.Array, k_packed: jax.Array,
                            v: jax.Array, *, d_true: int,
                            causal: bool = True, window: int | None = None,
                            attn_softcap: float | None = None,
                            q_offset: int = 0, block_q: int | None = None,
                            block_kv: int | None = None,
                            words_per_step: int = DEFAULT_WORDS_PER_STEP,
                            interpret: bool = False) -> jax.Array:
    """Blocked binary attention on pre-packed Q/K (see module docstring).

    ``q_packed``: (B, Sq, Hq, Dw) uint32, ``k_packed``: (B, Skv, Hkv, Dw)
    uint32, ``v``: (B, Skv, Hkv, Dv) real; ``d_true`` is the logical
    head_dim before packing.  Returns (B, Sq, Hq, Dv) float32.

    Block knobs validate by raising: ``block_q`` must be a positive
    multiple of 8 (sublanes), ``block_kv`` a positive multiple of 128
    (lanes), ``words_per_step`` a positive divisor of 128.  The output
    is invariant to all three (property-tested).
    """
    b, sq, hq, dw = q_packed.shape
    bk, skv, hkv, dwk = k_packed.shape
    if bk != b or dwk != dw:
        raise ValueError(f"q/k packed shapes disagree: "
                         f"{q_packed.shape} vs {k_packed.shape}")
    if v.shape[:3] != (b, skv, hkv):
        raise ValueError(f"k/v shapes disagree: {k_packed.shape} vs "
                         f"{v.shape}")
    if hkv < 1 or hq % hkv:
        raise ValueError(f"Hq={hq} not a multiple of Hkv={hkv}")
    group = hq // hkv
    dv = v.shape[-1]

    bq = DEFAULT_BLOCK_Q if block_q is None else block_q
    bkv = DEFAULT_BLOCK_KV if block_kv is None else block_kv
    check_block_sublanes("block_q", bq)
    check_block_lanes("block_kv", bkv)
    check_words_per_step("words_per_step", words_per_step)
    bq = min(bq, _ceil_mult(sq, _SUBLANE))
    bkv = min(bkv, _ceil_mult(skv, _LANE))

    sq_p = _ceil_mult(sq, bq)
    skv_p = _ceil_mult(skv, bkv)
    dw_p = _ceil_mult(dw, _LANE)
    dv_p = _ceil_mult(dv, _LANE)
    n_kv_blocks = skv_p // bkv

    def lay_out(x, s_mult, last_mult):
        x = B.pad_to_multiple(x, s_mult, axis=1)
        x = B.pad_to_multiple(x, last_mult, axis=3)
        h = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], -1)

    qp = lay_out(q_packed, bq, _LANE)                    # (B*Hq, Sq_p, Dw_p)
    kp = lay_out(k_packed, bkv, _LANE)                   # (B*Hkv, Skv_p, Dw_p)
    vp = lay_out(v.astype(jnp.float32), bkv, _LANE)      # (B*Hkv, Skv_p, Dv_p)

    def q_map(bh, iq, kb):
        return (bh, iq, 0)

    def kv_map(bh, iq, kb):
        # GQA: query head bh % Hq reads KV head (bh % Hq) // group.
        return ((bh // hq) * hkv + (bh % hq) // group, kb, 0)

    out = pl.pallas_call(
        functools.partial(
            _attention_kernel, d_true=d_true, skv_true=skv, causal=causal,
            window=window, softcap=attn_softcap, q_offset=q_offset,
            n_kv_blocks=n_kv_blocks, block_q=bq, block_kv=bkv,
            words_per_step=words_per_step),
        name="_attention_kernel",
        grid=(b * hq, sq_p // bq, n_kv_blocks),
        in_specs=[pl.BlockSpec((1, bq, dw_p), q_map),
                  pl.BlockSpec((1, bkv, dw_p), kv_map),
                  pl.BlockSpec((1, bkv, dv_p), kv_map)],
        out_specs=pl.BlockSpec((1, bq, dv_p), q_map),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq_p, dv_p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bq, _LANE), jnp.float32),
                        pltpu.VMEM((bq, _LANE), jnp.float32),
                        pltpu.VMEM((bq, dv_p), jnp.float32),
                        *contraction_scratch(dw_p, bq, bkv)],
        interpret=interpret,
    )(qp, kp, vp)
    out = out.reshape(b, hq, sq_p, dv_p)[:, :, :sq, :dv]
    return out.transpose(0, 2, 1, 3)
