"""Property suite for the flash-style blocked binary attention kernel.

Three-way contract over ragged (Sq, Skv, Hq, Hkv) geometries
(``strategies.attention_cases``):

    pallas kernel == jnp oracle (``ref.binary_attention_ref``)
                  == float-sign reference (naive softmax attention on
                     sign-binarized Q/K — an independent formulation)

plus block-knob invariance and the raising knob/argument validation.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import hypothesis
from strategies import attention_blocks, attention_cases, seeds, \
    words_per_steps

from repro.core import binarize as B
from repro.kernels import ops as kops
from repro.kernels import ref as kref

settings = hypothesis.settings(max_examples=15, deadline=None)


def _qkv(case, seed):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    hq = case.hkv * case.group
    q = jax.random.normal(ks[0], (case.batch, case.sq, hq, case.d),
                          jnp.float32)
    k = jax.random.normal(ks[1], (case.batch, case.skv, case.hkv, case.d),
                          jnp.float32)
    v = jax.random.normal(ks[2], (case.batch, case.skv, case.hkv, case.d),
                          jnp.float32)
    return q, k, v


def _float_sign_naive(q, k, v, *, causal, window, q_offset):
    """Independent reference: exact-softmax attention over the ±1
    sign-binarized Q/K (einsum form, no online recurrence, no packing)."""
    hq, hkv = q.shape[2], k.shape[2]
    g = hq // hkv
    qb = B.sign_pm1(q)
    kb = jnp.repeat(B.sign_pm1(k), g, axis=2)
    vf = jnp.repeat(v.astype(jnp.float32), g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb) * q.shape[-1] ** -0.5
    qpos = q_offset + jnp.arange(q.shape[1])[:, None]
    kpos = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones_like(qpos >= kpos)
    if causal:
        mask = mask & (qpos >= kpos)
    if window is not None:
        mask = mask & (qpos - kpos < window)
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vf)


@hypothesis.given(case=attention_cases(), seed=seeds())
@settings
def test_kernel_matches_oracle_and_float_sign(case, seed):
    q, k, v = _qkv(case, seed)
    q_offset = max(0, case.skv - case.sq)
    kw = dict(causal=case.causal, window=case.window, q_offset=q_offset)
    out = kops.binary_attention(q, k, v, backend="pallas", **kw)
    oracle = kref.binary_attention_ref(q, k, v, **kw)
    naive = _float_sign_naive(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(oracle), np.asarray(naive),
                               rtol=2e-5, atol=2e-5)


@hypothesis.given(case=attention_cases(), blocks=attention_blocks(),
                  ws=words_per_steps(), seed=seeds())
@settings
def test_output_invariant_to_block_knobs(case, blocks, ws, seed):
    q, k, v = _qkv(case, seed)
    q_offset = max(0, case.skv - case.sq)
    kw = dict(causal=case.causal, window=case.window, q_offset=q_offset)
    base = kops.binary_attention(q, k, v, backend="pallas", **kw)
    block_q, block_kv = blocks
    out = kops.binary_attention(q, k, v, backend="pallas", block_q=block_q,
                                block_kv=block_kv, words_per_step=ws, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               rtol=2e-5, atol=2e-5)


def test_softcap_and_backends_agree():
    """Deterministic spot-check of the softcap path (gemma-2 form) on
    every backend, GQA heads, ragged head_dim."""
    case_q, case_k = 9, 21
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (2, case_q, 4, 33), jnp.float32)
    k = jax.random.normal(ks[1], (2, case_k, 2, 33), jnp.float32)
    v = jax.random.normal(ks[2], (2, case_k, 2, 33), jnp.float32)
    kw = dict(causal=True, window=7, attn_softcap=30.0,
              q_offset=case_k - case_q)
    out_p = kops.binary_attention(q, k, v, backend="pallas", **kw)
    out_j = kops.binary_attention(q, k, v, backend="jnp", **kw)
    out_r = kops.binary_attention(q, k, v, backend="ref", **kw)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_j),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(out_j), np.asarray(out_r))


def test_invalid_knobs_raise():
    q = jnp.zeros((1, 4, 2, 16), jnp.float32)
    k = v = jnp.zeros((1, 4, 1, 16), jnp.float32)
    with pytest.raises(ValueError, match="block_q"):
        kops.binary_attention(q, k, v, backend="pallas", block_q=7)
    with pytest.raises(ValueError, match="block_kv"):
        kops.binary_attention(q, k, v, backend="pallas", block_kv=64)
    with pytest.raises(ValueError, match="words_per_step"):
        kops.binary_attention(q, k, v, backend="pallas", words_per_step=3)
    with pytest.raises(ValueError, match="window"):
        kops.binary_attention(q, k, v, window=0)
    with pytest.raises(ValueError, match="backend"):
        kops.binary_attention(q, k, v, backend="pallsa")
    k2 = v2 = jnp.zeros((1, 4, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="Hq"):
        kops.binary_attention(jnp.zeros((1, 4, 3, 16)), k2, v2,
                              backend="pallas")


def test_no_score_matrix_in_hbm():
    """The flash property: the largest live intermediate of the packed
    attention launch stays far below the (B, Hq, Sq, Skv) float score
    matrix an unfused attention materializes.  Traces the attention
    stage on pre-packed Q/K — the online-softmax claim is about the
    launch, not the (linear-in-S) bitpack staging in front of it —
    and jaxpr never descends into kernel bodies, so intermediates are
    exactly the HBM-visible arrays."""
    from repro.kernels import binary_attention as BA
    from repro import analysis as J
    b, s, h, d = 1, 1024, 4, 64
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, s, h, d), jnp.float32)
    qp = kops.bitpack(q)

    def packed(qp, kp, v):
        return BA.binary_attention_packed(qp, kp, v, d_true=d,
                                          causal=True, interpret=True)

    def unfused(q, k, v):
        return _float_sign_naive(q, k, v, causal=True, window=None,
                                 q_offset=0)

    packed_bytes, packed_shape = J.max_intermediate_bytes(packed, qp, qp, q)
    unfused_bytes, _ = J.max_intermediate_bytes(unfused, q, q, q)
    score_bytes = b * h * s * s * 4
    assert unfused_bytes >= score_bytes
    assert packed_bytes < score_bytes / 4, (packed_bytes, packed_shape)
    # Channel packing shrinks the float32 Q/K operands 32x.
    assert q.nbytes == 32 * qp.nbytes


def test_dispatcher_is_three_launches():
    """``kops.binary_attention`` on float Q/K/V: one bitpack launch each
    for Q and K, then ONE blocked attention launch."""
    from repro import analysis as J
    q = jnp.zeros((1, 8, 2, 64), jnp.float32)
    launches = J.pallas_launches(
        lambda a, b_, c: kops.binary_attention(a, b_, c, backend="pallas"),
        q, q, q)
    assert len(launches) == 3, launches
    assert sum("attention" in ln.kernel for ln in launches) == 1, launches
