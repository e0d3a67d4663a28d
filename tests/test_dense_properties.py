"""Property suite locking down the dense megakernel subsystem
(kernels/binary_matmul.py).

Invariants, sampled over the awkward-shape grid in ``strategies.py``:

* fused GEMM + BN-sign-repack epilogue == separate GEMM -> ``bn_sign_pack``
  == the float oracle, every backend, including pack-seam tails (K and N
  not multiples of 32),
* the contraction is invariant to ``words_per_step`` (plain, fused, and
  stack kernels), and invalid values raise like ``block_oh``/``block_n``,
* the single-launch hidden stack == per-layer fused launches == the jnp
  oracle, and the resident path traces to exactly ONE ``pallas_call``
  (``bmlp_forward_packed``'s hidden stack included — the acceptance
  criterion),
* the GEMV/serving path (M ≤ 8, N-major grid) is bit-exact across the
  sublane boundary,
* the block knobs of the rebuilt GEMM validate like the conv grid knobs
  (raise instead of silently clamping),
* ``apply_bitplane_dense_packed`` (first-layer dense, paper C4) == the
  float oracle on both backends — previously only exercised indirectly
  through ``bmlp_forward_packed`` — and its single-launch kernel == the
  per-plane jnp oracle == x.int32 @ sign(W)^T across row tiles, N tiles,
  ragged K and N, and plane counts.
"""
from _hypothesis_compat import hypothesis, st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import strategies as S

from repro.core import binarize as B
from repro.core import binary_layers as L
from repro.kernels import binary_matmul as BMM
from repro.kernels import ops, ref
from repro.models import cnn
from repro.utils.jaxpr import count_pallas_calls

settings = hypothesis.settings(max_examples=8, deadline=None)


def _rand_folded(key, c):
    tau = jax.random.normal(key, (c,)) * 3
    flip = jnp.where(jax.random.bernoulli(jax.random.fold_in(key, 1), 0.4,
                                          (c,)), -1.0, 1.0)
    return tau, flip


def _rand_gemm(key, m, k, n):
    a = jax.random.normal(key, (m, k))
    b = jax.random.normal(jax.random.fold_in(key, 1), (n, k))
    return a, b, B.pack_bits(a), B.pack_bits(b)


def _rand_stack(key, k_in, widths):
    stages = []
    for i, n in enumerate(widths):
        sub = jax.random.fold_in(key, 100 + i)
        w = jax.random.normal(sub, (n, k_in))
        tau, flip = _rand_folded(jax.random.fold_in(sub, 1), n)
        stages.append({"w_packed": B.pack_bits(w), "k_true": k_in,
                       "tau": tau, "flip": flip})
        k_in = n
    return stages


# ---------------------------------------------------------------------------
# Fused epilogue == separate GEMM -> bn_sign_pack == float oracle
# ---------------------------------------------------------------------------

@settings
@hypothesis.given(case=S.dense_cases(), ws=S.words_per_steps(),
                  seed=S.seeds())
def test_fused_epilogue_matches_separate_and_float(case, ws, seed):
    key = jax.random.PRNGKey(seed)
    a, b, ap, bp = _rand_gemm(key, case.m, case.k, case.n)
    tau, flip = _rand_folded(jax.random.fold_in(key, 2), case.n)
    # Float oracle: threshold + pack the exact integer GEMM.
    want = np.asarray(ref.bn_sign_pack_ref(ref.binary_matmul_ref(a, b),
                                           tau, flip))
    # Separate kernels: GEMM, then the standalone epilogue.
    sep = ops.bn_sign_pack(
        ops.binary_matmul_packed(ap, bp, k_true=case.k, backend="pallas",
                                 words_per_step=ws),
        tau, flip, backend="pallas")
    np.testing.assert_array_equal(np.asarray(sep), want,
                                  err_msg=f"separate path diverged {case}")
    for backend in ("pallas", "jnp"):
        got = ops.binary_matmul_bn_sign_packed(
            ap, bp, tau, flip, k_true=case.k, backend=backend,
            words_per_step=ws)
        np.testing.assert_array_equal(
            np.asarray(got), want,
            err_msg=f"{backend} fused epilogue diverged on {case} ws={ws}")


@settings
@hypothesis.given(case=S.dense_cases(), ws=S.words_per_steps(),
                  seed=S.seeds())
def test_gemm_invariant_to_words_per_step(case, ws, seed):
    """Any words_per_step == the single-word (pre-vectorization) scheme,
    through both the blocked-K and the GEMV grids."""
    key = jax.random.PRNGKey(seed)
    _, _, ap, bp = _rand_gemm(key, case.m, case.k, case.n)
    base = BMM.binary_matmul_packed(ap, bp, k_true=case.k, words_per_step=1,
                                    interpret=True)
    got = ops.binary_matmul_packed(ap, bp, k_true=case.k, backend="pallas",
                                   words_per_step=ws)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(base))


def test_gemv_path_bit_exact_across_sublane_boundary():
    """M = 8 takes the N-major GEMV grid, M = 9 the blocked grid — both
    must match the oracle (and each other's shared rows)."""
    key = jax.random.PRNGKey(5)
    a = jax.random.normal(key, (9, 500))
    b = jax.random.normal(jax.random.fold_in(key, 1), (300, 500))
    want = np.asarray(ref.binary_matmul_ref(a, b))
    kwp = B.packed_width(500)
    assert BMM.dispatch_batch(8, kwp) == "gemv"
    assert BMM.dispatch_batch(9, kwp) == "gemm"
    assert BMM.dispatch_batch(1, BMM._GEMV_MAX_KW + 128) == "gemm"
    for m in (1, 8, 9):
        got = BMM.binary_matmul_packed(B.pack_bits(a[:m]), B.pack_bits(b),
                                       k_true=500, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), want[:m])


# ---------------------------------------------------------------------------
# Single-launch hidden stack
# ---------------------------------------------------------------------------

@settings
@hypothesis.given(m=st.sampled_from((1, 8, 13)), k_in=st.sampled_from(
    (33, 64, 100)), widths=S.dense_stack_widths(), seed=S.seeds())
def test_stack_resident_equals_per_layer_equals_oracle(m, k_in, widths,
                                                       seed):
    key = jax.random.PRNGKey(seed)
    stages = _rand_stack(key, k_in, widths)
    xp = B.pack_bits(jax.random.normal(jax.random.fold_in(key, 9),
                                       (m, k_in)))
    want = np.asarray(ref.binary_dense_stack_packed_ref(stages, xp))
    for mode in (True, False, None):
        got = ops.binary_dense_stack_packed(stages, xp, backend="pallas",
                                            resident=mode)
        np.testing.assert_array_equal(
            np.asarray(got), want,
            err_msg=f"stack resident={mode} diverged {widths} m={m}")
    got = ops.binary_dense_stack_packed(stages, xp, backend="jnp")
    np.testing.assert_array_equal(np.asarray(got), want)


def test_stack_launch_counts():
    """Resident stack == ONE pallas_call; per-layer == one per stage;
    an over-budget stack auto-falls back to per-layer."""
    key = jax.random.PRNGKey(3)
    stages = _rand_stack(key, 64, (48, 96, 40))
    xp = B.pack_bits(jax.random.normal(jax.random.fold_in(key, 9), (4, 64)))
    n_res = count_pallas_calls(
        lambda v: ops.binary_dense_stack_packed(stages, v, backend="pallas",
                                                resident=True), xp)
    n_per = count_pallas_calls(
        lambda v: ops.binary_dense_stack_packed(stages, v, backend="pallas",
                                                resident=False), xp)
    n_auto = count_pallas_calls(
        lambda v: ops.binary_dense_stack_packed(stages, v,
                                                backend="pallas"), xp)
    assert (n_res, n_per, n_auto) == (1, 3, 1), (n_res, n_per, n_auto)
    # Auto decision honors the budget: zero budget -> per-layer fallback.
    n_tight = count_pallas_calls(
        lambda v: ops.binary_dense_stack_packed(stages, v, backend="pallas",
                                                vmem_budget_bytes=0), xp)
    assert n_tight == 3, n_tight


def test_stack_vmem_budget_is_shape_math():
    """The residency decision needs only shapes (so every shard of a
    sharded forward agrees), and the flagship BMLP hidden stack fits the
    default budget."""
    w4096 = jax.ShapeDtypeStruct((4096, 128), jnp.uint32)
    assert BMM.dense_stack_fits_vmem([w4096, w4096])
    big = jax.ShapeDtypeStruct((8192, 4096), jnp.uint32)
    assert not BMM.dense_stack_fits_vmem([big, big])
    small = BMM.dense_stack_vmem_bytes([w4096])
    assert small < BMM.dense_stack_vmem_bytes([w4096, w4096])


def test_bmlp_hidden_stack_is_single_kernel_launch():
    """The acceptance criterion: bmlp_forward_packed's hidden stack
    traces to exactly ONE pallas_call on the VMEM-resident path.

    Launch budget of the whole forward: 1 for the bit-plane first layer
    (every plane in one launch), 1 standalone epilogue, H launches for
    the H-layer hidden stack (1 when resident), 1 output GEMM."""
    key = jax.random.PRNGKey(7)
    spec = cnn.BMLPSpec(sizes=(20, 64, 96, 64, 10), nbits_input=2)
    packed = cnn.pack_bmlp(cnn.init_bmlp(key, spec), spec)
    x = jax.random.randint(jax.random.fold_in(key, 1), (3, 20), 0,
                           4).astype(jnp.uint8)
    base = 1 + 1 + 1                            # bit-plane + epi + output
    n_res = count_pallas_calls(
        lambda v: cnn.bmlp_forward_packed(packed, v, backend="pallas",
                                          dense_stack="auto"), x)
    n_per = count_pallas_calls(
        lambda v: cnn.bmlp_forward_packed(packed, v, backend="pallas",
                                          dense_stack="per_layer"), x)
    assert n_res == base + 1, (n_res, base)     # hidden stack == 1 launch
    assert n_per == base + 2, (n_per, base)     # two hidden layers
    # And both modes agree numerically with the jnp path.
    want = cnn.bmlp_forward_packed(packed, x, backend="jnp")
    for mode in ("auto", "resident", "per_layer"):
        got = cnn.bmlp_forward_packed(packed, x, backend="pallas",
                                      dense_stack=mode)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


def test_bcnn_dense_tail_uses_fused_stack():
    """The BCNN classifier tail: dense hidden layers contribute exactly
    one launch on the resident path, and the unpacked int32 dense
    activation never appears between them."""
    key = jax.random.PRNGKey(9)
    spec = cnn.BCNNSpec(input_hw=(8, 8), c_in=3,
                        stages=(cnn.ConvStage(16, pool=True),),
                        dense=(48, 64, 10))
    packed = cnn.pack_bcnn(cnn.init_bcnn(key, spec), spec)
    x = jax.random.randint(jax.random.fold_in(key, 1), (2, 8, 8, 3), 0,
                           256).astype(jnp.uint8)
    n_res = count_pallas_calls(
        lambda v: cnn.bcnn_forward_packed(packed, v, backend="pallas",
                                          dense_stack="auto"), x)
    n_per = count_pallas_calls(
        lambda v: cnn.bcnn_forward_packed(packed, v, backend="pallas",
                                          dense_stack="per_layer"), x)
    assert n_per - n_res == 1, (n_res, n_per)   # 2 hidden layers -> 1
    want = cnn.bcnn_forward_packed(packed, x, backend="jnp")
    got = cnn.bcnn_forward_packed(packed, x, backend="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# Knob validation (the conv-knob contract, extended to the dense suite)
# ---------------------------------------------------------------------------

def _tiny_gemm():
    key = jax.random.PRNGKey(11)
    _, _, ap, bp = _rand_gemm(key, 16, 64, 32)
    return ap, bp


@pytest.mark.parametrize("bad_ws", [0, -1, 3, 5, 7, 48, 200])
def test_words_per_step_invalid_raises(bad_ws):
    """Non-divisors of the 128-lane group raise — on the plain GEMM, the
    fused epilogue, the stack, and through the ops dispatchers."""
    ap, bp = _tiny_gemm()
    tau = jnp.zeros((32,))
    flip = jnp.ones((32,))
    with pytest.raises(ValueError, match="words_per_step"):
        BMM.binary_matmul_packed(ap, bp, k_true=64, words_per_step=bad_ws,
                                 interpret=True)
    with pytest.raises(ValueError, match="words_per_step"):
        ops.binary_matmul_packed(ap, bp, k_true=64, backend="pallas",
                                 words_per_step=bad_ws)
    with pytest.raises(ValueError, match="words_per_step"):
        ops.binary_matmul_bn_sign_packed(ap, bp, tau, flip, k_true=64,
                                         backend="pallas",
                                         words_per_step=bad_ws)
    stages = [{"w_packed": bp, "k_true": 64, "tau": tau, "flip": flip}]
    with pytest.raises(ValueError, match="words_per_step"):
        ops.binary_dense_stack_packed(stages, ap, backend="pallas",
                                      resident=True,
                                      words_per_step=bad_ws)


def test_gemm_block_knobs_raise():
    """The rebuilt GEMM validates its blocks like the conv grid does
    (regression: they used to be silently clamped)."""
    ap, bp = _tiny_gemm()
    with pytest.raises(ValueError, match="block_m"):
        BMM.binary_matmul_packed(ap, bp, k_true=64, block_m=4,
                                 interpret=True)
    with pytest.raises(ValueError, match="block_n"):
        BMM.binary_matmul_packed(ap, bp, k_true=64, block_n=64,
                                 interpret=True)
    with pytest.raises(ValueError, match="block_kw"):
        BMM.binary_matmul_packed(ap, bp, k_true=64, block_kw=100,
                                 interpret=True)
    with pytest.raises(ValueError, match="block_m"):
        BMM.binary_dense_stack_packed(
            ap, [bp], [jnp.zeros((32,))], [jnp.ones((32,))], k_trues=(64,),
            block_m=3, interpret=True)


# ---------------------------------------------------------------------------
# First-layer bit-plane dense (paper C4) vs the float oracle
# ---------------------------------------------------------------------------

@settings
@hypothesis.given(m=st.sampled_from((1, 4, 9)), k=st.sampled_from(
    (20, 32, 50, 100)), n=st.sampled_from((10, 33, 64)),
    nbits=st.sampled_from((1, 4, 8)), seed=S.seeds())
def test_bitplane_dense_packed_matches_float(m, k, n, nbits, seed):
    """apply_bitplane_dense_packed == x.int32 @ sign(W)^T exactly, both
    backends (previously only covered through bmlp_forward_packed)."""
    key = jax.random.PRNGKey(seed)
    params = L.init_binary_dense(key, k, n)
    x = jax.random.randint(jax.random.fold_in(key, 1), (m, k), 0,
                           1 << nbits).astype(jnp.uint8)
    want = np.asarray(L.apply_bitplane_dense_float(params, x)
                      ).astype(np.int32)
    packed = L.pack_bitplane_dense(params, nbits=nbits)
    for backend in ("jnp", "pallas"):
        got = L.apply_bitplane_dense_packed(packed, x, backend=backend)
        np.testing.assert_array_equal(
            np.asarray(got), want,
            err_msg=f"{backend} bitplane dense diverged m={m} k={k} n={n}")


def test_bitplane_dense_uint8_edges_exact():
    """Constant 0 and 255 inputs: every plane all-0 / all-1."""
    params = L.init_binary_dense(jax.random.PRNGKey(0), 40, 16)
    packed = L.pack_bitplane_dense(params)
    for fill in (0, 255):
        x = jnp.full((3, 40), fill, jnp.uint8)
        want = np.asarray(L.apply_bitplane_dense_float(params, x)
                          ).astype(np.int32)
        for backend in ("jnp", "pallas"):
            got = L.apply_bitplane_dense_packed(packed, x, backend=backend)
            np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("m,k,n,nbits", [
    (1, 784, 96, 8),      # BMLP K: 24 full words + a 16-bit tail word
    (3, 50, 10, 8),       # K and N far below a word / a lane group
    (8, 784, 130, 8),     # 8 images = 64 plane rows, N past 128
    (9, 50, 33, 2),       # two row tiles, 2-bit input
    (16, 100, 1100, 1),   # 1-bit input, several N tiles (1100 -> 3 x 384)
    (3, 784, 200, 2),
    (1, 50, 2048, 8),     # BMLP width over a 2-way model axis
])
def test_bitplane_dense_single_launch_matches_oracles(m, k, n, nbits):
    """The single-launch kernel == the jnp per-plane oracle ==
    x.int32 @ sign(W)^T, bit for bit, in exactly one launch."""
    key = jax.random.PRNGKey(m * 7919 + k * 31 + n + nbits)
    params = L.init_binary_dense(key, k, n)
    x = jax.random.randint(jax.random.fold_in(key, 1), (m, k), 0,
                           1 << nbits).astype(jnp.uint8)
    packed = L.pack_bitplane_dense(params, nbits=nbits)
    want = np.asarray(x, np.int64) @ np.where(
        np.asarray(params["w"]) >= 0, 1, -1).T
    oracle = np.asarray(ops.bitplane_dense_packed(packed, x, backend="jnp"))
    got = np.asarray(ops.bitplane_dense_packed(packed, x, backend="pallas"))
    assert got.dtype == np.int32 and got.shape == (m, n)
    np.testing.assert_array_equal(oracle, want)
    np.testing.assert_array_equal(got, want)
    assert count_pallas_calls(
        lambda v: ops.bitplane_dense_packed(packed, v, backend="pallas"),
        x) == 1


def test_bitplane_dense_dispatch_counted():
    """Each pallas dispatch bumps ``ops.dispatch.bitplane_dense``; the
    jnp oracle does not."""
    from repro import telemetry
    params = L.init_binary_dense(jax.random.PRNGKey(5), 40, 16)
    packed = L.pack_bitplane_dense(params, nbits=2)
    x = jnp.full((2, 40), 3, jnp.uint8)
    counter = telemetry.default().metrics.counter(
        "ops.dispatch.bitplane_dense")
    before = counter.value
    ops.bitplane_dense_packed(packed, x, backend="pallas")
    ops.bitplane_dense_packed(packed, x, backend="jnp")
    assert counter.value == before + 1
