"""Pallas TPU kernels: the bit-packed dense GEMM megakernel suite
(paper §4.2, §5.2, §6.2 — C1/C2/C7).

The dense analogue of the conv subsystem (``binary_conv.py``), built
around  out[m, n] = K − 2·popcount(XOR(a[m, :], b[n, :]))  over packed
uint32 operands:

* **Contraction** — both operands are transposed into int32 VMEM
  scratch (word axis on sublanes), and each loop step reads
  ``words_per_step`` words of each as refs (``pl.ds``) and adds one
  (bm, bn) popcount-of-XOR outer product per word: Mosaic slices
  loaded values only statically and has no unsigned reductions.  The
  knob is validated like ``block_oh``/``block_n`` (divisors of the
  128-lane group; invalid values raise) and the output is invariant to
  it.
* **Fused BN-sign-repack epilogue** (:func:`binary_matmul_bn_sign_packed`)
  — the kernel flush thresholds the int32 accumulator against the folded
  BN (``fold_bn_sign``) and re-bitpacks along N, so hidden dense layers
  emit packed uint32 directly and the (M, N) int32 activation never
  leaves VMEM.  ``block_n`` must land on 32-bit pack seams (the lane
  check subsumes it, asserted like the conv epilogue).
* **Single-launch hidden stack** (:func:`binary_dense_stack_packed`) —
  when every hidden layer's packed weights + folded thresholds fit a
  VMEM budget (:func:`dense_stack_fits_vmem`), the whole stack runs as
  ONE ``pallas_call``: grid over M tiles only, every weight BlockSpec
  pinned to block (0, 0) so the weights stay resident across tiles, and
  an in-kernel stage loop chains GEMM -> threshold -> repack entirely in
  VMEM.  The dense analogue of the conv subsystem's single-launch
  bit-plane kernel.
* **GEMV / serving specialization** (paper §6.2: matrix-vector swap at
  batch 1) — for M ≤ 8 the M tile collapses to the sublane minimum and
  the grid becomes N-major 1-D: the packed activation block is pinned
  resident in VMEM, weight row blocks stream past it, and the
  contraction completes per program (no cross-step accumulator).
* **Residual pointwise conv** (:func:`pointwise_residual_packed`) — a
  1x1 binary conv over the B·H·W pixel rows whose epilogue adds a float
  shortcut and writes both the float stream and the packed sign
  (ReActNet; ``fused_epilogue.residual_epilogue``).
* **Single-launch bit-plane first layer** (:func:`bitplane_dense_packed`)
  — every (image, plane) pair of the fixed-precision input is one
  contraction row against word-major weights packed once at load, so
  the whole layer is one launch over only the real packed words, and
  the epilogue folds the 2^p plane weights.  Its own contraction loop:
  the shared one above transposes both operands on every call.

HBM→VMEM staging via ``BlockSpec`` tiles is the TPU analogue of the
paper's shared-memory tiling (C7); 32-bit packing words match the TPU
VPU lane width (DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import vmem
from repro.core import binarize as B
from repro.kernels.fused_epilogue import (EPI_ROWS, bn_sign_bits_to_words,
                                          check_block_lanes, unblock_packed,
                                          check_block_sublanes,
                                          check_words_per_step, pack_lanes,
                                          pad_bn_params, residual_epilogue)

# Minimum int32 tile granularity on TPU: (8 sublanes, 128 lanes).
_SUBLANE = 8
_LANE = 128

# Packed words read per contraction loop step (each unrolled into ws
# (bm, bn) popcount outer products).
DEFAULT_WORDS_PER_STEP = 8

# GEMV path bound: both operands hold their whole packed-K extent in one
# block, so cap it (4096 words = 128K logical K; the streamed weight
# block is then block_n * 16 KB).
_GEMV_MAX_KW = 4096

# Single-launch stack defaults: serving-shaped M tiles (the resident
# stack is a decode/serve feature — weights dominate VMEM, activations
# ride in sublane-minimum tiles) and a budget that leaves headroom for
# Mosaic's double buffering under the ~16 MB/core VMEM.
STACK_BLOCK_M = _SUBLANE
STACK_VMEM_BUDGET = 8 * 2**20


# ---------------------------------------------------------------------------
# Shared contraction body
# ---------------------------------------------------------------------------

def _mismatch_counts(a: jax.Array, b: jax.Array, at_ref, bt_ref, *,
                     words_per_step: int) -> jax.Array:
    """Vectorized XNOR-popcount contraction of two packed blocks.

    ``a``: (bm, kw) and ``b``: (bn, kw) packed words.  Returns the
    (bm, bn) int32 total mismatch count.  Both operands are first
    transposed into the int32 VMEM scratch refs ``at_ref`` / ``bt_ref``
    (at least (kw, bm) / (kw, bn)), so the word axis is the sublane axis
    and a loop step can slice ``ws`` words at a dynamic offset — Mosaic
    only slices values statically and lanes at 128-aligned offsets.
    Each step turns its ``ws`` activation rows back into (bm, ws)
    columns and adds one (bm, bn) popcount-of-XOR outer product per
    word.  A static tail handles kw not divisible by ws (ragged stack
    stages); the result is invariant to ``words_per_step``.
    """
    bm, kw = a.shape
    bn = b.shape[0]
    # int32 view: XOR, popcount and the transpose ignore the sign, and
    # Mosaic has no unsigned reductions.
    at_ref[:kw, :bm] = jax.lax.bitcast_convert_type(a, jnp.int32).T
    bt_ref[:kw, :bn] = jax.lax.bitcast_convert_type(b, jnp.int32).T
    ws = min(words_per_step, kw)
    steps, rem = divmod(kw, ws)

    def chunk(start, size: int, acc: jax.Array) -> jax.Array:
        a_cols = at_ref[pl.ds(start, size), :bm].T          # (bm, size)
        b_rows = bt_ref[pl.ds(start, size), :bn]            # (size, bn)
        for j in range(size):
            acc = acc + jax.lax.population_count(
                a_cols[:, j:j + 1] ^ b_rows[j:j + 1, :])
        return acc

    acc = jax.lax.fori_loop(0, steps, lambda i, acc: chunk(i * ws, ws, acc),
                            jnp.zeros((bm, bn), jnp.int32))
    if rem:
        acc = chunk(steps * ws, rem, acc)
    return acc


def contraction_scratch(kw: int, bm: int, bn: int) -> list:
    """The two transposed-operand scratch buffers of
    :func:`_mismatch_counts` for a (bm, kw) x (bn, kw) contraction."""
    return [pltpu.VMEM((kw, bm), jnp.int32), pltpu.VMEM((kw, bn), jnp.int32)]


# ---------------------------------------------------------------------------
# Kernels
# ---------------------------------------------------------------------------

def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, at_ref, bt_ref, *,
                 k_true: int, n_k_blocks: int, words_per_step: int):
    """One (bm, bn) output tile; grid dim 2 walks the packed-K blocks."""
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _mismatch_counts(a_ref[...], b_ref[...], at_ref, bt_ref,
                                     words_per_step=words_per_step)

    @pl.when(kb == n_k_blocks - 1)
    def _flush():
        o_ref[...] = jnp.int32(k_true) - 2 * acc_ref[...]


def _gemm_bn_sign_kernel(a_ref, b_ref, tau_ref, flip_ref, o_ref, acc_ref,
                         at_ref, bt_ref, *, k_true: int, n_k_blocks: int,
                         words_per_step: int):
    """Fused variant: the flush thresholds + re-bitpacks along N, so the
    int32 activation never leaves the accumulator scratch."""
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _mismatch_counts(a_ref[...], b_ref[...], at_ref, bt_ref,
                                     words_per_step=words_per_step)

    @pl.when(kb == n_k_blocks - 1)
    def _flush():
        y = jnp.int32(k_true) - 2 * acc_ref[...]
        o_ref[0] = bn_sign_bits_to_words(y, tau_ref[...], flip_ref[...])


def _gemv_kernel(a_ref, b_ref, o_ref, at_ref, bt_ref, *, k_true: int,
                 words_per_step: int):
    """N-major serving path: full-K contraction per program, A resident."""
    o_ref[...] = jnp.int32(k_true) - 2 * _mismatch_counts(
        a_ref[...], b_ref[...], at_ref, bt_ref,
        words_per_step=words_per_step)


def _gemv_bn_sign_kernel(a_ref, b_ref, tau_ref, flip_ref, o_ref, at_ref,
                         bt_ref, *, k_true: int, words_per_step: int):
    y = jnp.int32(k_true) - 2 * _mismatch_counts(
        a_ref[...], b_ref[...], at_ref, bt_ref,
        words_per_step=words_per_step)
    o_ref[0] = bn_sign_bits_to_words(y, tau_ref[...], flip_ref[...])


def _dense_stack_kernel(*refs, k_trues: tuple[int, ...],
                        words_per_step: int):
    """In-kernel stage loop over the VMEM-resident hidden-layer weights.

    ``refs`` = (x, [w, tau, flip] per stage, out, at, bt) — the last two
    are the contraction scratch, sized for the widest stage.  Each stage
    runs the full contraction for this M tile (the stack grid has no N
    or K blocking — residency is the point), thresholds against its
    folded BN, and re-bitpacks; the packed words feed the next stage
    without ever leaving VMEM.  Stage widths are lane-padded by the host
    wrapper so every repack lands on 32-bit word seams; padded channels
    carry tau=+inf / flip=+1 and pack as 0-bits, matching the
    zero-bit-tail convention of the next stage's zero-padded weight
    words.
    """
    x_ref, o_ref, at_ref, bt_ref = refs[0], refs[-3], refs[-2], refs[-1]
    h = x_ref[...]
    for s in range(len(k_trues)):
        w_ref, tau_ref, flip_ref = refs[1 + 3 * s:4 + 3 * s]
        mism = _mismatch_counts(h, w_ref[...], at_ref, bt_ref,
                                words_per_step=words_per_step)
        y = jnp.int32(k_trues[s]) - 2 * mism
        h = bn_sign_bits_to_words(y, tau_ref[...], flip_ref[...])
    o_ref[...] = h


# ---------------------------------------------------------------------------
# Host-side wrappers
# ---------------------------------------------------------------------------

def _resolve_blocks(m: int, n: int, kw: int, block_m: int, block_n: int,
                    block_kw: int, words_per_step: int):
    """Validate the GEMM knobs (raising, like the conv grid knobs) and
    trim over-padding.  M ≤ 8 collapses the M tile to the sublane
    minimum — the GEMV specialization's entry condition."""
    check_block_sublanes("block_m", block_m)
    check_block_lanes("block_n", block_n)
    check_block_lanes("block_kw", block_kw)
    check_words_per_step("words_per_step", words_per_step)
    if m <= _SUBLANE:
        block_m = _SUBLANE
    block_m = min(block_m, _ceil_mult(m, _SUBLANE))
    block_n = min(block_n, _ceil_mult(n, _LANE))
    block_kw = min(block_kw, _ceil_mult(kw, _LANE))
    return block_m, block_n, block_kw


def dispatch_batch(m: int, kw_words: int) -> str:
    """The GEMV-vs-GEMM routing rule — the one seam every dense caller
    (the GEMM wrappers here, ``ops.dispatch_batch``, the serving layer)
    shares, so the batching queue and the kernels can never disagree on
    which grid a flush lowers to.

    ``m`` is the batch (GEMM M) and ``kw_words`` the packed-K width in
    uint32 words.  Returns ``'gemv'`` when the M tile collapses to the
    8-sublane minimum AND the lane-padded packed K fits the resident
    activation block (``kw_words`` ≤ 4096 words = 128K logical K) —
    the N-major serving grid; ``'gemm'`` otherwise — the (M, N, K)
    blocked grid.  Idempotent under lane padding, so callers may pass
    either the logical or the padded word count.
    """
    kwp = _ceil_mult(kw_words, _LANE)
    return "gemv" if (m <= _SUBLANE and kwp <= _GEMV_MAX_KW) else "gemm"


@functools.partial(jax.jit, static_argnames=("k_true", "block_m", "block_n",
                                             "block_kw", "words_per_step",
                                             "interpret"))
def binary_matmul_packed(a_packed: jax.Array, b_packed: jax.Array, *,
                         k_true: int, block_m: int = 128, block_n: int = 128,
                         block_kw: int = 128,
                         words_per_step: int = DEFAULT_WORDS_PER_STEP,
                         interpret: bool = False) -> jax.Array:
    """Packed binary GEMM via Pallas.

    ``a_packed``: (M, Kw) uint32, ``b_packed``: (N, Kw) uint32 (pre-packed
    weights — packing happens once at load time, paper C2).  ``k_true`` is
    the *logical* K before packing/padding.  Returns (M, N) int32.

    Block knobs must honor TPU granularity (bm: multiples of 8, bn/bkw:
    multiples of 128; invalid values raise) and are trimmed down to the
    padded operand.  Zero-padded words XOR to zero and contribute no
    mismatches, so padding is exact (``core.binarize.pack_bits``).
    ``words_per_step`` packed words are contracted per loop step; the
    output is invariant to it.  M ≤ 8 with a VMEM-sized K takes the
    N-major GEMV grid (paper §6.2).
    """
    m, kw = a_packed.shape
    n, kw_b = b_packed.shape
    assert kw == kw_b, (a_packed.shape, b_packed.shape)
    block_m, block_n, block_kw = _resolve_blocks(
        m, n, kw, block_m, block_n, block_kw, words_per_step)

    a_p = B.pad_to_multiple(B.pad_to_multiple(a_packed, block_m, 0),
                            block_kw, 1)
    b_p = B.pad_to_multiple(B.pad_to_multiple(b_packed, block_n, 0),
                            block_kw, 1)
    mp, kwp = a_p.shape
    np_, _ = b_p.shape

    if dispatch_batch(m, kwp) == "gemv":
        kernel = functools.partial(_gemv_kernel, k_true=k_true,
                                   words_per_step=words_per_step)
        out = pl.pallas_call(
            kernel,
            name="_gemv_kernel",
            grid=(np_ // block_n,),
            in_specs=[
                pl.BlockSpec((mp, kwp), lambda j: (0, 0)),
                pl.BlockSpec((block_n, kwp), lambda j: (j, 0)),
            ],
            out_specs=pl.BlockSpec((mp, block_n), lambda j: (0, j)),
            out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
            scratch_shapes=contraction_scratch(kwp, mp, block_n),
            interpret=interpret,
        )(a_p, b_p)
        return out[:m, :n]

    grid = (mp // block_m, np_ // block_n, kwp // block_kw)
    kernel = functools.partial(_gemm_kernel, k_true=k_true,
                               n_k_blocks=grid[2],
                               words_per_step=words_per_step)
    out = pl.pallas_call(
        kernel,
        name="_gemm_kernel",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_kw), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_n, block_kw), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32),
                        *contraction_scratch(block_kw, block_m, block_n)],
        interpret=interpret,
    )(a_p, b_p)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("k_true", "block_m", "block_n",
                                             "block_kw", "words_per_step",
                                             "interpret"))
def binary_matmul_bn_sign_packed(a_packed: jax.Array, b_packed: jax.Array,
                                 tau: jax.Array, flip: jax.Array, *,
                                 k_true: int, block_m: int = 128,
                                 block_n: int = 128, block_kw: int = 128,
                                 words_per_step: int = DEFAULT_WORDS_PER_STEP,
                                 interpret: bool = False) -> jax.Array:
    """Fused packed GEMM + BN-sign-fold + re-bitpack; packed uint32 output.

    Same contraction (and the same GEMV specialization) as
    :func:`binary_matmul_packed`, but the kernel flush thresholds the
    int32 accumulator against the folded BN (``tau``/``flip`` per output
    channel) and packs the resulting ±1 bits along N — the hidden-layer
    activation leaves the kernel already packed for the next GEMM.
    Returns (M, ceil(N/32)) uint32, bit-identical to
    ``pack_bits(apply_bn_sign_folded(gemm_out))``.  ``block_n`` is a
    multiple of 128 (validated), which lands every output block on a
    32-bit pack seam — asserted like the conv epilogue.
    """
    m, kw = a_packed.shape
    n, kw_b = b_packed.shape
    assert kw == kw_b, (a_packed.shape, b_packed.shape)
    block_m, block_n, block_kw = _resolve_blocks(
        m, n, kw, block_m, block_n, block_kw, words_per_step)
    assert block_n % B.WORD_BITS == 0

    a_p = B.pad_to_multiple(B.pad_to_multiple(a_packed, block_m, 0),
                            block_kw, 1)
    b_p = B.pad_to_multiple(B.pad_to_multiple(b_packed, block_n, 0),
                            block_kw, 1)
    tau_p, flip_p = pad_bn_params(tau, flip, block_n)
    mp, kwp = a_p.shape
    np_, _ = b_p.shape
    bnw = block_n // B.WORD_BITS
    n_blocks = np_ // block_n
    cw_out = B.packed_width(n)

    if dispatch_batch(m, kwp) == "gemv":
        kernel = functools.partial(_gemv_bn_sign_kernel, k_true=k_true,
                                   words_per_step=words_per_step)
        out = pl.pallas_call(
            kernel,
            name="_gemv_bn_sign_kernel",
            grid=(n_blocks,),
            in_specs=[
                pl.BlockSpec((mp, kwp), lambda j: (0, 0)),
                pl.BlockSpec((block_n, kwp), lambda j: (j, 0)),
                pl.BlockSpec((1, block_n), lambda j: (0, j)),
                pl.BlockSpec((1, block_n), lambda j: (0, j)),
            ],
            out_specs=pl.BlockSpec((1, mp, bnw), lambda j: (j, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((n_blocks, mp, bnw), jnp.uint32),
            scratch_shapes=contraction_scratch(kwp, mp, block_n),
            interpret=interpret,
        )(a_p, b_p, tau_p, flip_p)
        return unblock_packed(out)[:m, :cw_out]

    grid = (mp // block_m, n_blocks, kwp // block_kw)
    kernel = functools.partial(_gemm_bn_sign_kernel, k_true=k_true,
                               n_k_blocks=grid[2],
                               words_per_step=words_per_step)
    out = pl.pallas_call(
        kernel,
        name="_gemm_bn_sign_kernel",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_kw), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_n, block_kw), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_m, bnw), lambda i, j, k: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks, mp, bnw), jnp.uint32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32),
                        *contraction_scratch(block_kw, block_m, block_n)],
        interpret=interpret,
    )(a_p, b_p, tau_p, flip_p)
    return unblock_packed(out)[:m, :cw_out]


# ---------------------------------------------------------------------------
# Single-launch hidden stack
# ---------------------------------------------------------------------------

def dense_stack_vmem_bytes(weights: list, *,
                           block_m: int = STACK_BLOCK_M,
                           words_per_step: int = DEFAULT_WORDS_PER_STEP
                           ) -> int:
    """Upper-bound VMEM residency of :func:`binary_dense_stack_packed`.

    Resident terms: every stage's lane-padded weight block + folded
    tau/flip rows + the activation M tile + the transposed-operand
    scratch of the widest stage.  Transient terms (the largest single
    stage): the int32 pre-threshold tile and the repacked words.

    The arithmetic lives in the shared static VMEM estimator
    (``analysis.vmem.dense_stack_estimate`` — the same cost model the
    ops preflight and the autotuner consume); this wrapper keeps the
    historical array-based signature.  The GEMV-vs-stack crossover is
    regression-pinned in tests/test_analysis.py.
    """
    return vmem.dense_stack_estimate(
        [tuple(w.shape) for w in weights],
        block_m=block_m, words_per_step=words_per_step).total


def dense_stack_fits_vmem(weights: list, *, budget: int | None = None,
                          block_m: int = STACK_BLOCK_M,
                          words_per_step: int = DEFAULT_WORDS_PER_STEP
                          ) -> bool:
    """Residency decision for the single-launch stack (pure shape math —
    identical on every shard, so sharded callers never diverge)."""
    budget = STACK_VMEM_BUDGET if budget is None else budget
    return dense_stack_vmem_bytes(
        weights, block_m=block_m,
        words_per_step=words_per_step) <= budget


@functools.partial(jax.jit, static_argnames=("k_trues", "block_m",
                                             "words_per_step", "interpret"))
def binary_dense_stack_packed(x_packed: jax.Array, weights: list,
                              taus: list, flips: list, *,
                              k_trues: tuple[int, ...],
                              block_m: int = STACK_BLOCK_M,
                              words_per_step: int = DEFAULT_WORDS_PER_STEP,
                              interpret: bool = False) -> jax.Array:
    """The whole hidden dense stack in ONE ``pallas_call``.

    ``x_packed``: (M, Kw₀) packed input activation; stage ``s`` applies
    weights ``(N_s, Kw_s)`` then the folded BN threshold ``taus[s]`` /
    ``flips[s]`` and re-bitpacks.  Returns (M, ceil(N_last/32)) uint32 —
    bit-identical to chaining ``binary_matmul_bn_sign_packed`` per layer
    (and to GEMM -> ``bn_sign_pack``), property-tested.

    Grid: (M tiles,) only.  Every weight/tau/flip BlockSpec is pinned to
    block (0, 0), so Pallas holds ONE DMA of the full stack resident in
    VMEM across all M tiles while the x/out tiles stream — callers gate
    on :func:`dense_stack_fits_vmem` and fall back to per-layer fused
    launches when the stack doesn't fit.  Stage widths are lane-padded;
    a stage's padded channels pack as 0-bits (tau=+inf, flip=+1) and the
    next stage's weights are zero-word-padded to match, so padding is
    exact end-to-end.
    """
    m, kw0 = x_packed.shape
    n_stages = len(weights)
    assert n_stages == len(taus) == len(flips) == len(k_trues) >= 1
    assert weights[0].shape[1] == kw0, (weights[0].shape, x_packed.shape)
    check_block_sublanes("block_m", block_m)
    check_words_per_step("words_per_step", words_per_step)
    block_m = min(block_m, _ceil_mult(m, _SUBLANE))

    x_p = B.pad_to_multiple(x_packed, block_m, 0)
    mp = x_p.shape[0]
    operands = [x_p]
    in_specs = [pl.BlockSpec((block_m, kw0), lambda i: (i, 0))]
    prev_words = kw0
    max_words = max_n = 0
    for s in range(n_stages):
        w = weights[s]
        n_s, kw_s = w.shape
        assert kw_s <= prev_words, (s, w.shape, prev_words)
        w_p = B.pad_to_multiple(w, prev_words, 1)        # zero word tails
        n_pad = _ceil_mult(n_s, _LANE)
        w_p = B.pad_to_multiple(w_p, n_pad, 0)
        tau_p, flip_p = pad_bn_params(taus[s], flips[s], n_pad)
        operands += [w_p, tau_p, flip_p]
        max_words, max_n = max(max_words, prev_words), max(max_n, n_pad)
        in_specs += [
            pl.BlockSpec((n_pad, prev_words), lambda i: (0, 0)),
            pl.BlockSpec((1, n_pad), lambda i: (0, 0)),
            pl.BlockSpec((1, n_pad), lambda i: (0, 0)),
        ]
        prev_words = n_pad // B.WORD_BITS

    kernel = functools.partial(_dense_stack_kernel, k_trues=k_trues,
                               words_per_step=words_per_step)
    out = pl.pallas_call(
        kernel,
        name="_dense_stack_kernel",
        grid=(mp // block_m,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_m, prev_words), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((mp, prev_words), jnp.uint32),
        scratch_shapes=contraction_scratch(max_words, block_m, max_n),
        interpret=interpret,
    )(*operands)
    return out[:m, :B.packed_width(weights[-1].shape[0])]


# ---------------------------------------------------------------------------
# Single-launch bit-plane first layer
# ---------------------------------------------------------------------------

def _bitplane_dense_kernel(x_ref, w_ref, rowsum_ref, o_ref, *, k_true: int,
                           nbits: int):
    """One (images, N) output tile of the bit-plane first layer.

    ``x_ref``: (1, Kw₈, rows) — this tile's packed planes, word-major,
    one lane per (image, plane) row in image-major order; ``w_ref``:
    (Kw, bn) word-major packed weights, so each contraction step reads
    its words as sublane rows with no transpose.  The loop walks only
    the Kw real words: a step transposes 8 of them into (rows, 8)
    columns and adds one (rows, bn) popcount-of-XOR per word, and a
    static tail takes the last Kw mod 8.  The epilogue folds the 2^p
    plane weights and the rowsum shift per image:

        out = (2^n − 1)·(K + rowsum)/2 − Σ_p 2^p·mism_p

    which is  1/2 Σ_p 2^p (K − 2·mism_p + rowsum)  — the exact identity
    of ``core.binarize.bitplane_dot`` (K + rowsum is even: rowsum sums
    K terms of ±1).
    """
    kw, bn = w_ref.shape
    rows = x_ref.shape[2]
    steps, rem = divmod(kw, _SUBLANE)

    def chunk(start, size: int, acc: jax.Array) -> jax.Array:
        a_cols = x_ref[0, pl.ds(start, _SUBLANE), :].T          # (rows, 8)
        b_rows = jax.lax.bitcast_convert_type(
            w_ref[pl.ds(start, size), :], jnp.int32)           # (size, bn)
        for j in range(size):
            acc = acc + jax.lax.population_count(
                a_cols[:, j:j + 1] ^ b_rows[j:j + 1, :])
        return acc

    acc = jnp.zeros((rows, bn), jnp.int32)
    if steps:
        acc = jax.lax.fori_loop(
            0, steps, lambda s, acc: chunk(s * _SUBLANE, _SUBLANE, acc), acc)
    if rem:
        acc = chunk(steps * _SUBLANE, rem, acc)
    half = (((1 << nbits) - 1) * (jnp.int32(k_true) + rowsum_ref[...])) >> 1
    plane = jax.lax.broadcasted_iota(jnp.int32, (nbits, bn), 0)
    for i in range(o_ref.shape[0]):
        mism = acc[i * nbits:(i + 1) * nbits] << plane
        o_ref[i:i + 1, :] = half - jnp.sum(mism, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("k_true", "nbits",
                                             "interpret"))
def bitplane_dense_packed(x_planes: jax.Array, w_words: jax.Array,
                          rowsum: jax.Array, *, k_true: int, nbits: int,
                          interpret: bool = False) -> jax.Array:
    """First-layer fixed-precision dense (paper C4) in ONE kernel launch.

    ``x_planes``: (nbits, M, Kw) packed bit planes
    (``core.binarize.pack_bitplanes_uint8``: plane bit == packed bit);
    ``w_words``: (Kw, N) word-major packed weights and ``rowsum``: (N,)
    int32 row sums of sign(W), both made once at pack time
    (``core.binary_layers.pack_bitplane_dense``).  Returns (M, N) int32
    == x.int32 @ sign(W)^T.

    Each contraction row is one (image, plane) pair, so the 8 planes of
    one image fill the 8 sublanes; the contraction runs over the Kw real
    words (the row tile pads them only to the 8-word sublane multiple).
    Grid: (N tiles, row tiles), N outer, so each weight tile is fetched
    once per call and stays resident while the row tiles stream past;
    at M = 1 and N = 4096 the grid is a single program.  Blocks come
    from the shapes alone (``analysis.vmem.bitplane_dense_blocks``).
    """
    nb, m, kw = x_planes.shape
    assert nb == nbits and w_words.shape[0] == kw, (x_planes.shape,
                                                   w_words.shape, nbits)
    n = w_words.shape[1]
    block_i, block_n = vmem.bitplane_dense_blocks(m, n, nbits)
    x_p = B.pad_to_multiple(x_planes, block_i, 1)
    tiles = x_p.shape[1] // block_i
    rows = block_i * nbits
    # (nbits, M, Kw) -> (tiles, Kw₈, block_i·nbits): word-major, with
    # the (image, plane) rows of a tile on lanes, image-major.
    x_t = x_p.reshape(nbits, tiles, block_i, kw).transpose(1, 3, 2, 0)
    x_t = B.pad_to_multiple(x_t.reshape(tiles, kw, rows), _SUBLANE, 1)
    x_t = jax.lax.bitcast_convert_type(x_t, jnp.int32)
    w_p = B.pad_to_multiple(w_words, block_n, 1)
    rs = B.pad_to_multiple(rowsum.reshape(1, n).astype(jnp.int32),
                           block_n, 1)
    np_ = w_p.shape[1]

    kernel = functools.partial(_bitplane_dense_kernel, k_true=k_true,
                               nbits=nbits)
    out = pl.pallas_call(
        kernel,
        name="_bitplane_dense_kernel",
        grid=(np_ // block_n, tiles),
        in_specs=[
            pl.BlockSpec((1, x_t.shape[1], rows), lambda j, t: (t, 0, 0)),
            pl.BlockSpec((kw, block_n), lambda j, t: (0, j)),
            pl.BlockSpec((1, block_n), lambda j, t: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_i, block_n), lambda j, t: (t, j)),
        out_shape=jax.ShapeDtypeStruct((tiles * block_i, np_), jnp.int32),
        interpret=interpret,
    )(x_t, w_p, rs)
    return out[:m, :n]


# ---------------------------------------------------------------------------
# Residual pointwise (1x1) binary conv
# ---------------------------------------------------------------------------

def _pointwise_residual_kernel(a_ref, w_ref, epi_ref, sc_ref, o_ref, p_ref,
                               *, k_true: int, repeat: int):
    """One (rows, bn) tile of a residual 1x1 binary conv.

    ``a_ref``: (bm, Kw) packed rows (one per pixel); ``w_ref``: (Kw, bn)
    word-major weights, so each word's weights are one sublane row; the
    Kw ≤ 32 words are unrolled, each one (bm, bn) popcount-of-XOR.  The
    float shortcut tile is ``repeat`` times narrower than the output
    where the width doubles (two convs over one input, each adding the
    same shortcut), and is tiled along lanes to match.  The epilogue is
    :func:`fused_epilogue.residual_epilogue`; writes the float32 stream
    (``o_ref``) and the packed sign of stream + the next bias
    (``p_ref``).
    """
    a = a_ref[...]
    w = w_ref[...]
    acc = jnp.zeros((a.shape[0], w.shape[1]), jnp.int32)
    for c in range(w.shape[0]):
        acc = acc + jax.lax.population_count(
            a[:, c:c + 1] ^ w[c:c + 1, :]).astype(jnp.int32)
    sc = sc_ref[...]
    if repeat > 1:
        sc = jnp.concatenate([sc] * repeat, axis=1)
    y, u = residual_epilogue(jnp.int32(k_true) - 2 * acc, epi_ref[...], sc)
    o_ref[...] = y
    p_ref[0] = pack_lanes((u >= 0).astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("k_true", "interpret"))
def pointwise_residual_packed(x_packed: jax.Array, w_words: jax.Array,
                              epi: jax.Array, shortcut: jax.Array, *,
                              k_true: int, interpret: bool = False
                              ) -> tuple[jax.Array, jax.Array]:
    """Residual 1x1 binary conv (ReActNet's second block half) as one
    GEMM launch over the B·H·W pixel rows.

    ``x_packed``: (M, Kw) packed sign rows; ``w_words``: (Kw, N)
    word-major packed weights, the two C -> C convs of a doubling block
    stacked along N = 2C; ``epi``: (8, N) float32 epilogue rows
    (``fused_epilogue.EPI_*``); ``shortcut``: (M, C) float32 stream,
    added to every C-channel group of the output.  Returns the float32
    stream (M, N) and its packed sign plus the next bias, (M,
    ceil(N/32)) uint32.  Grid (row tiles, N blocks), blocks from the
    shapes (``analysis.vmem.pointwise_residual_blocks``); no lane
    padding of the K words or of N up to 128.
    """
    m, kw = x_packed.shape
    kw_w, n = w_words.shape
    c = shortcut.shape[1]
    assert kw == kw_w and n % c == 0, (x_packed.shape, w_words.shape,
                                       shortcut.shape)
    bm, bn = vmem.pointwise_residual_blocks(m, kw, n)
    a_p = B.pad_to_multiple(x_packed, bm, 0)
    sc = B.pad_to_multiple(shortcut, bm, 0)
    mp = a_p.shape[0]
    n_blocks = n // bn
    bnw = bn // B.WORD_BITS
    if bn <= c:
        sc_spec = pl.BlockSpec((bm, bn), lambda i, j: (i, j % (c // bn)))
        repeat = 1
    else:
        sc_spec = pl.BlockSpec((bm, c), lambda i, j: (i, 0))
        repeat = bn // c

    kernel = functools.partial(_pointwise_residual_kernel, k_true=k_true,
                               repeat=repeat)
    y, p = pl.pallas_call(
        kernel,
        name="_pointwise_residual_kernel",
        grid=(mp // bm, n_blocks),
        in_specs=[
            pl.BlockSpec((bm, kw), lambda i, j: (i, 0)),
            pl.BlockSpec((kw, bn), lambda i, j: (0, j)),
            pl.BlockSpec((EPI_ROWS, bn), lambda i, j: (0, j)),
            sc_spec,
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, bm, bnw), lambda i, j: (j, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((mp, n), jnp.float32),
            jax.ShapeDtypeStruct((n_blocks, mp, bnw), jnp.uint32),
        ],
        interpret=interpret,
    )(a_p, w_words, epi, sc)
    return y[:m], unblock_packed(p)[:m]


def _ceil_mult(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
