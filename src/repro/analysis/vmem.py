"""VMEM preflight pass: static per-launch VMEM estimation from block
shapes, grids, and dtypes — BEFORE any tracing or compilation.

A Pallas launch that oversubscribes the ~16 MB/core VMEM fails deep
inside Mosaic (or silently thrashes in interpret mode); the only
guard the repo had was ``dense_stack_fits_vmem``'s hand-rolled budget
arithmetic for ONE kernel family.  This pass generalizes it:

* **Closed-form estimators** (``gemm_estimate``, ``conv_estimate``,
  ``attention_estimate``, ``dense_stack_estimate``,
  ``bitplane_dense_estimate``, ``residual_conv_estimate``, ...) mirror each
  wrapper's own block-resolution math, so ``kernels/ops.py`` can
  :func:`preflight` a launch from shapes + knobs alone — at Python
  call time, before ``jax.jit`` ever traces.  An over-budget launch
  raises :class:`VmemBudgetError` with the per-term breakdown.  These
  estimators are also the static cost model the ROADMAP autotuner
  consumes (score = estimate.total, feasibility = estimate.fits()).
* **Traced estimator** (:func:`estimate_eqn` / :func:`estimate_forward`)
  reads a traced ``pallas_call``'s ``grid_mapping`` (block shapes,
  array dtypes, scratch avals) — the ground-truth view the merged
  analysis report records per launch and CI drift-gates.

Accounting model (matches the old ``dense_stack_vmem_bytes``): a
BlockSpec whose block covers its whole array is DMA'd once and held
resident (1 buffer); a genuinely tiled block is double-buffered by the
pipeline emitter (2 buffers).  Scratch is a single allocation.  The
closed-form estimators additionally charge the kernel's compute
transient (one loop step's ``ws`` transposed operand words + the
pre-pack int32 tile), which the traced view cannot see.

Budget: 16 MiB/core by default; override with the environment knob
``REPRO_VMEM_BUDGET_BYTES`` (e.g. to model a smaller core or leave
explicit headroom).  The single-launch dense stack keeps its own
tighter 8 MiB gate (``kernels.binary_matmul.STACK_VMEM_BUDGET``) —
residency there is a routing *choice* with a jnp fallback, not an
error.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Sequence

from repro.analysis import graph

# TPU tile granularity + packing word width (kept in sync with
# core.binarize.WORD_BITS and the kernels' own module constants; pure
# ints here so this module never imports jax at module level for the
# closed-form path).
SUBLANE = 8
LANE = 128
WORD_BITS = 32

# GEMV routing bound (kernels.binary_matmul._GEMV_MAX_KW).
GEMV_MAX_KW = 4096

DEFAULT_VMEM_BUDGET = 16 * 2**20

# Rows of a residual epilogue's per-channel table
# (kernels.fused_epilogue.EPI_ROWS).
EPI_ROWS = 8


def vmem_budget() -> int:
    """The per-core VMEM budget preflight enforces (env-overridable)."""
    env = os.environ.get("REPRO_VMEM_BUDGET_BYTES")
    return int(env) if env else DEFAULT_VMEM_BUDGET


def _ceil_mult(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _prod(xs: Sequence[int]) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


# ---------------------------------------------------------------------------
# Estimate model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class VmemTerm:
    """One VMEM resident: a staged operand block, scratch, or transient.

    ``bytes`` is per buffer; ``buffers`` is 2 for pipeline-streamed
    blocks (double-buffered), 1 for pinned/resident blocks, scratch,
    and compute transients.
    """
    name: str
    bytes: int
    buffers: int = 1

    @property
    def total(self) -> int:
        return self.bytes * self.buffers


@dataclasses.dataclass(frozen=True)
class LaunchEstimate:
    """Static VMEM estimate for one pallas launch."""
    kernel: str
    grid: tuple[int, ...]
    terms: tuple[VmemTerm, ...]

    @property
    def total(self) -> int:
        return sum(t.total for t in self.terms)

    def fits(self, budget: int | None = None) -> bool:
        return self.total <= (vmem_budget() if budget is None else budget)

    def breakdown(self) -> str:
        lines = [f"{self.kernel} grid={self.grid}: "
                 f"{self.total} B estimated VMEM"]
        for t in sorted(self.terms, key=lambda t: -t.total):
            tag = f" x{t.buffers}" if t.buffers != 1 else ""
            lines.append(f"  {t.name}: {t.bytes} B{tag} = {t.total} B")
        return "\n".join(lines)

    def to_json(self) -> dict[str, Any]:
        return {
            "kernel": self.kernel,
            "grid": list(self.grid),
            "bytes": self.total,
            "fits": self.fits(),
            "terms": {t.name: t.total for t in self.terms},
        }


class VmemBudgetError(ValueError):
    """A launch's static VMEM estimate exceeds the per-core budget."""

    def __init__(self, estimate: LaunchEstimate, budget: int):
        self.estimate = estimate
        self.budget = budget
        super().__init__(
            f"launch would need ~{estimate.total} B VMEM, over the "
            f"{budget} B budget (REPRO_VMEM_BUDGET_BYTES to override).\n"
            f"{estimate.breakdown()}\n"
            "Shrink the block knobs (block_m/block_n/block_kw/...) or "
            "raise the budget.")


def preflight(estimate: LaunchEstimate,
              budget: int | None = None) -> LaunchEstimate:
    """Raise :class:`VmemBudgetError` if ``estimate`` oversubscribes
    VMEM; return it unchanged otherwise (so call sites can chain)."""
    budget = vmem_budget() if budget is None else budget
    if estimate.total > budget:
        raise VmemBudgetError(estimate, budget)
    return estimate


# ---------------------------------------------------------------------------
# Closed-form estimators (pre-trace; mirror each wrapper's block math)
# ---------------------------------------------------------------------------

def gemm_estimate(m: int, n: int, kw: int, *, block_m: int = 128,
                  block_n: int = 128, block_kw: int = 128,
                  words_per_step: int = 8,
                  fused: bool = False) -> LaunchEstimate:
    """Estimate the packed GEMM / GEMV launch of
    ``kernels.binary_matmul`` for (M, Kw) x (N, Kw) packed operands.

    Reproduces ``_resolve_blocks``'s trimming and the GEMV-vs-GEMM
    routing, so the estimate tracks the grid the wrapper actually
    emits.  ``fused=True`` adds the BN tau/flip rows and the packed
    output (the ``*_bn_sign_packed`` variants).
    """
    if m <= SUBLANE:
        block_m = SUBLANE
    block_m = min(block_m, _ceil_mult(m, SUBLANE))
    block_n = min(block_n, _ceil_mult(n, LANE))
    block_kw = min(block_kw, _ceil_mult(kw, LANE))
    mp = _ceil_mult(m, block_m)
    np_ = _ceil_mult(n, block_n)
    kwp = _ceil_mult(kw, block_kw)

    gemv = m <= SUBLANE and kwp <= GEMV_MAX_KW
    bm = mp if gemv else block_m
    bkw = kwp if gemv else block_kw
    ws = min(words_per_step, bkw)
    out_w = block_n // WORD_BITS if fused else block_n

    terms = [
        VmemTerm("a_block", bm * bkw * 4, 1 if gemv else 2),
        VmemTerm("b_block", block_n * bkw * 4, 2),
        VmemTerm("out_block", bm * out_w * 4, 2),
        *_contraction_terms(bkw, bm, block_n, ws),
        VmemTerm("y_tile", bm * block_n * 4),
    ]
    if fused:
        terms += [VmemTerm("tau_block", block_n * 4, 2),
                  VmemTerm("flip_block", block_n * 4, 2)]
    if not gemv:
        terms.append(VmemTerm("acc_scratch", block_m * block_n * 4))
    if gemv:
        grid: tuple[int, ...] = (np_ // block_n,)
    else:
        grid = (mp // block_m, np_ // block_n, kwp // block_kw)
    return LaunchEstimate(kernel="gemv" if gemv else "gemm",
                          grid=grid, terms=tuple(terms))


def _contraction_terms(kw: int, bm: int, bn: int, ws: int
                       ) -> list[VmemTerm]:
    """The shared XNOR-popcount contraction (``binary_matmul.
    _mismatch_counts``): both operands transposed into int32 scratch
    (word axis on sublanes, lanes padded to 128), and one loop step's
    ``ws`` words of each read back."""
    return [VmemTerm("a_transposed", kw * _ceil_mult(bm, LANE) * 4),
            VmemTerm("b_transposed", kw * _ceil_mult(bn, LANE) * 4),
            VmemTerm("step_words", ws * (_ceil_mult(bm, LANE) + bn) * 4)]


def dense_stack_estimate(weight_shapes: Sequence[tuple[int, int]], *,
                         block_m: int = SUBLANE,
                         words_per_step: int = 8) -> LaunchEstimate:
    """Estimate the single-launch hidden stack
    (``kernels.binary_matmul.binary_dense_stack_packed``).

    ``weight_shapes``: per-stage packed weight shapes (N_s, Kw_s).
    This IS the arithmetic ``dense_stack_vmem_bytes`` historically
    hand-rolled (that function now delegates here; the crossover is
    regression-pinned in tests): the x tile + every stage's lane-padded
    resident weights and folded tau/flip rows, the transposed-operand
    scratch sized for the widest stage, plus the single largest stage
    transient — the int32 pre-threshold tile and the repacked words.
    """
    prev_words = int(weight_shapes[0][1])
    terms = [VmemTerm("x_tile", block_m * prev_words * 4)]
    peak = max_words = max_n = 0
    for s, (n_s, _) in enumerate(weight_shapes):
        n_pad = _ceil_mult(int(n_s), LANE)
        terms.append(VmemTerm(f"stage{s}_weights", n_pad * prev_words * 4))
        terms.append(VmemTerm(f"stage{s}_bn", 2 * n_pad * 4))
        stage = (block_m * n_pad * 4
                 + block_m * (n_pad // WORD_BITS) * 4)
        peak = max(peak, stage)
        max_words, max_n = max(max_words, prev_words), max(max_n, n_pad)
        prev_words = n_pad // WORD_BITS
    terms += _contraction_terms(max_words, block_m, max_n,
                                min(words_per_step, max_words))
    terms.append(VmemTerm("stage_transient_peak", peak))
    return LaunchEstimate(kernel="dense_stack", grid=(1,),
                          terms=tuple(terms))


# Accumulator budget of the bit-plane first layer, in int32 words: the
# dense stack's (8, 4096) tile, 32 of the 64 vector registers.
BITPLANE_ACC_WORDS = SUBLANE * 4096


def bitplane_dense_blocks(m: int, n: int, nbits: int) -> tuple[int, int]:
    """(images per row tile, N tile) of the single-launch bit-plane
    first layer (``kernels.binary_matmul.bitplane_dense_packed``).

    Up to 8 images ride in one row tile (at M = 1 the 8 planes are the
    8 sublanes); larger batches tile 8 images at a time.  The N tile is
    the widest multiple of 128 lanes that divides the lane-padded N and
    keeps the (rows, N tile) int32 accumulator within
    ``BITPLANE_ACC_WORDS``.
    """
    block_i = min(m, SUBLANE)
    rows = _ceil_mult(block_i * nbits, SUBLANE)
    groups = _ceil_mult(n, LANE) // LANE
    fit = max(1, BITPLANE_ACC_WORDS // (rows * LANE))
    block_n = max(d for d in range(1, min(fit, groups) + 1)
                  if groups % d == 0) * LANE
    return block_i, block_n


def bitplane_dense_estimate(m: int, n: int, kw: int, *,
                            nbits: int) -> LaunchEstimate:
    """Estimate the single-launch bit-plane first layer for (M, K) input
    of ``kw`` packed words against (Kw, N) word-major weights.

    The staged blocks are exactly the wrapper's (:func:`estimate_eqn`
    reads the same bytes and buffers off the traced launch); on top,
    the (rows, N tile) accumulator and one loop step's 8 words of each
    operand (the activation words transposed, lanes padded to 128).
    """
    block_i, block_n = bitplane_dense_blocks(m, n, nbits)
    tiles = -(-m // block_i)
    n_tiles = _ceil_mult(n, block_n) // block_n
    rows = block_i * nbits
    kw8 = _ceil_mult(kw, SUBLANE)
    x_bufs = 1 if tiles == 1 else 2
    w_bufs = 1 if n_tiles == 1 else 2
    out_bufs = 1 if tiles == n_tiles == 1 else 2
    terms = (
        VmemTerm("x_block", kw8 * rows * 4, x_bufs),
        VmemTerm("w_block", kw * block_n * 4, w_bufs),
        VmemTerm("rowsum_block", block_n * 4, w_bufs),
        VmemTerm("out_block", block_i * block_n * 4, out_bufs),
        VmemTerm("acc_tile", rows * block_n * 4),
        VmemTerm("step_words",
                 SUBLANE * (_ceil_mult(rows, LANE) + block_n) * 4),
    )
    return LaunchEstimate(kernel="bitplane_dense", grid=(n_tiles, tiles),
                          terms=terms)


def conv_estimate(batch: int, padded_hw: tuple[int, int], cw: int,
                  kh: int, kw: int, c_out: int, out_hw: tuple[int, int], *,
                  block_n: int, block_oh: int, fused: bool = False,
                  nbits: int = 1) -> LaunchEstimate:
    """Estimate the fused conv launches of ``kernels.binary_conv``.

    ``padded_hw`` is the spatially padded image size the wrapper stages
    (``_prep_operands``), ``cw`` the packed channel words.  ``nbits > 1``
    models the bit-plane first-layer kernel (the plane stack rides in
    one VMEM block).  ``fused`` adds the BN rows and shrinks the output
    to packed words; the plain conv instead stages the correction tile.
    """
    hp, wp = padded_hw
    oh, ow = out_hw
    block_m = block_oh * ow
    m_tiles = -(-oh // block_oh)
    c_out_p = _ceil_mult(c_out, block_n)
    out_w = block_n // WORD_BITS if fused else block_n
    terms = [
        # Image BlockSpec depends only on the batch index: resident
        # across (m, j) steps, double-buffered across batch elements.
        VmemTerm("image_block", nbits * hp * wp * cw * 4, 2),
        VmemTerm("weight_block", block_n * kh * kw * cw * 4, 2),
        VmemTerm("out_block", block_m * out_w * 4, 2),
        VmemTerm("acc_tile", block_m * block_n * 4),
    ]
    if fused:
        terms += [VmemTerm("tau_block", block_n * 4, 2),
                  VmemTerm("flip_block", block_n * 4, 2)]
    elif nbits > 1:
        terms.append(VmemTerm("rowsum_block", block_n * 4, 2))
    else:
        terms.append(VmemTerm("correction_block", block_m * block_n * 4, 2))
    return LaunchEstimate(
        kernel="bitplane_conv" if nbits > 1 else
        ("conv_bn_sign" if fused else "conv"),
        grid=(batch, m_tiles, c_out_p // block_n),
        terms=tuple(terms))


# Residual kernels' tile budget: output pixels (rows) per tile, and
# pixels x packed words a tile contracts, which bounds the unrolled word
# loop of the pointwise kernel and of the conv's VPU route (and so their
# compile time).
RESIDUAL_TILE_M = 1024
RESIDUAL_TILE_WORDS = 4096

# Pixels of one MXU-route residual conv tile: a few hundred rows a dot
# (several whole images where one image is fewer), halved where the
# launch would overrun VMEM (``residual_conv_blocks``).
RESIDUAL_CONV_TILE_M = 512


def residual_conv_route(cw: int, c_out: int) -> str:
    """The residual conv's contraction: 'mxu' (±1 bf16 operands unpacked
    in VMEM, one dot a tap) where C_in and C_out each fill a 128-lane
    group, else 'vpu' (XOR-popcount).  On a v5e ReActNet-A's 112² blocks
    (C 32 and 64) ran faster on the VPU and every wider block on the MXU
    (the route table in PERF.md, section 6)."""
    return "mxu" if min(cw * WORD_BITS, c_out) >= LANE else "vpu"


def residual_block_n(c_out: int) -> int:
    """C_out block of the residual kernels: the whole C_out up to one
    lane group (a block as wide as its array, not padded to 128 lanes:
    ReActNet's 32- and 64-channel blocks), else 128 lanes."""
    if c_out % WORD_BITS or (c_out > LANE and c_out % LANE):
        raise ValueError(f"residual kernels need C_out a multiple of 32, "
                         f"and of 128 above 128; got {c_out}")
    return min(c_out, LANE)


def _tile_cap(cw: int) -> int:
    return max(SUBLANE, min(RESIDUAL_TILE_M, RESIDUAL_TILE_WORDS // cw))


def _conv_tile(batch: int, oh: int, ow: int, cap: int) -> tuple[int, int]:
    """(images, output rows) of a conv M tile of at most ``cap`` pixels:
    whole images, as many as divide the batch, where one image fits;
    else the largest divisor of OH whose band is a whole number of
    sublane groups (at least one row), or the whole image."""
    if oh * ow <= cap:
        return max(d for d in range(1, batch + 1)
                   if batch % d == 0 and d * oh * ow <= cap), oh
    fits = [d for d in range(1, oh + 1) if oh % d == 0 and
            (d == oh or (d * ow) % SUBLANE == 0)]
    within = [d for d in fits if d * ow <= cap]
    return 1, (max(within) if within else min(fits))


def residual_conv_blocks(batch: int, oh: int, ow: int, cw: int, c_out: int,
                         *, stride: int = 1, kh: int = 3,
                         kw: int = 3) -> tuple[int, int, int]:
    """(images per M tile, output rows per M tile, C_out block) of
    ``kernels.binary_conv.binary_conv2d_residual_packed``: on the MXU
    route the largest tile within ``RESIDUAL_CONV_TILE_M`` pixels,
    halved while the launch estimate overruns the VMEM budget (7x7x1024
    at 256 images: 4 images, not 8); on the VPU route within the
    residual tile budget."""
    bn = residual_block_n(c_out)
    if residual_conv_route(cw, c_out) == "vpu":
        return (*_conv_tile(batch, oh, ow, _tile_cap(cw)), bn)
    cap = RESIDUAL_CONV_TILE_M
    while True:
        nb, block_oh = _conv_tile(batch, oh, ow, cap)
        terms = _residual_conv_terms(nb, block_oh, ow, cw, bn, route="mxu",
                                     stride=stride, kh=kh, kw=kw,
                                     in_w=ow * stride)
        if cap <= SUBLANE or sum(t.total for t in terms) <= vmem_budget():
            return nb, block_oh, bn
        cap //= 2


def pointwise_residual_blocks(m: int, kw: int,
                              n: int) -> tuple[int, int]:
    """(row tile, N block) of ``kernels.binary_matmul.
    pointwise_residual_packed``: the largest multiple of 8 rows within
    the residual tile budget that divides M, or all of M when it fits;
    else the budget itself (the wrapper pads M)."""
    cap = _tile_cap(kw) // SUBLANE * SUBLANE
    if m <= cap:
        return m, residual_block_n(n)
    for bm in range(cap, SUBLANE - 1, -SUBLANE):
        if m % bm == 0:
            return bm, residual_block_n(n)
    return cap, residual_block_n(n)


def _lanes(c: int) -> int:
    return _ceil_mult(c, LANE)


def _residual_conv_terms(nb: int, block_oh: int, ow: int, cw: int, bn: int,
                         *, route: str, stride: int, kh: int, kw: int,
                         in_w: int) -> tuple[VmemTerm, ...]:
    block_m = nb * block_oh * ow
    c_in = cw * WORD_BITS
    hblk, wp = (block_oh - 1) * stride + kh, in_w + kw - 1
    hh, wh = -(-hblk // stride), -(-wp // stride)
    sc_rows = block_m if stride == 1 else nb * 2 * block_oh * in_w
    bf16_rows = 16      # sublanes of a bf16 tile
    terms = [
        VmemTerm("row_band_block", nb * hblk * _ceil_mult(wp, SUBLANE)
                 * _lanes(cw) * 4, 2),
        VmemTerm("weight_block", kh * kw * _ceil_mult(cw, SUBLANE)
                 * _lanes(bn) * 4, 2),
        VmemTerm("correction_block", block_m * _lanes(bn) * 4, 2),
        VmemTerm("epilogue_block", EPI_ROWS * _lanes(bn) * 4, 2),
        VmemTerm("shortcut_block", sc_rows * _lanes(bn) * 4, 2),
        VmemTerm("stream_block", block_m * _lanes(bn) * 4, 2),
        VmemTerm("packed_block", block_m * LANE * 4, 2),
        VmemTerm("acc_tile", block_m * _lanes(bn) * 4),
        VmemTerm("epilogue_tiles", 2 * block_m * _lanes(bn) * 4),
    ]
    if route == "mxu":
        terms += [
            VmemTerm("byte_spread_block", _ceil_mult(4 * cw, bf16_rows)
                     * _lanes(c_in) * 2),
            # ±1 bf16 operands, unpacked in VMEM: the row band by phase,
            # and every tap of the C_out block's weights.
            VmemTerm("row_band_scratch", stride * stride * nb * hh
                     * _ceil_mult(wh, bf16_rows) * _lanes(c_in) * 2),
            VmemTerm("weight_scratch", kh * kw * _ceil_mult(c_in, bf16_rows)
                     * _lanes(bn) * 2),
            # The row band's unpack (spread f32, bits int32, ±1 f32 and
            # bf16) a phase at a time, one tap window, one dot's sums.
            VmemTerm("unpack_tiles", _ceil_mult(nb * hh * wh, SUBLANE)
                     * _lanes(c_in) * 14),
            VmemTerm("tap_window", block_m * _lanes(c_in) * 2),
            VmemTerm("dot_tile", block_m * _lanes(bn) * 4),
        ]
    return tuple(terms)


def residual_conv_estimate(batch: int, in_hw: tuple[int, int], cw: int,
                           kh: int, kw: int, c_out: int,
                           out_hw: tuple[int, int], *,
                           stride: int) -> LaunchEstimate:
    """Estimate the residual conv launch: the tile's padded packed input
    row band, the tap-major weight block, the correction, epilogue rows,
    shortcut and both output tiles (all double-buffered, lanes padded to
    128), the accumulator and float epilogue transients; on the MXU
    route also the byte-spread matrix, the ±1 bf16 scratch of the
    unpacked row band and weights, and the unpack and tap window
    transients."""
    oh, ow = out_hw
    nb, block_oh, bn = residual_conv_blocks(batch, oh, ow, cw, c_out,
                                            stride=stride, kh=kh, kw=kw)
    return LaunchEstimate(
        kernel="conv_residual",
        grid=(c_out // bn, batch // nb, oh // block_oh),
        terms=_residual_conv_terms(nb, block_oh, ow, cw, bn,
                                   route=residual_conv_route(cw, c_out),
                                   stride=stride, kh=kh, kw=kw,
                                   in_w=in_hw[1]))


def pointwise_residual_estimate(m: int, kw: int, n: int,
                                c_shortcut: int) -> LaunchEstimate:
    """Estimate the residual pointwise launch: the packed row tile, the
    word-major weight block, epilogue rows, shortcut and both output
    tiles (double-buffered, lanes padded to 128), plus the accumulator
    and float epilogue transients."""
    bm, bn = pointwise_residual_blocks(m, kw, n)
    terms = (
        VmemTerm("row_block", bm * _lanes(kw) * 4, 2),
        VmemTerm("weight_block", _ceil_mult(kw, SUBLANE) * _lanes(bn) * 4,
                 2),
        VmemTerm("epilogue_block", EPI_ROWS * _lanes(bn) * 4, 2),
        VmemTerm("shortcut_block", bm * _lanes(min(bn, c_shortcut)) * 4, 2),
        VmemTerm("stream_block", bm * _lanes(bn) * 4, 2),
        VmemTerm("packed_block", bm * LANE * 4, 2),
        VmemTerm("acc_tile", bm * _lanes(bn) * 4),
        VmemTerm("epilogue_tiles", 2 * bm * _lanes(bn) * 4),
    )
    return LaunchEstimate(kernel="pointwise_residual",
                          grid=(_ceil_mult(m, bm) // bm, n // bn),
                          terms=terms)


def attention_estimate(b: int, hq: int, sq: int, skv: int, dw: int,
                       dv: int, *, block_q: int = 128,
                       block_kv: int = 128) -> LaunchEstimate:
    """Estimate the packed flash-attention launch
    (``kernels.binary_attention.binary_attention_packed``)."""
    sq_p = _ceil_mult(sq, block_q)
    skv_p = _ceil_mult(skv, block_kv)
    dw_p = _ceil_mult(dw, LANE)
    dv_p = _ceil_mult(dv, LANE)
    terms = (
        VmemTerm("q_block", block_q * dw_p * 4, 2),
        VmemTerm("k_block", block_kv * dw_p * 4, 2),
        VmemTerm("v_block", block_kv * dv_p * 4, 2),
        VmemTerm("out_block", block_q * dv_p * 4, 2),
        VmemTerm("m_scratch", block_q * LANE * 4),
        VmemTerm("l_scratch", block_q * LANE * 4),
        VmemTerm("acc_scratch", block_q * dv_p * 4),
        VmemTerm("scores_tile", block_q * block_kv * 4),
    )
    return LaunchEstimate(kernel="binary_attention",
                          grid=(b * hq, sq_p // block_q, skv_p // block_kv),
                          terms=terms)


def bitpack_estimate(m: int, k: int, *, block_m: int = 256,
                     block_kw: int = 128) -> LaunchEstimate:
    """Estimate the sign-binarize + bitpack launch (``kernels.bitpack``)."""
    kw = -(-k // WORD_BITS)
    block_m = min(block_m, _ceil_mult(m, SUBLANE))
    block_kw = min(block_kw, _ceil_mult(kw, LANE // WORD_BITS))
    block_k = block_kw * WORD_BITS
    mp = _ceil_mult(m, block_m)
    kp = _ceil_mult(k, block_k)
    terms = (
        VmemTerm("x_block", block_m * block_k * 4, 2),
        VmemTerm("out_block", block_m * block_kw * 4, 2),
        VmemTerm("bits_tile", block_m * block_k * 4),
    )
    return LaunchEstimate(kernel="bitpack",
                          grid=(mp // block_m, kp // block_k), terms=terms)


def bn_sign_pack_estimate(m: int, c: int, *, block_m: int = 256,
                          block_cw: int = LANE) -> LaunchEstimate:
    """Estimate the standalone BN-sign-repack epilogue launch
    (``kernels.fused_epilogue.bn_sign_pack``)."""
    cw = -(-c // WORD_BITS)
    block_m = min(block_m, _ceil_mult(m, SUBLANE))
    block_cw = min(block_cw, _ceil_mult(cw, LANE // WORD_BITS))
    block_c = block_cw * WORD_BITS
    mp = _ceil_mult(m, block_m)
    cp = _ceil_mult(c, block_c)
    terms = (
        VmemTerm("x_block", block_m * block_c * 4, 2),
        VmemTerm("tau_block", block_c * 4, 2),
        VmemTerm("flip_block", block_c * 4, 2),
        VmemTerm("out_block", block_m * block_cw * 4, 2),
        VmemTerm("bits_tile", block_m * block_c * 4),
    )
    return LaunchEstimate(kernel="bn_sign_pack",
                          grid=(mp // block_m, cp // block_c), terms=terms)


# ---------------------------------------------------------------------------
# Traced estimator (per-launch ground truth for the analysis report)
# ---------------------------------------------------------------------------

def _block_dims(block_shape: Sequence[Any]) -> list[int]:
    """Block dims as ints (``Blocked(n)`` counts n, squeezed dims 1)."""
    return [int(getattr(d, "block_size", 1)) for d in block_shape]


def estimate_eqn(eqn: Any) -> LaunchEstimate:
    """VMEM estimate of one traced ``pallas_call`` eqn, from its
    ``grid_mapping`` block shapes + dtypes and its scratch avals.

    A block that covers its whole operand array is pinned (1 buffer);
    a tiled block is double-buffered (2).  Kernel-internal compute
    transients are invisible at this level — the closed-form
    estimators account for those.
    """
    gm = eqn.params["grid_mapping"]
    terms: list[VmemTerm] = []
    n_in = gm.num_inputs
    for i, bm in enumerate(gm.block_mappings):
        asd = bm.array_aval
        dims = _block_dims(bm.block_shape)
        nbytes = _prod(dims) * asd.dtype.itemsize
        pinned = dims == [int(d) for d in asd.shape]
        role = "in" if i < n_in else "out"
        terms.append(VmemTerm(f"{role}{i if i < n_in else i - n_in}_block",
                              nbytes, 1 if pinned else 2))
    for j, aval in enumerate(gm.scratch_avals):
        inner = getattr(aval, "inner_aval", aval)
        if hasattr(inner, "size") and hasattr(inner, "dtype"):
            terms.append(VmemTerm(f"scratch{j}",
                                  int(inner.size) * inner.dtype.itemsize))
    return LaunchEstimate(kernel=graph.kernel_name(eqn),
                          grid=tuple(int(g) for g in gm.grid),
                          terms=tuple(terms))


def estimate_forward(fn: Any, *args: Any) -> list[LaunchEstimate]:
    """Traced VMEM estimate of every launch in ``fn``, in trace order."""
    return [estimate_eqn(eqn) for eqn in graph.pallas_eqns(fn, *args)]
