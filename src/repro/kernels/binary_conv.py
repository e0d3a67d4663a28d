"""Pallas TPU kernel: fused bit-packed binary 2-D convolution (paper C5/C6).

The paper's headline claim is *dedicated convolutional layers for BCNNs*
that keep data bit-packed end-to-end.  The previous packed conv path did
im2col in plain jnp **outside** any kernel — materializing the full
(B·H'·W', KH·KW·Cw) patch matrix in HBM — then ran the packed GEMM over
it.  This kernel performs im2col **inside** the kernel:

* the channel-packed input image lives in VMEM ((Hp, Wp, Cw) uint32,
  channels packed 32/word, paper C3 "free lift" layout),
* each program slices its M tile's input slab from the VMEM-resident
  image with ``pl.ds`` (rows ``m·block_oh·stride`` onward), then for each
  of the KH·KW taps takes a strided in-VMEM slice of the slab (the
  im2col gather — never written back to HBM),
* XNOR-popcount accumulates word-by-word into an int32 accumulator
  (one full (block_m, bn) VPU op per packed word, same scheme as
  ``binary_matmul``),
* the epilogue folds the paper's pad-as-(−1) correction matrix (C5), and
  optionally the BN-sign threshold + re-bitpack (``fused_epilogue``), so
  the activation leaves the kernel already packed for the next layer.

Grid: ``(batch, M tiles of OH·OW, C_out blocks)``.  The M dimension is
tiled by output *rows* — an M tile is ``block_oh`` rows = ``block_oh·OW``
flattened output pixels — so each tile's input slab is a contiguous row
band of the image and the contraction is complete per program (no
cross-step scratch accumulator).  The image BlockSpec depends only on
the batch index, so Pallas holds one image DMA resident in VMEM across
all (m, j) steps of a batch element while the pipeline emitter
double-buffers the streaming blocks (weights, correction, output tiles)
— and prefetches the *next* batch element's image DMA under the current
batch's compute.

The first-layer fixed-precision conv (paper C4) is a third kernel,
:func:`bitplane_conv2d_packed`: the 8 bit-plane images ride along in one
VMEM block and an in-kernel plane loop reuses the resident image across
planes, folding the ``2^i`` plane weighting and the rowsum form of the
pad correction into the epilogue — one kernel launch where the model
previously issued 8 sequential plane convs.

ReActNet's residual conv is a fourth, :func:`binary_conv2d_residual_packed`:
the same pad correction, but the contraction on the MXU — the packed
words unpacked to ±1 bf16 in VMEM and one dot a tap — then an epilogue
that adds a float32 shortcut and writes both the float stream and its
packed sign (``fused_epilogue.residual_epilogue``).

Supported: arbitrary integer stride (paper evaluates 1 and 2), SAME,
VALID or explicit padding; spatial padding is staged as all-zero words
(bit 0 == −1, the paper's convention) and corrected exactly in the
epilogue.  The BCNN's kernels slice taps out of a loaded slab, which
Mosaic allows at stride 1 only; the residual kernel splits its unpacked
row band by stride phase, so each tap is a contiguous read.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.analysis import vmem
from repro.core import binarize as B
from repro.kernels.fused_epilogue import (EPI_ROWS, avg_pool2x2,
                                          bn_sign_bits_to_words,
                                          check_block_lanes, pack_lanes,
                                          pad_bn_params, residual_epilogue,
                                          unblock_packed)

# Minimum tile granularity on TPU: (8 sublanes, 128 lanes).
_LANE = 128

# Default M-tile budget: ~this many output pixels per tile.  Small images
# fit in one tile (the pre-tiling behaviour); serving-sized spatial dims
# stream in row bands so the output/correction tiles stay VMEM-friendly.
_DEFAULT_TILE_M = 1024


# ---------------------------------------------------------------------------
# Conv plan: geometry + one-time weight packing (paper C2/C3/C5)
# ---------------------------------------------------------------------------

def conv_geometry(input_hw: tuple[int, int], kh: int, kw: int, stride: int,
                  padding) -> tuple[tuple[int, int], tuple]:
    """Output spatial size and ((top, bottom), (left, right)) pads.

    ``padding``: 'SAME' or 'VALID', matching XLA's conventions (extra
    pad goes low-index-last, i.e. bottom/right), so the packed path lines
    up pixel-for-pixel with ``jax.lax.conv_general_dilated``; or explicit
    ((top, bottom), (left, right)) pads, as PyTorch's ``padding=1`` is
    ((1, 1), (1, 1)) at any stride where 'SAME' pads (0, 1) at stride 2.
    """
    h, w = input_hw
    if not isinstance(padding, str):
        (pt, pb), (pl_, pr) = padding
        pads = ((int(pt), int(pb)), (int(pl_), int(pr)))
        if min(pt, pb, pl_, pr) < 0:
            raise ValueError(f"pads must be >= 0, got {padding!r}")
        out_h = (h + pt + pb - kh) // stride + 1
        out_w = (w + pl_ + pr - kw) // stride + 1
    elif padding == "SAME":
        out_h = -(-h // stride)
        out_w = -(-w // stride)
        pad_h = max((out_h - 1) * stride + kh - h, 0)
        pad_w = max((out_w - 1) * stride + kw - w, 0)
        pads = ((pad_h // 2, pad_h - pad_h // 2),
                (pad_w // 2, pad_w - pad_w // 2))
    elif padding == "VALID":
        out_h = (h - kh) // stride + 1
        out_w = (w - kw) // stride + 1
        pads = ((0, 0), (0, 0))
    else:
        raise ValueError(f"padding must be SAME, VALID or explicit "
                         f"((top, bottom), (left, right)), got {padding!r}")
    if out_h <= 0 or out_w <= 0:
        raise ValueError(
            f"conv output would be empty: input {input_hw}, kernel "
            f"({kh}, {kw}), stride {stride}, {padding} padding")
    return (out_h, out_w), pads


def make_conv_plan(w: jax.Array, *, input_hw: tuple[int, int],
                   stride: int = 1, padding="SAME") -> dict:
    """Pack conv weights per-tap along channels (C3) and precompute the

    zero-padding correction matrix (C5) for the layer's input size.

    ``w``: (C_out, KH, KW, C_in) latent fp weights.  The packed kernel
    treats padded pixels as −1, so the true zero-pad result is
    ``packed_result + conv(pad_indicator, Σ_c w)`` — computed once here.

    Returns the plan dict consumed by every conv backend (Pallas / jnp /
    ref): packed weights, geometry statics, and the correction.
    """
    c_out, kh, kw, c_in = w.shape
    wsign = B.sign_pm1(w)
    # Per-tap channel packing: (O, KH*KW, I) -> pack I -> (O, KH*KW*Iw).
    w_packed = B.pack_bits(wsign.reshape(c_out, kh * kw, c_in)
                           ).reshape(c_out, -1)

    (out_h, out_w), pads = conv_geometry(input_hw, kh, kw, stride, padding)
    h, wdt = input_hw

    # Correction (C5): pad_mask is 1 on the padded ring, 0 inside.  The
    # packed conv computes Σ w·(−1) at pad taps; truth is 0, so add
    # +Σ_{pad taps} w == valid-correlate(pad_mask, Σ_c w).
    pad_mask = jnp.pad(jnp.zeros((h, wdt), jnp.float32), pads,
                       constant_values=1.0)
    w_tap_sum = wsign.sum(axis=3)                     # (O, KH, KW)
    corr = jax.lax.conv_general_dilated(
        pad_mask[None, :, :, None],
        jnp.transpose(w_tap_sum, (1, 2, 0))[:, :, None, :],  # HWIO, I=1
        window_strides=(stride, stride), padding="VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))[0]       # (H', W', O)

    return {
        "w_packed": w_packed, "k_true": kh * kw * c_in,
        "kh": kh, "kw": kw, "c_in": c_in, "c_out": c_out,
        "cw": B.packed_width(c_in),
        "stride": stride, "pads": pads,
        "in_hw": (h, wdt), "out_hw": (out_h, out_w),
        "correction": corr.astype(jnp.int32),
    }


def make_bitplane_conv_plan(w: jax.Array, *, input_hw: tuple[int, int],
                            stride: int = 1, padding: str = "SAME",
                            nbits: int = 8) -> dict:
    """Conv plan for the first-layer bit-plane conv (paper C4).

    Per-plane the plane identity  x·w = 1/2 Σ_i 2^i (p̂_i ⊛ w + Σ_taps w)
    holds, where the all-taps rowsum replaces BOTH the {0,1}->±1 shift and
    the pad correction: a zero-padded pixel has every plane bit 0
    (p̂ = −1), so its per-plane contribution (−Σw + Σw) vanishes exactly.
    The C5 correction matrix is therefore identically zero and the plan
    carries none (passing a bitplane plan to the ±1 conv ops fails
    loudly rather than silently dropping the rowsum).
    """
    plan = make_conv_plan(w, input_hw=input_hw, stride=stride,
                          padding=padding)
    wsign = B.sign_pm1(w)
    plan["rowsum"] = wsign.sum(axis=(1, 2, 3)).astype(jnp.int32)
    del plan["correction"]
    plan["nbits"] = nbits
    return plan


def make_residual_conv_plan(w: jax.Array, *, input_hw: tuple[int, int],
                            stride: int) -> dict:
    """Conv plan for :func:`binary_conv2d_residual_packed`, the residual
    binary conv of ReActNet: PyTorch's symmetric ``padding=kh//2`` at
    either stride, and the packed weights stored tap- and word-major,
    ``w_taps`` (KH·KW, Cw, C_out), so the kernel's rolled tap loop reads
    one tap's words as sublane rows of one block."""
    c_out, kh, kw, _ = w.shape
    plan = make_conv_plan(w, input_hw=input_hw, stride=stride,
                          padding=((kh // 2, kh // 2), (kw // 2, kw // 2)))
    plan["w_taps"] = plan.pop("w_packed").reshape(
        c_out, kh * kw, plan["cw"]).transpose(1, 2, 0)
    return plan


# ---------------------------------------------------------------------------
# Block-size resolution (the knobs `ops.py` exposes)
# ---------------------------------------------------------------------------

def resolve_block_n(block_n: int | None, c_out: int) -> int:
    """Validate/resolve the C_out block size.

    ``None`` -> one lane group (128).  Explicit values must be positive
    multiples of 128: silently *clamping up* a too-small user value used
    to hide mis-tuned configs, so it is now an error (clamping *down* to
    the padded C_out is still done — it only trims over-padding).
    """
    if block_n is None:
        block_n = _LANE
    check_block_lanes("block_n", block_n)
    return min(block_n, _ceil_mult(c_out, _LANE))


def resolve_block_oh(block_oh: int | None, oh: int, ow: int) -> int:
    """Validate/resolve the M-tile height (output rows per tile).

    ``None`` picks the largest row band whose flattened pixel count stays
    within ``_DEFAULT_TILE_M`` (whole image when it fits — the untiled
    pre-refactor grid).  Explicit values must be in [1, OH].
    """
    if block_oh is None:
        return max(1, min(oh, _DEFAULT_TILE_M // max(ow, 1) or 1))
    if not 1 <= block_oh:
        raise ValueError(f"block_oh must be >= 1, got {block_oh}")
    return min(block_oh, oh)


# ---------------------------------------------------------------------------
# The kernels
# ---------------------------------------------------------------------------

def _tile_slab(x_ref, prefix: tuple, tile, *, block_oh: int, stride: int,
               kh: int) -> jax.Array:
    """Read this M tile's input row band out of the VMEM-resident image.

    ``x_ref``: ref whose trailing dims are (Hp, Wp, Cw); ``prefix``
    indexes the leading dims (batch slot / plane).  Tile ``m`` (grid dim
    1) covers output rows [m·block_oh, (m+1)·block_oh), which read input
    rows [m·block_oh·stride, m·block_oh·stride + (block_oh−1)·stride
    + kh); ``tile`` is ``pl.program_id(1)``, read by the caller outside
    any loop (interpret mode cannot resolve it inside one).  The
    ``pl.ds`` ref read loads ONLY the slab — the rest of the
    image stays in VMEM untouched.  The host wrapper pads Hp so the last
    tile's slab stays in bounds.
    """
    row0 = tile * (block_oh * stride)
    hblk = (block_oh - 1) * stride + kh
    return x_ref[(*prefix, pl.ds(row0, hblk))]


def _tap_mismatch(xs: jax.Array, w: jax.Array, *, kh, kw, stride, n_rows,
                  ow, cw) -> jax.Array:
    """In-VMEM im2col + XNOR-popcount mismatch accumulation.

    ``xs``: ((n_rows−1)·stride + kh, Wp, Cw) input slab, ``w``: (bn,
    KH·KW·Cw) tap-major packed weights.  Returns the (n_rows·ow, bn)
    int32 total mismatch count over all taps and packed words.
    """
    m = n_rows * ow
    bn = w.shape[0]
    acc = jnp.zeros((m, bn), jnp.int32)
    for di in range(kh):
        for dj in range(kw):
            # The im2col gather for tap (di, dj): a strided slice of the
            # VMEM-resident slab — never materialized as a patch matrix.
            tap = jax.lax.slice(
                xs, (di, dj, 0),
                (di + (n_rows - 1) * stride + 1,
                 dj + (ow - 1) * stride + 1, cw),
                (stride, stride, 1))                    # (n_rows, OW, Cw)
            a = tap.reshape(m, cw)
            base = (di * kw + dj) * cw
            for c in range(cw):
                aw = jax.lax.slice_in_dim(a, c, c + 1, axis=1)      # (m, 1)
                ww = jax.lax.slice_in_dim(w, base + c, base + c + 1,
                                          axis=1)                   # (bn, 1)
                # One full (m, bn) VPU op per packed word.
                mism = jax.lax.population_count(aw ^ ww.reshape(1, bn))
                acc = acc + mism.astype(jnp.int32)
    return acc


def _conv_kernel(x_ref, w_ref, corr_ref, o_ref, *, kh, kw, stride, block_oh,
                 ow, cw, k_true):
    """In-kernel im2col + XNOR-popcount, int32 output tile."""
    y = _conv_accumulate(x_ref, w_ref, corr_ref, kh=kh, kw=kw, stride=stride,
                         block_oh=block_oh, ow=ow, cw=cw, k_true=k_true)
    o_ref[0] = y


def _conv_bn_sign_kernel(x_ref, w_ref, corr_ref, tau_ref, flip_ref, o_ref, *,
                         kh, kw, stride, block_oh, ow, cw, k_true):
    """Fused variant: conv -> BN-sign threshold -> re-bitpack (uint32)."""
    y = _conv_accumulate(x_ref, w_ref, corr_ref, kh=kh, kw=kw, stride=stride,
                         block_oh=block_oh, ow=ow, cw=cw, k_true=k_true)
    o_ref[0, 0] = bn_sign_bits_to_words(y, tau_ref[...], flip_ref[...])


def _conv_accumulate(x_ref, w_ref, corr_ref, *, kh, kw, stride, block_oh, ow,
                     cw, k_true):
    """Shared body: slab-slice this tile, popcount-accumulate, + correction.

    Returns the (block_oh·ow, bn) int32 pre-epilogue conv output tile.
    """
    xs = _tile_slab(x_ref, (0,), pl.program_id(1), block_oh=block_oh,
                    stride=stride, kh=kh)
    mism = _tap_mismatch(xs, w_ref[...], kh=kh, kw=kw, stride=stride,
                         n_rows=block_oh, ow=ow, cw=cw)
    return jnp.int32(k_true) - 2 * mism + corr_ref[...]


def _bitplane_conv_kernel(x_ref, w_ref, rowsum_ref, o_ref, *, kh, kw, stride,
                          block_oh, ow, cw, k_true, nbits):
    """Single-launch first-layer conv: in-kernel loop over bit planes.

    ``x_ref``: (nbits, 1, Hp, Wp, Cw) — all planes of one batch element
    resident in VMEM, so the plane loop re-reads the same block instead
    of re-DMAing the image per plane.  The epilogue folds the 2^i plane
    weighting and the rowsum pad/shift correction:

        out = ( (2^n − 1)·(K + rowsum)  −  2·Σ_p 2^p·mism_p ) >> 1

    which is  1/2 Σ_p 2^p (K − 2·mism_p + rowsum)  — the exact integer
    identity of ``core.binarize.bitplane_dot`` per output pixel.  The
    pre-shift value is always even, and >> on int32 is arithmetic, so
    the halving is exact for negative accumulators too.
    """
    w = w_ref[...]
    m = block_oh * ow
    bn = w.shape[0]
    tile = pl.program_id(1)

    def plane(p, wacc):
        xs = _tile_slab(x_ref, (p, 0), tile, block_oh=block_oh,
                        stride=stride, kh=kh)
        mism = _tap_mismatch(xs, w, kh=kh, kw=kw, stride=stride,
                             n_rows=block_oh, ow=ow, cw=cw)
        return wacc + (mism << p)

    wacc = jax.lax.fori_loop(0, nbits, plane, jnp.zeros((m, bn), jnp.int32))
    full = jnp.int32((1 << nbits) - 1)
    o_ref[0] = (full * (jnp.int32(k_true) + rowsum_ref[...])
                - 2 * wacc) >> 1


def _unpack_lanes(words: jax.Array, sel: jax.Array) -> jax.Array:
    """(r, Cw) packed words -> (r, 32·Cw) ±1 float32, channel 32j + i (bit i
    of word j) on lane 32j + i; bit 1 -> +1, bit 0 -> −1.

    Splitting the lane axis into (Cw, 32) is a relayout Mosaic refuses,
    so the words go through the MXU: their 4 bytes side by side,
    (r, 4·Cw), times the 0/1 selection ``sel`` (4·Cw, 32·Cw)
    (:func:`_byte_spread`) puts each byte on its 8 lanes (bytes are
    below 256, exact in bf16), and each lane keeps bit ``lane % 8``.
    """
    v = jax.lax.bitcast_convert_type(words, jnp.int32)
    b = jnp.concatenate([(v >> (8 * k)) & 0xFF for k in range(4)], axis=1)
    spread = jnp.dot(b.astype(jnp.float32).astype(jnp.bfloat16), sel,
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    shift = jax.lax.broadcasted_iota(jnp.int32, spread.shape, 1) & 7
    return (2 * ((spread >> shift) & 1) - 1).astype(jnp.float32)


def _byte_spread(cw: int) -> jax.Array:
    """The (4·Cw, 32·Cw) bf16 0/1 matrix of :func:`_unpack_lanes`: row
    k·Cw + j (byte k of word j) feeds lanes 32j + 8k ... 32j + 8k + 7."""
    shape = (4 * cw, B.WORD_BITS * cw)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    sel = ((jax.lax.rem(row, cw) == lane >> 5)
           & (jax.lax.div(row, cw) == (lane >> 3) & 3))
    return sel.astype(jnp.bfloat16)


def _unpack_sublanes(words: jax.Array) -> jax.Array:
    """(Cw, n) packed weight words -> (32·Cw, n) ±1 bf16, channel 32j + i
    on row 32j + i: :func:`_unpack_lanes` mirrored, the selection on the
    left."""
    cw = words.shape[0]
    v = jax.lax.bitcast_convert_type(words, jnp.int32)
    row = jax.lax.broadcasted_iota(jnp.int32, (B.WORD_BITS * cw, cw), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (B.WORD_BITS * cw, cw), 1)
    spread = None
    for k in range(4):
        sel = ((row >> 5) == col) & (((row >> 3) & 3) == k)
        b = ((v >> (8 * k)) & 0xFF).astype(jnp.float32).astype(jnp.bfloat16)
        part = jnp.dot(sel.astype(jnp.float32).astype(jnp.bfloat16), b,
                       preferred_element_type=jnp.float32)
        spread = part if spread is None else spread + part
    spread = spread.astype(jnp.int32)
    shift = jax.lax.broadcasted_iota(jnp.int32, spread.shape, 0) & 7
    return (2 * ((spread >> shift) & 1) - 1).astype(jnp.bfloat16)


def _unpack_row_band(x_ref, sel_ref, xs_ref, *, stride) -> None:
    """Unpack the tile's packed row band (``x_ref``: (nb, hblk, Wp, Cw))
    into ``xs_ref`` (stride², nb, ⌈hblk/s⌉, ⌈Wp/s⌉, C_in) ±1 bf16, split
    by (row, column) phase at stride 2 so that every tap window is a
    contiguous read (Mosaic has strided loads of 32-bit data only)."""
    nb, hblk, wp, cw = x_ref.shape
    for pr in range(stride):
        for pc in range(stride):
            h, w = -(-(hblk - pr) // stride), -(-(wp - pc) // stride)
            if stride == 1:
                words = x_ref[...]
            else:
                words = x_ref[:, pl.ds(pr, h, stride=stride),
                              pl.ds(pc, w, stride=stride), :]
            pm1 = _unpack_lanes(words.reshape(nb * h * w, cw), sel_ref[...])
            # Reshaped while 32-bit: Mosaic splits bf16 rows only in pairs.
            xs_ref[pr * stride + pc, :, :h, :w] = pm1.reshape(
                nb, h, w, pm1.shape[-1]).astype(jnp.bfloat16)


def _tap_dots(xs_ref, ws_ref, *, kh, kw, stride, block_oh, ow) -> jax.Array:
    """Σ over the KH·KW taps of window (m, C_in) @ weights (C_in, bn), one
    MXU dot a tap, f32: K_tap − 2·mismatch per tap, exact (|sum| ≤ K)."""
    _, nb, _, _, c_in = xs_ref.shape
    m = nb * block_oh * ow
    acc = None
    for di in range(kh):
        for dj in range(kw):
            win = xs_ref[(di % stride) * stride + dj % stride, :,
                         pl.ds(di // stride, block_oh),
                         pl.ds(dj // stride, ow), :]
            part = jnp.dot(win.reshape(m, c_in), ws_ref[di * kw + dj],
                           preferred_element_type=jnp.float32)
            acc = part if acc is None else acc + part
    return acc


def _shortcut_tile(sc_ref, *, stride, nb, block_oh, ow) -> jax.Array:
    """This M tile's float shortcut, (nb·block_oh·ow, bn): the stream tile
    itself at stride 1 (``sc_ref`` block (1, nb·block_m, bn)), its 2x2
    average at stride 2 (block (nb, 2·block_oh, W, bn))."""
    if stride == 1:
        return sc_ref[0]

    def part(r, c):
        return sc_ref[:, pl.ds(r, block_oh, stride=2),
                      pl.ds(c, ow, stride=2), :]

    avg = avg_pool2x2(part(0, 0), part(0, 1), part(1, 0), part(1, 1))
    return avg.reshape(nb * block_oh * ow, avg.shape[-1])


def _rolled_tap_mismatch(x_ref, w_ref, *, kh, kw, stride, block_oh, ow,
                         cw) -> jax.Array:
    """XNOR-popcount mismatch count of one M tile on the VPU, over a
    rolled loop of the KH·KW taps.

    Each step reads its tap straight from the tile's padded input row
    band (``x_ref``: (nb, (block_oh−1)·stride + KH, Wp, Cw)), strided at
    stride 2, and adds one (nb·block_oh·ow, bn) popcount-of-XOR per
    packed word against the tap's weight rows (``w_ref``: (KH·KW, Cw,
    bn)).  Only the Cw words of one tap are unrolled, so compile time
    does not grow with KH·KW.
    """
    nb = x_ref.shape[0]
    m = nb * block_oh * ow
    bn = w_ref.shape[-1]

    def tap(t, acc):
        di, dj = t // kw, t % kw
        a = x_ref[:, pl.ds(di, block_oh, stride=stride),
                  pl.ds(dj, ow, stride=stride), :].reshape(m, cw)
        wt = w_ref[t]                                       # (Cw, bn)
        for c in range(cw):
            acc = acc + jax.lax.population_count(
                a[:, c:c + 1] ^ wt[c:c + 1, :]).astype(jnp.int32)
        return acc

    return jax.lax.fori_loop(0, kh * kw, tap, jnp.zeros((m, bn), jnp.int32))


def _residual_out(z, corr_ref, epi_ref, sc_ref, o_ref, p_ref, *, stride, nb,
                  block_oh, ow) -> None:
    """Pad correction, :func:`fused_epilogue.residual_epilogue` against the
    float shortcut; writes the float32 stream tile (``o_ref``) and the
    packed sign of stream + the next conv's bias (``p_ref``)."""
    sc = _shortcut_tile(sc_ref, stride=stride, nb=nb, block_oh=block_oh,
                        ow=ow)
    y, u = residual_epilogue(z + corr_ref[...], epi_ref[...], sc)
    o_ref[0] = y
    p_ref[0, 0] = pack_lanes((u >= 0).astype(jnp.int32))


def _conv_residual_mxu_kernel(x_ref, w_ref, sel_ref, corr_ref, epi_ref,
                              sc_ref, o_ref, p_ref, xs_ref, ws_ref, *, kh,
                              kw, stride, block_oh, ow):
    """Residual binary conv on the MXU: ±1 operands unpacked in VMEM, one
    bf16 dot a tap.  Grid (C_out blocks, image groups, M tiles): the
    C_out block's weights are unpacked into ``ws_ref`` at its first step
    and reused by every later one; each step unpacks its own row band
    into ``xs_ref``."""
    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        def tap(t, carry):
            ws_ref[t] = _unpack_sublanes(w_ref[t])
            return carry
        jax.lax.fori_loop(0, kh * kw, tap, 0)

    _unpack_row_band(x_ref, sel_ref, xs_ref, stride=stride)
    dots = _tap_dots(xs_ref, ws_ref, kh=kh, kw=kw, stride=stride,
                     block_oh=block_oh, ow=ow)
    _residual_out(dots.astype(jnp.int32), corr_ref, epi_ref, sc_ref, o_ref,
                  p_ref, stride=stride, nb=x_ref.shape[0], block_oh=block_oh,
                  ow=ow)


def _conv_residual_vpu_kernel(x_ref, w_ref, corr_ref, epi_ref, sc_ref, o_ref,
                              p_ref, *, kh, kw, stride, block_oh, ow, k_true):
    """Residual binary conv with XNOR-popcount on the VPU, for C_out blocks
    narrower than the MXU (``analysis.vmem.residual_conv_route``)."""
    mism = _rolled_tap_mismatch(x_ref, w_ref, kh=kh, kw=kw, stride=stride,
                                block_oh=block_oh, ow=ow,
                                cw=x_ref.shape[-1])
    _residual_out(jnp.int32(k_true) - 2 * mism, corr_ref, epi_ref, sc_ref,
                  o_ref, p_ref, stride=stride, nb=x_ref.shape[0],
                  block_oh=block_oh, ow=ow)


# ---------------------------------------------------------------------------
# Host-side wrappers
# ---------------------------------------------------------------------------

def _prep_operands(x_packed, w_packed, correction, *, pads, c_out, block_n,
                   block_oh, stride, kh, out_hw):
    """Stage every operand for the (batch, M tiles, C_out blocks) grid.

    * spatial zero-word padding (pad == all −1) on the image, plus extra
      zero rows so the last M tile's input slab stays in bounds,
    * C_out padding on weights/correction up to the block size,
    * OH padding on the correction up to a whole number of M tiles
      (padded output rows are computed then discarded by the caller).

    Works for both (B, H, W, Cw) images and (nbits, B, H, W, Cw) plane
    stacks — spatial axes are the last three.  ``correction=None`` (the
    bit-plane kernel, whose rowsum epilogue subsumes it) skips the
    correction staging and returns None in its slot.
    """
    lead = x_packed.ndim - 3
    xp = jnp.pad(x_packed,
                 ((0, 0),) * lead + (pads[0], pads[1], (0, 0)),
                 constant_values=0)
    oh, ow = out_hw
    m_tiles = -(-oh // block_oh)
    oh_p = m_tiles * block_oh
    need_h = (oh_p - 1) * stride + kh
    extra_h = max(0, need_h - xp.shape[lead])
    if extra_h:
        xp = jnp.pad(xp, ((0, 0),) * lead + ((0, extra_h), (0, 0), (0, 0)),
                     constant_values=0)
    c_out_p = _ceil_mult(c_out, block_n)
    w_p = B.pad_to_multiple(w_packed, block_n, 0)
    corr = None
    if correction is not None:
        corr = B.pad_to_multiple(correction.reshape(oh, ow, c_out),
                                 block_oh, 0)             # (OH_p, OW, C)
        corr = B.pad_to_multiple(corr.reshape(oh_p * ow, c_out), block_n, 1)
    return xp, w_p, corr, c_out_p, m_tiles, oh_p


@functools.partial(jax.jit, static_argnames=(
    "kh", "kw", "stride", "pads", "out_hw", "c_out", "k_true", "block_n",
    "block_oh", "interpret"))
def binary_conv2d_packed(x_packed: jax.Array, w_packed: jax.Array,
                         correction: jax.Array, *, kh: int, kw: int,
                         stride: int, pads, out_hw: tuple[int, int],
                         c_out: int, k_true: int, block_n: int | None = None,
                         block_oh: int | None = None,
                         interpret: bool = False) -> jax.Array:
    """Packed binary conv via Pallas; int32 output.

    ``x_packed``: (B, H, W, Cw) channel-packed uint32, ``w_packed``:
    (C_out, KH*KW*Cw) tap-major packed weights (from ``make_conv_plan``).
    Returns (B, OH, OW, C_out) int32 — the exact integer conv of the ±1
    tensors with true zero padding (pad-as-(−1) + correction, paper C5).

    ``block_oh``/``block_n`` tile the (OH·OW, C_out) output: the grid is
    (B, ⌈OH/block_oh⌉, ⌈C_out/block_n⌉) and the result is invariant to
    both knobs (property-tested in tests/test_conv_properties.py).
    """
    bsz = x_packed.shape[0]
    cw = x_packed.shape[-1]
    oh, ow = out_hw
    block_n = resolve_block_n(block_n, c_out)
    block_oh = resolve_block_oh(block_oh, oh, ow)
    xp, w_p, corr, c_out_p, m_tiles, oh_p = _prep_operands(
        x_packed, w_packed, correction, pads=pads, c_out=c_out,
        block_n=block_n, block_oh=block_oh, stride=stride, kh=kh,
        out_hw=out_hw)
    hp, wp = xp.shape[1:3]
    block_m = block_oh * ow
    grid = (bsz, m_tiles, c_out_p // block_n)

    kernel = functools.partial(_conv_kernel, kh=kh, kw=kw, stride=stride,
                               block_oh=block_oh, ow=ow, cw=cw,
                               k_true=k_true)
    out = pl.pallas_call(
        kernel,
        name="_conv_kernel",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hp, wp, cw), lambda b, m, j: (b, 0, 0, 0)),
            pl.BlockSpec((block_n, kh * kw * cw), lambda b, m, j: (j, 0)),
            pl.BlockSpec((block_m, block_n), lambda b, m, j: (m, j)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda b, m, j: (b, m, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, oh_p * ow, c_out_p), jnp.int32),
        interpret=interpret,
    )(xp, w_p, corr)
    return out[:, :oh * ow, :c_out].reshape(bsz, oh, ow, c_out)


@functools.partial(jax.jit, static_argnames=(
    "kh", "kw", "stride", "pads", "out_hw", "c_out", "k_true", "block_n",
    "block_oh", "interpret"))
def binary_conv2d_bn_sign_packed(x_packed: jax.Array, w_packed: jax.Array,
                                 correction: jax.Array, tau: jax.Array,
                                 flip: jax.Array, *, kh: int, kw: int,
                                 stride: int, pads, out_hw: tuple[int, int],
                                 c_out: int, k_true: int,
                                 block_n: int | None = None,
                                 block_oh: int | None = None,
                                 interpret: bool = False) -> jax.Array:
    """Fused conv + BN-sign-fold + re-bitpack; packed uint32 output.

    Same contraction (and same M-tiled grid) as
    :func:`binary_conv2d_packed`, but the epilogue thresholds against the
    folded BN (``tau``/``flip``, per C_out channel) and packs the
    resulting ±1 bits along C_out — the activation never leaves packed
    form in HBM.  Returns (B, OH, OW, ceil(C_out/32)) uint32,
    bit-identical to ``pack_bits(apply_bn_sign_folded(conv_out))``.
    """
    bsz = x_packed.shape[0]
    cw = x_packed.shape[-1]
    oh, ow = out_hw
    block_n = resolve_block_n(block_n, c_out)
    block_oh = resolve_block_oh(block_oh, oh, ow)
    assert block_n % B.WORD_BITS == 0
    xp, w_p, corr, c_out_p, m_tiles, oh_p = _prep_operands(
        x_packed, w_packed, correction, pads=pads, c_out=c_out,
        block_n=block_n, block_oh=block_oh, stride=stride, kh=kh,
        out_hw=out_hw)
    tau_p, flip_p = pad_bn_params(tau, flip, block_n)
    hp, wp = xp.shape[1:3]
    block_m = block_oh * ow
    n_blocks = c_out_p // block_n
    grid = (bsz, m_tiles, n_blocks)
    bnw = block_n // B.WORD_BITS

    kernel = functools.partial(_conv_bn_sign_kernel, kh=kh, kw=kw,
                               stride=stride, block_oh=block_oh, ow=ow,
                               cw=cw, k_true=k_true)
    out = pl.pallas_call(
        kernel,
        name="_conv_bn_sign_kernel",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, hp, wp, cw), lambda b, m, j: (b, 0, 0, 0)),
            pl.BlockSpec((block_n, kh * kw * cw), lambda b, m, j: (j, 0)),
            pl.BlockSpec((block_m, block_n), lambda b, m, j: (m, j)),
            pl.BlockSpec((1, block_n), lambda b, m, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda b, m, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_m, bnw),
                               lambda b, m, j: (b, j, m, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, n_blocks, oh_p * ow, bnw),
                                       jnp.uint32),
        interpret=interpret,
    )(xp, w_p, corr, tau_p, flip_p)
    cw_out = B.packed_width(c_out)
    out = unblock_packed(out)
    return out[:, :oh * ow, :cw_out].reshape(bsz, oh, ow, cw_out)


@functools.partial(jax.jit, static_argnames=(
    "kh", "kw", "stride", "pads", "out_hw", "c_out", "k_true", "nbits",
    "block_n", "block_oh", "interpret"))
def bitplane_conv2d_packed(x_planes: jax.Array, w_packed: jax.Array,
                           rowsum: jax.Array, *, kh: int, kw: int,
                           stride: int, pads, out_hw: tuple[int, int],
                           c_out: int, k_true: int, nbits: int,
                           block_n: int | None = None,
                           block_oh: int | None = None,
                           interpret: bool = False) -> jax.Array:
    """First-layer fixed-precision conv (paper C4) in ONE kernel launch.

    ``x_planes``: (nbits, B, H, W, Cw) packed bit-plane images (from
    ``core.binarize.pack_bitplanes_uint8`` — plane bit == packed bit, so
    plane value 0 encodes the ±1 value −1).  ``rowsum``: (C_out,) int32
    all-taps weight row sums (``make_bitplane_conv_plan``).  Returns
    (B, OH, OW, C_out) int32 == the exact integer conv of the raw
    fixed-precision input against sign(W) with true zero padding.

    Replaces the model's previous 8 sequential per-plane conv launches:
    all planes share one VMEM-resident image block and the plane loop,
    2^i weighting, and pad correction live in the kernel epilogue.
    """
    nb, bsz = x_planes.shape[:2]
    assert nb == nbits, (nb, nbits)
    cw = x_planes.shape[-1]
    oh, ow = out_hw
    block_n = resolve_block_n(block_n, c_out)
    block_oh = resolve_block_oh(block_oh, oh, ow)
    xp, w_p, _, c_out_p, m_tiles, oh_p = _prep_operands(
        x_planes, w_packed, None, pads=pads, c_out=c_out,
        block_n=block_n, block_oh=block_oh, stride=stride, kh=kh,
        out_hw=out_hw)
    rs = B.pad_to_multiple(rowsum.reshape(1, c_out).astype(jnp.int32),
                           block_n, 1)
    hp, wp = xp.shape[2:4]
    block_m = block_oh * ow
    grid = (bsz, m_tiles, c_out_p // block_n)

    kernel = functools.partial(_bitplane_conv_kernel, kh=kh, kw=kw,
                               stride=stride, block_oh=block_oh, ow=ow,
                               cw=cw, k_true=k_true, nbits=nbits)
    out = pl.pallas_call(
        kernel,
        name="_bitplane_conv_kernel",
        grid=grid,
        in_specs=[
            pl.BlockSpec((nbits, 1, hp, wp, cw),
                         lambda b, m, j: (0, b, 0, 0, 0)),
            pl.BlockSpec((block_n, kh * kw * cw), lambda b, m, j: (j, 0)),
            pl.BlockSpec((1, block_n), lambda b, m, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_m, block_n),
                               lambda b, m, j: (b, m, j)),
        out_shape=jax.ShapeDtypeStruct((bsz, oh_p * ow, c_out_p), jnp.int32),
        interpret=interpret,
    )(xp, w_p, rs)
    return out[:, :oh * ow, :c_out].reshape(bsz, oh, ow, c_out)


@functools.partial(jax.jit, static_argnames=(
    "kh", "kw", "stride", "pads", "out_hw", "c_out", "k_true", "interpret"))
def binary_conv2d_residual_packed(x_packed: jax.Array, w_taps: jax.Array,
                                  correction: jax.Array, epi: jax.Array,
                                  shortcut: jax.Array, *, kh: int, kw: int,
                                  stride: int, pads, out_hw: tuple[int, int],
                                  c_out: int, k_true: int,
                                  interpret: bool = False
                                  ) -> tuple[jax.Array, jax.Array]:
    """Residual binary conv (ReActNet's 3x3 block half) in one launch.

    ``x_packed``: (B, H, W, Cw) packed sign of the block input;
    ``w_taps``: (KH·KW, Cw, C_out) (``make_residual_conv_plan``);
    ``correction``: (OH, OW, C_out) int32 pad correction; ``epi``: (8,
    C_out) float32 epilogue rows (``fused_epilogue.EPI_*``);
    ``shortcut``: (B, H, W, C_out) float32 stream, 2x2-averaged in the
    kernel at stride 2.  Returns the float32 stream (B, OH, OW, C_out)
    and its packed sign plus the next bias, (B, OH, OW, ceil(C_out/32))
    uint32.

    The contraction's route comes from the shapes
    (``analysis.vmem.residual_conv_route``): ±1 bf16 operands unpacked in
    VMEM and one MXU dot a tap where C_in and C_out each fill a 128-lane
    group, XOR-popcount on the VPU below that.  Grid (C_out blocks,
    image groups, M tiles), blocks from the shapes
    (``analysis.vmem.residual_conv_blocks``): an M tile is ``block_oh``
    output rows of one image, or ``nb`` whole images where one image is
    fewer rows than the tile.  Each M tile reads only its input row
    band, halo rows included (element offsets): the packed words of a
    row sit on lanes, so a whole image of 1-2 words a pixel would take
    128 times its size in VMEM.  A C_out of at most 128 lanes is one
    block as wide as the array, not padded to 128.
    """
    bsz, h, w, cw = x_packed.shape
    oh, ow = out_hw
    route = vmem.residual_conv_route(cw, c_out)
    nb, block_oh, block_n = vmem.residual_conv_blocks(bsz, oh, ow, cw, c_out,
                                                      stride=stride, kh=kh,
                                                      kw=kw)
    xp = jnp.pad(x_packed, ((0, 0), pads[0], pads[1], (0, 0)))
    wp = xp.shape[2]
    hblk = (block_oh - 1) * stride + kh
    block_m = nb * block_oh * ow
    m_tiles = oh // block_oh
    n_blocks = c_out // block_n
    bnw = block_n // B.WORD_BITS
    groups = bsz // nb
    grid = (n_blocks, groups, m_tiles)
    if stride == 1:
        sc = shortcut.reshape(groups, nb * oh * ow, c_out)
        sc_spec = pl.BlockSpec((1, block_m, block_n),
                               lambda j, g, m: (g, m, j))
    else:
        if stride != 2 or (h, w) != (2 * oh, 2 * ow):
            raise ValueError(f"the residual conv takes stride 1, or stride 2 "
                             f"on an even input; got stride {stride}, input "
                             f"{(h, w)}, output {out_hw}")
        sc = shortcut
        sc_spec = pl.BlockSpec((nb, 2 * block_oh, w, block_n),
                               lambda j, g, m: (g, m, 0, j))
    corr = jnp.tile(correction.reshape(oh * ow, c_out), (nb, 1))
    x_spec = pl.BlockSpec((pl.Element(nb), pl.Element(hblk), pl.Element(wp),
                           pl.Element(cw)),
                          lambda j, g, m: (g * nb, m * block_oh * stride, 0, 0))
    w_spec = pl.BlockSpec((kh * kw, cw, block_n), lambda j, g, m: (0, 0, j))
    tile_specs = [
        pl.BlockSpec((block_m, block_n), lambda j, g, m: (m, j)),
        pl.BlockSpec((EPI_ROWS, block_n), lambda j, g, m: (0, j)),
        sc_spec,
    ]
    statics = dict(kh=kh, kw=kw, stride=stride, block_oh=block_oh, ow=ow)
    if route == "mxu":
        c_in = cw * B.WORD_BITS
        kernel = functools.partial(_conv_residual_mxu_kernel, **statics)
        in_specs = [x_spec, w_spec,
                    pl.BlockSpec((4 * cw, c_in), lambda j, g, m: (0, 0)),
                    *tile_specs]
        operands = (xp, w_taps, _byte_spread(cw), corr, epi, sc)
        scratch = [
            pltpu.VMEM((stride * stride, nb, -(-hblk // stride),
                        -(-wp // stride), c_in), jnp.bfloat16),
            pltpu.VMEM((kh * kw, c_in, block_n), jnp.bfloat16)]
    else:
        kernel = functools.partial(_conv_residual_vpu_kernel, k_true=k_true,
                                   **statics)
        in_specs = [x_spec, w_spec, *tile_specs]
        operands = (xp, w_taps, corr, epi, sc)
        scratch = []

    y, p = pl.pallas_call(
        kernel,
        name="_conv_residual_kernel",
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_m, block_n), lambda j, g, m: (g, m, j)),
            pl.BlockSpec((1, 1, block_m, bnw), lambda j, g, m: (g, j, m, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((groups, nb * oh * ow, c_out), jnp.float32),
            jax.ShapeDtypeStruct((groups, n_blocks, nb * oh * ow, bnw),
                                 jnp.uint32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands)
    return (y.reshape(bsz, oh, ow, c_out),
            unblock_packed(p).reshape(bsz, oh, ow, n_blocks * bnw))


def _ceil_mult(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
