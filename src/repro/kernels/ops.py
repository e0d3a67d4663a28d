"""Public jit'd wrappers for the Pallas kernels.

On a real TPU the kernels run compiled; on CPU (this container, CI) they
run in ``interpret=True`` mode, which executes the kernel body in Python
with identical semantics — the correctness contract is enforced against
``ref.py`` either way.

Shared argument semantics (every dispatcher in this module):

* ``backend``: ``'pallas'`` (the kernel subsystem), ``'jnp'`` (pure-jnp
  path), ``'ref'`` (the slow oracle), or ``'auto'`` (pallas on TPU, jnp
  elsewhere).  Any other string raises ``ValueError`` — backend typos
  never silently fall through to a different implementation
  (see :func:`_resolve`).
* Grid/blocking knobs (``block_oh``, ``block_n``, ``block_m``,
  ``block_kw``, ``words_per_step``) only affect the pallas backend, are
  *validated* rather than clamped, and never change the output
  (property-tested).  ``None`` always means "auto-size".
* **VMEM preflight**: before any Pallas launch, the dispatcher runs the
  shape-only static estimator (``analysis.vmem``) against the per-core
  budget (16 MiB default; ``REPRO_VMEM_BUDGET_BYTES`` overrides).  An
  over-budget launch raises ``analysis.vmem.VmemBudgetError`` with a
  per-term breakdown at Python call time — before jit traces, compiles,
  or (on CPU) interprets anything.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.analysis import vmem as _vmem
from repro.core import binarize as B
from repro.kernels import binary_attention as _batt
from repro.kernels import binary_conv as _bconv
from repro.kernels import binary_matmul as _bmm
from repro.kernels import bitpack as _bp
from repro.kernels import fused_epilogue as _fe
from repro.kernels import ref as _ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(backend: str) -> str:
    """Single point of backend resolution for EVERY public dispatcher.

    Re-implementing the 'auto' check inline used to let a typo like
    ``backend="pallsa"`` fall through to the jnp path silently; routing
    everything here makes an unknown backend a loud ValueError.
    """
    if backend == "auto":
        return "pallas" if _on_tpu() else "jnp"
    if backend not in ("pallas", "jnp", "ref"):
        raise ValueError(f"unknown backend {backend!r}")
    return backend


def _words_per_step(words_per_step: int | None) -> int:
    return (_bmm.DEFAULT_WORDS_PER_STEP if words_per_step is None
            else words_per_step)


def dispatch_batch(m: int, kw_words: int) -> str:
    """The GEMV-vs-GEMM batch-dispatch seam (paper §6.2).

    Given a flush of ``m`` rows contracting ``kw_words`` packed uint32
    words of K, returns which dense grid the Pallas backend lowers to:

    * ``'gemv'`` — ``m`` ≤ 8 (the TPU sublane minimum) and the
      lane-padded K extent fits the resident activation block
      (≤ 4096 words = 128K logical K).  N-major 1-D grid: the packed
      activation is pinned in VMEM, weight row blocks stream past it,
      no cross-step accumulator.  The single-query / small-batch
      serving path.
    * ``'gemm'`` — everything else.  The (M tiles, N tiles, K blocks)
      blocked grid with a VMEM accumulator.

    This is the ONE routing rule: :func:`binary_matmul_packed`,
    :func:`binary_matmul_bn_sign_packed`, and the serving layer
    (``train.serve.PackedInferenceServer``) all consult it, so a
    batching policy can never disagree with the kernels about which
    launch shape a flush takes (asserted on traced grids in
    ``tests/test_serve_batching.py``).

    Raises ``ValueError`` if ``m`` or ``kw_words`` is not a positive
    integer.

    Every routing decision bumps ``ops.dispatch.gemv`` /
    ``ops.dispatch.gemm`` on the process-wide telemetry registry
    (``telemetry.default()``) — dispatch has no object to hang a
    registry on, and the counter pair is the CI invariant "a batch-1
    serve never took the GEMM grid" (``docs/observability.md``).
    """
    if m < 1 or kw_words < 1:
        raise ValueError(
            f"dispatch_batch needs positive (m, kw_words), got "
            f"({m}, {kw_words})")
    route = _bmm.dispatch_batch(m, kw_words)
    telemetry.default().metrics.counter(f"ops.dispatch.{route}").inc()
    return route


def binary_matmul(a: jax.Array, b: jax.Array, *, backend: str = "auto",
                  words_per_step: int | None = None) -> jax.Array:
    """End-to-end binary GEMM on real-valued operands.

    ``a``: (M, K), ``b``: (N, K).  Sign-binarizes both, packs, and runs the
    XNOR-popcount GEMM.  Returns (M, N) int32.

    backend: 'pallas' | 'jnp' | 'ref' | 'auto' (pallas on TPU, jnp else);
    unknown strings raise ``ValueError``.  Packing goes through the
    :func:`bitpack` dispatcher, so the pallas backend packs with the
    pallas kernel (it used to fall back to the host-side ``pack_bits``
    even when a Pallas GEMM followed).  ``words_per_step`` forwards to
    :func:`binary_matmul_packed` (pallas only; must be a positive
    divisor of 128 — anything else raises ``ValueError``).
    """
    backend = _resolve(backend)
    if backend == "ref":
        return _ref.binary_matmul_ref(a, b)
    k = a.shape[-1]
    a_p = bitpack(a, backend=backend)
    b_p = bitpack(b, backend=backend)
    return binary_matmul_packed(a_p, b_p, k_true=k, backend=backend,
                                words_per_step=words_per_step)


def binary_matmul_packed(a_packed: jax.Array, b_packed: jax.Array, *,
                         k_true: int, backend: str = "auto",
                         words_per_step: int | None = None) -> jax.Array:
    """Binary GEMM on pre-packed operands (weights packed once, paper C2).

    ``a_packed``: (M, Kw) uint32, ``b_packed``: (N, Kw) uint32; ``k_true``
    is the logical K before packing.  Returns (M, N) int32.

    backend: 'pallas' | 'jnp' | 'ref' | 'auto'; unknown strings raise
    ``ValueError``.  On the pallas backend ``words_per_step`` packed
    words are contracted per kernel loop step (``None`` auto-sizes to
    8); the output is invariant to it, and invalid values — anything
    that is not a positive divisor of the 128-lane group — raise
    ``ValueError`` like the conv ``block_oh``/``block_n`` knobs do.
    M ≤ 8 with a VMEM-sized K lowers to the N-major GEMV grid
    (:func:`dispatch_batch`).
    """
    backend = _resolve(backend)
    if backend == "pallas":
        ws = _words_per_step(words_per_step)
        _vmem.preflight(_vmem.gemm_estimate(
            a_packed.shape[0], b_packed.shape[0], a_packed.shape[1],
            words_per_step=ws))
        return _bmm.binary_matmul_packed(
            a_packed, b_packed, k_true=k_true, words_per_step=ws,
            interpret=not _on_tpu())
    return B.packed_matmul(a_packed, b_packed, k_true)


def binary_matmul_bn_sign_packed(a_packed: jax.Array, b_packed: jax.Array,
                                 tau: jax.Array, flip: jax.Array, *,
                                 k_true: int, backend: str = "auto",
                                 words_per_step: int | None = None
                                 ) -> jax.Array:
    """Fused packed GEMM + BN-sign-fold + re-bitpack (the dense analogue
    of ``binary_conv2d_bn_sign_packed``).

    ``tau``/``flip``: the per-output-channel folded BN threshold from
    ``core.binary_layers.fold_bn_sign``.  Returns (M, ceil(N/32)) uint32
    — the next binary layer's input, without the (M, N) int32 activation
    ever leaving the kernel.  Bit-identical to
    ``bn_sign_pack(binary_matmul_packed(...))``.

    backend: 'pallas' | 'jnp' | 'ref' | 'auto' ('jnp' and 'ref' both run
    the pure oracle); unknown strings raise ``ValueError``.
    ``words_per_step`` as in :func:`binary_matmul_packed` (non-divisors
    of 128 raise ``ValueError``).  M ≤ 8 takes the fused GEMV grid
    (:func:`dispatch_batch`).
    """
    backend = _resolve(backend)
    if backend == "pallas":
        ws = _words_per_step(words_per_step)
        _vmem.preflight(_vmem.gemm_estimate(
            a_packed.shape[0], b_packed.shape[0], a_packed.shape[1],
            words_per_step=ws, fused=True))
        return _bmm.binary_matmul_bn_sign_packed(
            a_packed, b_packed, tau, flip, k_true=k_true,
            words_per_step=ws, interpret=not _on_tpu())
    return _ref.binary_matmul_bn_sign_packed_ref(a_packed, b_packed, tau,
                                                 flip, k_true)


def binary_dense_stack_packed(stages: list, x_packed: jax.Array, *,
                              backend: str = "auto",
                              resident: bool | None = None,
                              block_m: int | None = None,
                              words_per_step: int | None = None,
                              vmem_budget_bytes: int | None = None
                              ) -> jax.Array:
    """A chain of hidden dense layers, each GEMM + BN-sign + re-bitpack.

    ``stages``: list of ``{"w_packed", "k_true", "tau", "flip"}``;
    ``x_packed``: (M, Kw₀) packed activation.  Returns the packed uint32
    activation after the last stage — bit-identical to chaining
    :func:`binary_matmul_bn_sign_packed`.  An empty ``stages`` list is
    the identity on every backend.

    backend: 'pallas' | 'jnp' | 'ref' | 'auto'; unknown strings raise
    ``ValueError``.  pallas backend: when the whole stack's weights +
    folded thresholds fit the VMEM budget
    (``binary_matmul.dense_stack_fits_vmem``; override the default
    8 MiB with ``vmem_budget_bytes``), the stack runs as ONE kernel
    launch with an in-kernel stage loop over the resident weights;
    otherwise it falls back to one fused launch per layer.  ``resident``
    overrides the auto decision (True forces the single launch, False
    forces per-layer).  ``block_m`` tiles the M axis (must be a positive
    multiple of 8 — the TPU sublane granularity — else ``ValueError``);
    ``words_per_step`` as in :func:`binary_matmul_packed` (non-divisors
    of 128 raise ``ValueError``).
    """
    backend = _resolve(backend)
    if not stages:                  # empty stack: identity on every backend
        return x_packed
    if backend != "pallas":
        return _ref.binary_dense_stack_packed_ref(stages, x_packed)
    weights = [s["w_packed"] for s in stages]
    bm = _bmm.STACK_BLOCK_M if block_m is None else block_m
    _fe.check_block_sublanes("block_m", bm)
    ws = _words_per_step(words_per_step)
    if resident is None:
        resident = _bmm.dense_stack_fits_vmem(
            weights, budget=vmem_budget_bytes, block_m=bm,
            words_per_step=ws)
    if resident:
        _vmem.preflight(_vmem.dense_stack_estimate(
            [tuple(w.shape) for w in weights], block_m=bm,
            words_per_step=ws))
        return _bmm.binary_dense_stack_packed(
            x_packed, weights,
            [s["tau"] for s in stages], [s["flip"] for s in stages],
            k_trues=tuple(int(s["k_true"]) for s in stages),
            block_m=bm, words_per_step=ws, interpret=not _on_tpu())
    h = x_packed
    for s in stages:
        _vmem.preflight(_vmem.gemm_estimate(
            h.shape[0], s["w_packed"].shape[0], s["w_packed"].shape[1],
            words_per_step=ws, fused=True))
        h = _bmm.binary_matmul_bn_sign_packed(
            h, s["w_packed"], s["tau"], s["flip"], k_true=s["k_true"],
            words_per_step=ws, interpret=not _on_tpu())
    return h


def binary_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     causal: bool = True, window: int | None = None,
                     attn_softcap: float | None = None, q_offset: int = 0,
                     backend: str = "auto", block_q: int | None = None,
                     block_kv: int | None = None,
                     words_per_step: int | None = None) -> jax.Array:
    """Flash-style blocked binary attention (``kernels/binary_attention``).

    ``q``: (B, Sq, Hq, D), ``k``: (B, Skv, Hkv, D), ``v``:
    (B, Skv, Hkv, Dv) — all real-valued.  Q and K are sign-binarized and
    packed along head_dim through the :func:`bitpack` dispatcher; every
    QKᵀ score is then the XNOR-popcount identity
    (D − 2·popcount) · D^(−1/2), softmaxed online over KV tiles (the
    (Sq, Skv) score matrix never hits HBM on the pallas backend), and
    averaged against the float V.  ``Hq % Hkv == 0`` groups query heads
    over KV heads (GQA/MQA).  ``causal`` masks qpos < kpos (``q_offset``
    aligns decode queries), ``window`` masks qpos − kpos ≥ window
    (sliding-window local layers), ``attn_softcap`` applies the logit
    tanh cap before masking.  Returns (B, Sq, Hq, Dv) float32.

    backend: 'pallas' | 'jnp' | 'ref' | 'auto' ('jnp'/'ref' both run
    ``ref.binary_attention_ref``, the exact-softmax oracle); unknown
    strings raise ``ValueError``.  Block knobs (pallas only) validate by
    raising, like ``block_oh``/``block_n``/``words_per_step`` everywhere
    else: ``block_q`` must be a positive multiple of 8 (sublanes),
    ``block_kv`` a positive multiple of 128 (lanes), ``words_per_step``
    a positive divisor of 128.  The output is invariant to all three
    (property-tested).  ``window`` must be a positive int on every
    backend.
    """
    if window is not None and window < 1:
        raise ValueError(f"window must be a positive int, got {window!r}")
    backend = _resolve(backend)
    if backend != "pallas":
        return _ref.binary_attention_ref(
            q, k, v, causal=causal, window=window,
            attn_softcap=attn_softcap, q_offset=q_offset)
    d = q.shape[-1]
    _vmem.preflight(_vmem.attention_estimate(
        q.shape[0], q.shape[2], q.shape[1], k.shape[1],
        B.packed_width(d), v.shape[-1],
        block_q=_batt.DEFAULT_BLOCK_Q if block_q is None else block_q,
        block_kv=_batt.DEFAULT_BLOCK_KV if block_kv is None else block_kv))
    q_p = bitpack(q, backend=backend)
    k_p = bitpack(k, backend=backend)
    return _batt.binary_attention_packed(
        q_p, k_p, v, d_true=d, causal=causal, window=window,
        attn_softcap=attn_softcap, q_offset=q_offset, block_q=block_q,
        block_kv=block_kv, words_per_step=_words_per_step(words_per_step),
        interpret=not _on_tpu())


def bitpack(x: jax.Array, *, backend: str = "auto") -> jax.Array:
    """Sign-binarize + pack along the last axis -> uint32 words.

    ``x``: (..., K) real-valued; values ≥ 0 encode to bit 1, < 0 to
    bit 0, LSB-first, 32 per word.  Returns (..., ceil(K/32)) uint32
    with zero-bit tails (exact under the XOR-popcount identity, see
    ``docs/kernels.md``).

    backend: 'pallas' | 'jnp' | 'ref' | 'auto' ('jnp'/'ref' both run
    ``binarize.pack_bits``); unknown strings raise ``ValueError``.
    """
    backend = _resolve(backend)
    if backend == "pallas":
        orig_shape = x.shape
        x2 = x.reshape(-1, orig_shape[-1])
        _vmem.preflight(_vmem.bitpack_estimate(x2.shape[0], x2.shape[1]))
        out = _bp.bitpack(x2, interpret=not _on_tpu())
        return out.reshape(*orig_shape[:-1], out.shape[-1])
    return B.pack_bits(x)


# ---------------------------------------------------------------------------
# Binary 2-D convolution (kernels/binary_conv.py) + fused epilogue
# ---------------------------------------------------------------------------

def _conv_preflight(plan: dict, x: jax.Array, *, block_oh: int | None,
                    block_n: int | None, fused: bool = False,
                    nbits: int = 1) -> None:
    """Shared VMEM preflight for the three conv dispatchers: resolve the
    block knobs exactly like the wrapper will, then budget-check the
    launch (spatial axes are the last three of ``x`` for both the
    (B, H, W, Cw) image and the (nbits, B, H, W, Cw) plane stack)."""
    bn = _bconv.resolve_block_n(block_n, plan["c_out"])
    oh, ow = plan["out_hw"]
    boh = _bconv.resolve_block_oh(block_oh, oh, ow)
    (pt, pb), (pl, pr) = plan["pads"]
    h, w, cw = x.shape[-3], x.shape[-2], x.shape[-1]
    batch = x.shape[0] if nbits == 1 else x.shape[1]
    _vmem.preflight(_vmem.conv_estimate(
        batch, (h + pt + pb, w + pl + pr), cw, plan["kh"], plan["kw"],
        plan["c_out"], plan["out_hw"], block_n=bn, block_oh=boh,
        fused=fused, nbits=nbits))


def binary_conv2d_packed(plan: dict, x_packed: jax.Array, *,
                         backend: str = "auto",
                         block_oh: int | None = None,
                         block_n: int | None = None) -> jax.Array:
    """Packed binary conv on a ``make_conv_plan`` plan.  Returns int32

    (B, OH, OW, C_out) — exact integer conv of the ±1 tensors with true
    zero padding (pad-as-(−1) + correction, paper C5).

    backend: 'pallas' (in-kernel im2col, no patch matrix in HBM) |
    'jnp'/'ref' (im2col outside, the pre-subsystem path) | 'auto';
    unknown strings raise ``ValueError``.  ``block_oh``/``block_n`` tile
    the Pallas grid over (OH rows, C_out); ``None`` auto-sizes.
    ``block_oh`` must be a positive multiple of 8 (sublane granularity)
    and ``block_n`` a positive multiple of 128 (lane granularity) —
    invalid values raise ``ValueError`` instead of being silently
    clamped up.  The output is invariant to both (property-tested).
    """
    backend = _resolve(backend)
    if backend == "pallas":
        _conv_preflight(plan, x_packed, block_oh=block_oh, block_n=block_n)
        return _bconv.binary_conv2d_packed(
            x_packed, plan["w_packed"], plan["correction"],
            kh=plan["kh"], kw=plan["kw"], stride=plan["stride"],
            pads=plan["pads"], out_hw=plan["out_hw"], c_out=plan["c_out"],
            k_true=plan["k_true"], block_oh=block_oh, block_n=block_n,
            interpret=not _on_tpu())
    return _ref.binary_conv2d_packed_ref(
        x_packed, plan["w_packed"], plan["correction"], kh=plan["kh"],
        kw=plan["kw"], stride=plan["stride"], pads=plan["pads"],
        c_out=plan["c_out"], k_true=plan["k_true"])


def binary_conv2d_bn_sign_packed(plan: dict, folded: dict,
                                 x_packed: jax.Array, *,
                                 backend: str = "auto",
                                 block_oh: int | None = None,
                                 block_n: int | None = None) -> jax.Array:
    """Fused conv + BN-sign-fold + re-bitpack.  Returns packed uint32

    (B, OH, OW, ceil(C_out/32)) — the next binary conv layer's input,
    without the int32 activation ever leaving the kernel un-packed.
    ``folded``: {"tau", "flip"} from ``core.binary_layers.fold_bn_sign``.

    backend and block knobs exactly as in :func:`binary_conv2d_packed`
    (unknown backends and off-granularity blocks raise ``ValueError``);
    the 128-lane ``block_n`` check also lands every output block on a
    32-bit pack seam.
    """
    backend = _resolve(backend)
    if backend == "pallas":
        _conv_preflight(plan, x_packed, block_oh=block_oh, block_n=block_n,
                        fused=True)
        return _bconv.binary_conv2d_bn_sign_packed(
            x_packed, plan["w_packed"], plan["correction"], folded["tau"],
            folded["flip"], kh=plan["kh"], kw=plan["kw"],
            stride=plan["stride"], pads=plan["pads"], out_hw=plan["out_hw"],
            c_out=plan["c_out"], k_true=plan["k_true"], block_oh=block_oh,
            block_n=block_n, interpret=not _on_tpu())
    return _ref.binary_conv2d_bn_sign_packed_ref(
        x_packed, plan["w_packed"], plan["correction"], folded["tau"],
        folded["flip"], kh=plan["kh"], kw=plan["kw"], stride=plan["stride"],
        pads=plan["pads"], c_out=plan["c_out"], k_true=plan["k_true"])


def bitplane_conv2d_packed(plan: dict, x_uint8: jax.Array, *,
                           backend: str = "auto",
                           block_oh: int | None = None,
                           block_n: int | None = None) -> jax.Array:
    """First-layer fixed-precision conv (paper C4) on a

    ``make_bitplane_conv_plan`` plan.  ``x_uint8``: (B, H, W, C_in) raw
    integer input.  Returns (B, OH, OW, C_out) int32 == the exact integer
    conv of the raw input against sign(W) with true zero padding.

    backend: 'pallas' — plane extraction/packing is pure jnp bit ops
    (``pack_bitplanes_uint8``) and the conv is ONE kernel launch (an
    in-kernel plane loop over the VMEM-resident plane stack with the 2^i
    weighting and rowsum pad correction folded into the epilogue);
    'jnp'/'ref' — the pre-fusion sequential 8-plane oracle; 'auto' as
    everywhere.  Unknown backends raise ``ValueError``; ``block_oh`` /
    ``block_n`` validate exactly as in :func:`binary_conv2d_packed`
    (``ValueError`` off sublane/lane granularity).
    """
    backend = _resolve(backend)
    nbits = plan["nbits"]
    if backend == "pallas":
        x_planes = B.pack_bitplanes_uint8(x_uint8, nbits)
        _conv_preflight(plan, x_planes, block_oh=block_oh, block_n=block_n,
                        nbits=nbits)
        return _bconv.bitplane_conv2d_packed(
            x_planes, plan["w_packed"], plan["rowsum"], kh=plan["kh"],
            kw=plan["kw"], stride=plan["stride"], pads=plan["pads"],
            out_hw=plan["out_hw"], c_out=plan["c_out"],
            k_true=plan["k_true"], nbits=nbits, block_oh=block_oh,
            block_n=block_n, interpret=not _on_tpu())
    return _ref.bitplane_conv2d_packed_ref(
        x_uint8, plan["w_packed"], plan["rowsum"], kh=plan["kh"],
        kw=plan["kw"], stride=plan["stride"], pads=plan["pads"],
        c_out=plan["c_out"], k_true=plan["k_true"], nbits=nbits)


def bitplane_dense_packed(packed: dict, x_uint8: jax.Array, *,
                          backend: str = "auto") -> jax.Array:
    """First-layer fixed-precision dense (paper C4) on a

    ``core.binary_layers.pack_bitplane_dense`` layer.  ``x_uint8``:
    (M, K) raw integer input.  Returns (M, N) int32 == x.int32 @
    sign(W)^T.

    backend: 'pallas' — plane extraction/packing is pure jnp bit ops
    (``pack_bitplanes_uint8``) and the layer is ONE kernel launch
    (every (image, plane) pair a contraction row, the 2^i weighting and
    rowsum correction in the epilogue; ``binary_matmul.
    bitplane_dense_packed``); 'jnp'/'ref' — the sequential per-plane
    oracle; 'auto' as everywhere.  Unknown backends raise
    ``ValueError``.  Each pallas dispatch bumps
    ``ops.dispatch.bitplane_dense`` on the process-wide telemetry
    registry, beside ``ops.dispatch.gemv``/``gemm``.
    """
    backend = _resolve(backend)
    nbits = packed["nbits"]
    if backend == "pallas":
        x_planes = B.pack_bitplanes_uint8(x_uint8, nbits)
        kw, n = packed["w_words"].shape
        _vmem.preflight(_vmem.bitplane_dense_estimate(
            x_uint8.shape[0], n, kw, nbits=nbits))
        telemetry.default().metrics.counter(
            "ops.dispatch.bitplane_dense").inc()
        return _bmm.bitplane_dense_packed(
            x_planes, packed["w_words"], packed["w_rowsum"],
            k_true=packed["k_true"], nbits=nbits, interpret=not _on_tpu())
    return _ref.bitplane_dense_packed_ref(
        x_uint8, packed["w_words"], packed["w_rowsum"],
        k_true=packed["k_true"], nbits=nbits)


def bn_sign_pack(x: jax.Array, tau: jax.Array, flip: jax.Array, *,
                 backend: str = "auto") -> jax.Array:
    """Fused sign(BN(x)) + bit-pack along the last axis.

    ``x``: (..., C) int32 (or any real) raw layer output; ``tau``/``flip``
    the folded BN threshold (``fold_bn_sign``).  Returns
    (..., ceil(C/32)) uint32 — bit-identical to
    ``pack_bits(apply_bn_sign_folded({tau, flip}, x))``.

    backend: 'pallas' | 'jnp' | 'ref' | 'auto' ('jnp'/'ref' both run the
    oracle); unknown strings raise ``ValueError``.
    """
    backend = _resolve(backend)
    lead = x.shape[:-1]
    if backend == "pallas":
        x2 = x.reshape(-1, x.shape[-1])
        _vmem.preflight(_vmem.bn_sign_pack_estimate(x2.shape[0],
                                                    x2.shape[1]))
        out = _fe.bn_sign_pack(x2, tau, flip, interpret=not _on_tpu())
        return out.reshape(*lead, out.shape[-1])
    return _ref.bn_sign_pack_ref(x, tau, flip)


def binary_conv2d_residual(plan: dict, epi: jax.Array, x_packed: jax.Array,
                           shortcut: jax.Array, *, backend: str = "auto"
                           ) -> tuple[jax.Array, jax.Array]:
    """Residual binary conv (ReActNet) on a ``make_residual_conv_plan``
    plan: the packed conv of ``x_packed`` (B, H, W, Cw), its pad
    correction, and ``fused_epilogue.residual_epilogue`` against the
    float ``shortcut`` (B, H, W, C_out), 2x2-averaged at stride 2, with
    the (8, C_out) rows ``epi``.  Returns the float32 stream
    (B, OH, OW, C_out) and its packed sign plus the next bias, uint32.

    backend: 'pallas' (``binary_conv._conv_residual_kernel``, one launch)
    | 'jnp'/'ref' (the oracle) | 'auto'; unknown strings raise
    ``ValueError``.  Route and blocks come from the shapes: the ±1
    operands unpacked in VMEM and contracted on the MXU, or XOR-popcount
    on the VPU below a 128-lane C_in or C_out
    (``analysis.vmem.residual_conv_route``).  Each pallas dispatch bumps
    ``ops.dispatch.residual_conv.<route>`` on the process-wide telemetry
    registry.
    """
    backend = _resolve(backend)
    statics = dict(kh=plan["kh"], kw=plan["kw"], stride=plan["stride"],
                   pads=plan["pads"], c_out=plan["c_out"],
                   k_true=plan["k_true"])
    if backend == "pallas":
        _vmem.preflight(_vmem.residual_conv_estimate(
            x_packed.shape[0], plan["in_hw"], plan["cw"], plan["kh"],
            plan["kw"], plan["c_out"], plan["out_hw"],
            stride=plan["stride"]))
        route = _vmem.residual_conv_route(plan["cw"], plan["c_out"])
        telemetry.default().metrics.counter(
            f"ops.dispatch.residual_conv.{route}").inc()
        return _bconv.binary_conv2d_residual_packed(
            x_packed, plan["w_taps"], plan["correction"], epi, shortcut,
            out_hw=plan["out_hw"], interpret=not _on_tpu(), **statics)
    return _ref.binary_conv2d_residual_ref(
        x_packed, plan["w_taps"], plan["correction"], epi, shortcut,
        **statics)


def pointwise_residual(w_words: jax.Array, epi: jax.Array,
                       x_packed: jax.Array, shortcut: jax.Array, *,
                       k_true: int, backend: str = "auto"
                       ) -> tuple[jax.Array, jax.Array]:
    """Residual 1x1 binary conv (ReActNet) over pixel rows:
    ``x_packed`` (M, Kw) against word-major ``w_words`` (Kw, N), then
    ``fused_epilogue.residual_epilogue`` with the (8, N) rows ``epi``
    against the float ``shortcut`` (M, C), added to each C-channel group
    of the N outputs (N = 2C where two convs share the input).  Returns
    the float32 stream (M, N) and its packed sign plus the next bias,
    uint32.

    backend: 'pallas' (``binary_matmul._pointwise_residual_kernel``, one
    launch) | 'jnp'/'ref' (the oracle) | 'auto'; unknown strings raise
    ``ValueError``.  Blocks come from the shapes.
    """
    backend = _resolve(backend)
    if backend == "pallas":
        _vmem.preflight(_vmem.pointwise_residual_estimate(
            x_packed.shape[0], x_packed.shape[1], w_words.shape[1],
            shortcut.shape[1]))
        return _bmm.pointwise_residual_packed(
            x_packed, w_words, epi, shortcut, k_true=k_true,
            interpret=not _on_tpu())
    return _ref.pointwise_residual_ref(x_packed, w_words, epi, shortcut,
                                       k_true=k_true)


def binary_conv2d(x: jax.Array, w: jax.Array, *, stride: int = 1,
                  padding: str = "SAME", backend: str = "auto",
                  block_oh: int | None = None,
                  block_n: int | None = None) -> jax.Array:
    """End-to-end binary conv on real-valued operands (mirrors

    ``binary_matmul``): sign-binarizes + channel-packs ``x``, packs ``w``
    per tap, and runs the XNOR-popcount conv.

    ``x``: (B, H, W, C_in) real, ``w``: (C_out, KH, KW, C_in) real.
    Returns (B, OH, OW, C_out) int32 == the integer dots of
    ``conv(sign(x), sign(w))`` with true zero padding.

    backend as everywhere (unknown strings raise ``ValueError``);
    ``block_oh``/``block_n`` forward to :func:`binary_conv2d_packed`
    with the same validation (``ValueError`` off sublane/lane
    granularity).
    """
    plan = _bconv.make_conv_plan(w, input_hw=x.shape[1:3], stride=stride,
                                 padding=padding)
    x2 = x.reshape(-1, x.shape[-1])
    x_p = bitpack(x2, backend=backend).reshape(*x.shape[:-1], -1)
    return binary_conv2d_packed(plan, x_p, backend=backend,
                                block_oh=block_oh, block_n=block_n)
