"""The paper's evaluation networks (§6.2, §6.3) as JAX models.

* ``bmlp``  — BinaryNet MLP for MNIST (Courbariaux et al. 2016 §2.1):
              784 -> 3 x [4096 dense, BN, sign] -> 10 dense, BN.
* ``bcnn``  — BinaryNet VGG-like CNN for CIFAR-10 (Hubara et al. 2016
              §2.3): 2x128C3-MP2-2x256C3-MP2-2x512C3-MP2-2x1024FC-10FC,
              BN + sign after every conv/dense.

Each network has:
  init(key, spec)        -> trainable params (latent fp weights + BN)
  forward_float(...)     -> the float-sign reference forward
  pack(params, spec)     -> one-time packed inference params (paper C2)
  forward_packed(...)    -> the optimized packed forward

forward_packed == forward_float exactly on the integer dots, and to fp
round-off on the final BN logits (tests/test_paper_equivalence.py).

ReActNet-A lives in ``models/reactnet.py``; its spec is re-exported here
and the serving seams below cover it, so every image network the server
takes is reached through this module.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro import telemetry
from repro.core import binarize as B
from repro.core import binary_layers as L
from repro.kernels import ops as kops
from repro.models import reactnet as R
from repro.models.reactnet import ReActNetSpec  # noqa: F401  (spec by name)


# ---------------------------------------------------------------------------
# Binary MLP (paper §6.2)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class BMLPSpec:
    sizes: tuple[int, ...] = (784, 4096, 4096, 4096, 10)
    nbits_input: int = 8          # MNIST pixels are 8-bit (paper §4.3)


def init_bmlp(key: jax.Array, spec: BMLPSpec) -> dict:
    layers, bns = [], []
    for i, (d_in, d_out) in enumerate(zip(spec.sizes[:-1], spec.sizes[1:])):
        key, sub = jax.random.split(key)
        layers.append(L.init_binary_dense(sub, d_in, d_out))
        bns.append(L.init_batchnorm(d_out))
    return {"layers": layers, "bns": bns}


def bmlp_forward_float(params: dict, x_uint8: jax.Array, *,
                       ste: bool = False) -> jax.Array:
    """Reference forward.  x_uint8: (B, 784) fixed-precision input."""
    n = len(params["layers"])
    h = None
    for i in range(n):
        if i == 0:
            z = L.apply_bitplane_dense_float(params["layers"][i], x_uint8)
        else:
            z = L.apply_binary_dense_float(params["layers"][i], h, ste=ste)
        z = L.apply_batchnorm(params["bns"][i], z)
        if i < n - 1:
            h = B.binarize_ste(z) if ste else B.sign_pm1(z)
    return z                       # logits (no sign on the output layer)


def pack_bmlp(params: dict, spec: BMLPSpec) -> dict:
    n = len(params["layers"])
    packed_layers = []
    for i in range(n):
        if i == 0:
            packed_layers.append(
                L.pack_bitplane_dense(params["layers"][i],
                                      nbits=spec.nbits_input))
        else:
            packed_layers.append(L.pack_binary_dense(params["layers"][i]))
    folded = [L.fold_bn_sign(bn) for bn in params["bns"][:-1]]
    return {"layers": packed_layers, "folded": folded,
            "bn_out": L.fold_bn_affine(params["bns"][-1])}


def _gather_packed(hp: jax.Array, axis_name: str) -> jax.Array:
    """Reassemble a C_out-sharded PACKED activation along its word axis.

    Inside the sharded forward each model shard packs its own span of
    32-bit words (``bn_sign_pack`` on its local channels), so a tiled
    all-gather along the trailing word axis reconstructs the exact
    single-device word layout — this is the ONLY cross-device traffic in
    the packed forward, and it moves 1-bit words, never the int32
    pre-threshold activation.

    Every gather site bumps ``sharding.gathers`` on the process-wide
    telemetry registry at TRACE time — i.e. it counts the all-gather
    eqns a sharded forward lowers to, the same structural fact the
    analysis report's ``sharding/`` cells gate, not per-execution
    traffic (the compiled function re-runs without re-tracing).
    """
    telemetry.default().metrics.counter("sharding.gathers").inc()
    return jax.lax.all_gather(hp, axis_name, axis=hp.ndim - 1, tiled=True)


def _check_dense_stack(dense_stack: str) -> None:
    if dense_stack not in ("auto", "resident", "per_layer"):
        raise ValueError(f"unknown dense_stack mode {dense_stack!r}")


def _dense_hidden_stack(layers: list, foldeds: list, hp: jax.Array, *,
                        backend: str, model_axis: str | None,
                        shards: tuple[int, ...],
                        dense_stack: str) -> jax.Array:
    """The hidden dense stack shared by both networks: every layer is a

    fused GEMM + BN-sign + re-bitpack, packed in / packed out.

    Unsharded stacks route through ``apply_binary_dense_stack_packed``:
    ONE kernel launch when the stack's weights + folded thresholds are
    VMEM-resident (``dense_stack='auto'``; ``'resident'`` forces it,
    ``'per_layer'`` forces the fallback), per-layer fused launches
    otherwise.  C_out-sharded layers always run per-layer — each shard
    computes its own word span (the ``c_out % (32·|model|)`` pack-seam
    rule guarantees word alignment) and the packed bits are
    all-gathered before the next contraction.
    """
    _check_dense_stack(dense_stack)
    if not layers:
        return hp
    if all(s == 1 for s in shards) and dense_stack != "per_layer":
        return L.apply_binary_dense_stack_packed(
            layers, foldeds, hp, backend=backend,
            resident=True if dense_stack == "resident" else None)
    for i, (layer, folded) in enumerate(zip(layers, foldeds)):
        hp = L.apply_binary_dense_bn_packed(layer, folded, hp,
                                            backend=backend)
        if shards[i] > 1:
            hp = _gather_packed(hp, model_axis)
    return hp


def bmlp_forward_packed(packed: dict, x_uint8: jax.Array, *,
                        backend: str = "auto", model_axis: str | None = None,
                        layer_shards: tuple[int, ...] | None = None,
                        dense_stack: str = "auto") -> jax.Array:
    """Optimized forward: bit-plane first layer (C4), packed GEMMs (C1),

    folded BN+sign thresholds between layers (no fp math until the output
    BN).  Hidden layers run as fused GEMM + BN-sign + re-bitpack kernels
    — and, when the stack is VMEM-resident, as ONE kernel launch for the
    whole hidden stack (``dense_stack``: 'auto' | 'resident' |
    'per_layer').

    When called per-shard inside ``shard_map`` (see
    ``distributed.sharding.make_sharded_forward``), ``layer_shards[i]``
    says how many ways layer ``i``'s d_out is split over ``model_axis``;
    a sharded layer computes its local output columns and the packed
    bits are all-gathered (word-aligned) before the next GEMM.  The
    final layer is always replicated (its output feeds the fp BN).
    """
    n = len(packed["layers"])
    shards = layer_shards or (1,) * n
    assert shards[-1] == 1, "output layer must stay replicated"
    z = L.apply_bitplane_dense_packed(packed["layers"][0], x_uint8,
                                      backend=backend)
    # Layer 0 accumulates over bit planes in int32, so its epilogue
    # runs standalone; every later hidden layer fuses GEMM + epilogue.
    hp = L.apply_bn_sign_folded_packed(packed["folded"][0], z,
                                       backend=backend)
    if shards[0] > 1:
        hp = _gather_packed(hp, model_axis)
    hp = _dense_hidden_stack(
        packed["layers"][1:n - 1], packed["folded"][1:], hp,
        backend=backend, model_axis=model_axis, shards=shards[1:n - 1],
        dense_stack=dense_stack)
    z = L.apply_binary_dense_prepacked(packed["layers"][n - 1], hp,
                                       backend=backend)
    return L.apply_bn_affine(packed["bn_out"], z)


# ---------------------------------------------------------------------------
# Binary CNN (paper §6.3)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class ConvStage:
    c_out: int
    pool: bool = False


@dataclass(frozen=True)
class BCNNSpec:
    input_hw: tuple[int, int] = (32, 32)
    c_in: int = 3
    stages: tuple[ConvStage, ...] = (
        ConvStage(128), ConvStage(128, pool=True),
        ConvStage(256), ConvStage(256, pool=True),
        ConvStage(512), ConvStage(512, pool=True),
    )
    dense: tuple[int, ...] = (1024, 1024, 10)
    ksize: int = 3
    nbits_input: int = 8


def _stage_hw(spec: BCNNSpec):
    """Spatial size entering each conv stage (SAME convs, pool /2)."""
    h, w = spec.input_hw
    out = []
    for st in spec.stages:
        out.append((h, w))
        if st.pool:
            h, w = h // 2, w // 2
    return out, (h, w)


def init_bcnn(key: jax.Array, spec: BCNNSpec) -> dict:
    convs, conv_bns = [], []
    c = spec.c_in
    for st in spec.stages:
        key, sub = jax.random.split(key)
        convs.append(L.init_binary_conv2d(sub, spec.ksize, spec.ksize, c,
                                          st.c_out))
        conv_bns.append(L.init_batchnorm(st.c_out))
        c = st.c_out
    _, (fh, fw) = _stage_hw(spec)
    d_in = fh * fw * c
    denses, dense_bns = [], []
    for d_out in spec.dense:
        key, sub = jax.random.split(key)
        denses.append(L.init_binary_dense(sub, d_in, d_out))
        dense_bns.append(L.init_batchnorm(d_out))
        d_in = d_out
    return {"convs": convs, "conv_bns": conv_bns,
            "denses": denses, "dense_bns": dense_bns}


def bcnn_forward_float(params: dict, x_uint8: jax.Array, spec: BCNNSpec,
                       *, ste: bool = False) -> jax.Array:
    """Reference forward.  x_uint8: (B, H, W, C) fixed-precision input.

    First conv consumes the raw integer input (no sign) — the binary
    technique handles it via bit-planes in the packed path (paper C4)."""
    binarize = B.binarize_ste if ste else B.sign_pm1
    h = x_uint8.astype(jnp.float32)
    for i, st in enumerate(spec.stages):
        w = binarize(params["convs"][i]["w"])
        z = jax.lax.conv_general_dilated(
            h if i == 0 else binarize(h),
            jnp.transpose(w, (1, 2, 3, 0)), (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if st.pool:
            z = L.maxpool2d(z)
        z = L.apply_batchnorm(params["conv_bns"][i], z)
        h = z
    h = binarize(h).reshape(h.shape[0], -1)
    n = len(params["denses"])
    for i in range(n):
        z = L.apply_binary_dense_float(params["denses"][i], h, ste=ste)
        z = L.apply_batchnorm(params["dense_bns"][i], z)
        if i < n - 1:
            h = binarize(z)
    return z


def pack_bcnn(params: dict, spec: BCNNSpec) -> dict:
    hws, _ = _stage_hw(spec)
    packed_convs = []
    for i, st in enumerate(spec.stages):
        if i == 0:
            # First layer runs via bit-planes (C4): the plan's rowsum
            # absorbs both the {0,1}->±1 shift and the pad correction
            # (pads are plane-value 0 == p̂ = -1), and the packed forward
            # runs all planes in ONE fused kernel launch.
            pc = L.pack_bitplane_conv2d(params["convs"][i],
                                        input_hw=hws[i], stride=1,
                                        padding="SAME",
                                        nbits=spec.nbits_input)
        else:
            pc = L.pack_binary_conv2d(params["convs"][i], input_hw=hws[i],
                                      stride=1, padding="SAME")
        packed_convs.append(pc)
    folded_conv = [L.fold_bn_sign(bn) for bn in params["conv_bns"]]
    # Bit-domain pooling masks (flip > 0 per channel) for pooled stages.
    pool_masks = [L.pool_flip_mask(folded_conv[i]) if st.pool else None
                  for i, st in enumerate(spec.stages)]
    # The first dense layer consumes the flattened *packed* conv activation
    # (fh, fw, Cw) — pack its weights per pixel group so the zero-bit
    # channel tails line up (see pack_binary_dense_grouped).
    c_last = spec.stages[-1].c_out
    packed_dense = [L.pack_binary_dense_grouped(params["denses"][0], c_last)]
    packed_dense += [L.pack_binary_dense(p) for p in params["denses"][1:]]
    folded_dense = [L.fold_bn_sign(bn) for bn in params["dense_bns"][:-1]]
    return {"convs": packed_convs, "folded_conv": folded_conv,
            "pool_masks": pool_masks,
            "denses": packed_dense, "folded_dense": folded_dense,
            "bn_out": L.fold_bn_affine(params["dense_bns"][-1]),
            "spec": spec}


def _bitplane_conv_packed(pc: dict, x_uint8: jax.Array, nbits: int, *,
                          backend: str = "auto") -> jax.Array:
    """Stage-0 conv on raw uint8 input: ONE kernel launch on the pallas

    backend (in-kernel plane loop, 2^i weighting + rowsum correction in
    the epilogue) — previously 8 sequential per-plane conv launches.
    ``nbits`` must match the plan (kept as an argument for the call sites
    / launch-count test)."""
    assert nbits == pc["nbits"], (nbits, pc["nbits"])
    return kops.bitplane_conv2d_packed(pc, x_uint8, backend=backend)


def bcnn_forward_packed(packed: dict, x_uint8: jax.Array, *,
                        backend: str = "auto", model_axis: str | None = None,
                        conv_shards: tuple[int, ...] | None = None,
                        dense_shards: tuple[int, ...] | None = None,
                        dense_stack: str = "auto") -> jax.Array:
    """Optimized forward: after the bit-plane first stage, every

    inter-layer activation stays bit-packed in HBM end-to-end — fused
    conv + BN-sign + re-bitpack kernels between conv stages, bit-domain
    max-pooling (OR/AND under the flip mask), and fused
    GEMM + BN-sign + re-bitpack kernels through the hidden dense tail
    (one launch for the whole tail when it is VMEM-resident;
    ``dense_stack``: 'auto' | 'resident' | 'per_layer').  Thresholding
    before pooling is exact because the folded BN-sign compare is
    monotone per channel.

    Sharded execution (per-shard body under ``shard_map``, built by
    ``distributed.sharding.make_sharded_forward``): ``conv_shards[i]`` /
    ``dense_shards[i]`` give the C_out-parallel split of each stage over
    ``model_axis``.  A sharded stage owns its own packed weight rows,
    folded BN thresholds, correction columns, and pool-mask words — the
    conv + BN-sign + repack (+ bit-domain pool) epilogue is fully local
    — and ends with a word-aligned all-gather of the PACKED activation
    so the next stage (which contracts over all input channels) sees the
    full image.  The conv→dense flatten needs no special casing: the
    last conv stage's gather restores the exact single-device word
    layout the grouped dense packing was built against.
    """
    spec: BCNNSpec = packed["spec"]
    n_conv = len(packed["convs"])
    conv_shards = conv_shards or (1,) * n_conv
    dense_shards = dense_shards or (1,) * len(packed["denses"])
    assert dense_shards[-1] == 1, "output layer must stay replicated"
    # Stage 0 accumulates 8 bit-plane convs in int32, so its epilogue runs
    # standalone: pool on int32, then fused threshold + re-bitpack.
    z = _bitplane_conv_packed(
        L.localize_conv_plan(packed["convs"][0], conv_shards[0]),
        x_uint8, spec.nbits_input, backend=backend)
    if spec.stages[0].pool:
        z = L.maxpool2d(z)
    hp = L.apply_bn_sign_folded_packed(packed["folded_conv"][0], z,
                                       backend=backend)
    if conv_shards[0] > 1:
        hp = _gather_packed(hp, model_axis)
    # Stages 1..n-1: packed in, packed out — zero un-packed activations.
    for i in range(1, n_conv):
        hp = L.apply_binary_conv2d_bn_packed(
            L.localize_conv_plan(packed["convs"][i], conv_shards[i]),
            packed["folded_conv"][i], hp, backend=backend)
        if spec.stages[i].pool:
            hp = L.maxpool2d_packed(hp, packed["pool_masks"][i])
        if conv_shards[i] > 1:
            hp = _gather_packed(hp, model_axis)
    h = hp.reshape(hp.shape[0], -1)         # packed (B, fh*fw*Cw) words
    # Classifier tail: hidden dense layers are fused GEMM + BN-sign +
    # re-bitpack (single-launch when VMEM-resident), the output layer
    # stays int32 for the fp batch-norm.
    n = len(packed["denses"])
    h = _dense_hidden_stack(
        packed["denses"][:n - 1], packed["folded_dense"], h,
        backend=backend, model_axis=model_axis,
        shards=dense_shards[:n - 1], dense_stack=dense_stack)
    z = L.apply_binary_dense_prepacked(packed["denses"][n - 1], h,
                                       backend=backend)
    return L.apply_bn_affine(packed["bn_out"], z)


# ---------------------------------------------------------------------------
# Serving seams (train/serve.py): one uniform view over every network
# ---------------------------------------------------------------------------

def packed_kind(packed: dict) -> str:
    """'reactnet' | 'bcnn' | 'transformer' | 'bmlp' from the shape of a
    ``pack_*`` tree.

    The serving layer and the sharding rules both dispatch on this, so
    the check lives once, next to the pack functions whose layout it
    reads ('reactnet' trees come from ``models.reactnet.pack_reactnet``
    and carry ``res_blocks``; 'transformer' trees from
    ``models.transformer.pack_transformer`` and carry a ``blocks``
    list).  Raises ``ValueError`` for anything else.
    """
    if "res_blocks" in packed:
        return "reactnet"
    if "convs" in packed:
        return "bcnn"
    if "blocks" in packed:
        return "transformer"
    if "layers" in packed:
        return "bmlp"
    raise ValueError(
        f"not a pack_reactnet/pack_bcnn/pack_bmlp/pack_transformer tree: "
        f"keys {sorted(packed)}")


def packed_input_shape(packed: dict) -> tuple[int, ...]:
    """Per-example input shape (no batch axis) a packed forward consumes.

    bcnn, reactnet: ``(H, W, C_in)`` raw uint8; bmlp: ``(K,)`` raw uint8;
    transformer: ``(S,)`` uint8 token ids (reduced registry configs have
    vocab ≤ 256) — every workload takes fixed-precision input, so the
    serving scratch pool can stage requests without knowing which
    network is behind the queue.
    """
    kind = packed_kind(packed)
    if kind in ("bcnn", "reactnet"):
        spec = packed["spec"]
        return (*spec.input_hw, spec.c_in)
    if kind == "transformer":
        return (int(packed["meta"]["seq_len"]),)
    return (int(packed["layers"][0]["k_true"]),)


def packed_dense_kw_words(packed: dict) -> int:
    """Widest dense packed-K extent of the network, in uint32 words.

    The K side of ``kernels.ops.dispatch_batch``: a batch routes
    through the GEMV serving grid only if every dense layer's packed K
    fits the resident activation block, so the widest layer decides
    the route for the whole forward.
    """
    kind = packed_kind(packed)
    if kind == "reactnet":
        # Its 1x1 convs (word-major).  They contract every pixel row of
        # the bucket in one grid whatever the route says: the route
        # label follows the bucket alone there.
        return max(int(b["w_pw"].shape[0]) for b in packed["res_blocks"])
    if kind == "transformer":
        mats = [blk[w] for blk in packed["blocks"]
                for w in ("wq", "wk", "wv", "wo", "w1", "w2")]
        mats.append(packed["head"])
        return max(int(p["w_packed"].shape[1]) for p in mats)
    layers = packed["denses"] if kind == "bcnn" else packed["layers"]
    # The BMLP's bit-plane first layer (word-major ``w_words``) is one
    # launch at every batch and takes no part in the routing.
    return max(int(p["w_packed"].shape[1]) for p in layers
               if "w_packed" in p)


def demo_model(kind: str, *, smoke: bool = False, seed: int = 0):
    """Reduced evaluation-network preset + random params for demo
    drivers — the serving CLI (``launch/serve.py``) and the static
    analysis report (``analysis/report.py``) both build from this
    one place so their shapes cannot drift.  Returns
    ``(params, spec, kind)``.  ``smoke`` picks CI-sized shapes.
    """
    key = jax.random.PRNGKey(seed)
    if kind == "bcnn":
        spec = BCNNSpec(
            input_hw=(8, 8) if smoke else (16, 16), c_in=3,
            stages=(ConvStage(64), ConvStage(64, pool=True)),
            dense=(128, 10))
        return init_bcnn(key, spec), spec, "bcnn"
    if kind == "bmlp":
        spec = BMLPSpec(sizes=(784, 256, 256, 10) if smoke
                        else (784, 1024, 1024, 10))
        return init_bmlp(key, spec), spec, "bmlp"
    if kind == "reactnet":
        # A same-width block, the stride-1 32 -> 64 doubling block and a
        # stride-2 doubling block.
        spec = ReActNetSpec(input_hw=(16, 16) if smoke else (32, 32),
                            stage_out_channel=(32, 32, 64, 128),
                            num_classes=10)
        return R.init_reactnet(key, spec), spec, "reactnet"
    raise ValueError(
        f"kind must be 'bcnn', 'bmlp' or 'reactnet', got {kind!r}")


def make_packed_forward(packed: dict, *, backend: str = "auto",
                        dense_stack: str = "auto"):
    """Jitted single-device forward ``fwd(x_uint8) -> logits``.

    Works for every packed network — the serving layer's default
    engine, and the same call signature as
    ``distributed.sharding.make_sharded_forward`` so a device mesh can
    sit behind the request queue as a drop-in.  ``backend`` /
    ``dense_stack`` validate as in the underlying forward (unknown
    values raise at first call).
    """
    kind = packed_kind(packed)
    if kind == "reactnet":              # no dense stack to route
        _check_dense_stack(dense_stack)

        def fwd(x):
            return R.reactnet_forward_packed(packed, x, backend=backend)
    elif kind == "bcnn":
        def fwd(x):
            return bcnn_forward_packed(packed, x, backend=backend,
                                       dense_stack=dense_stack)
    elif kind == "transformer":
        from repro.models import transformer as TF

        def fwd(x):
            return TF.transformer_forward_packed(packed, x,
                                                 backend=backend,
                                                 dense_stack=dense_stack)
    else:
        def fwd(x):
            return bmlp_forward_packed(packed, x, backend=backend,
                                       dense_stack=dense_stack)
    return jax.jit(fwd)
