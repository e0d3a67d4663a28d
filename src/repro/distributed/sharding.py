"""Sharding rules + divisibility-aware resolver (DESIGN.md §5).

Logical mesh axes:
* ``pod``   — outer data-parallel axis (multi-pod runs)
* ``data``  — inner data-parallel / FSDP axis
* ``model`` — tensor/expert-parallel axis

Parameter rules are matched on the *path* of each leaf in the param tree
(column-parallel projections shard d_out over 'model', row-parallel shard
d_in, experts shard E, embeddings shard vocab, FSDP shards one remaining
large dim over 'data').  The resolver drops any axis assignment whose
mesh size does not divide the dimension — small models (whisper-base)
degrade gracefully to replication instead of failing to lower.

SSM/RG-LRU internals: Mamba-2's fused in-projection interleaves five
semantic blocks on one axis; sharding it over 'model' misaligns shard and
split boundaries and GSPMD inserts reshuffles.  We shard Mamba-2 params
over 'data' (FSDP) only and keep 'model' for the (elementwise-shardable)
RG-LRU width — see EXPERIMENTS.md §Roofline notes.
"""
from __future__ import annotations

import re
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXES = ("pod", "data")      # batch shards over both when present


def _axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, (tuple, list)):
        s = 1
        for n in name:
            s *= _axis_size(mesh, n)
        return s
    return mesh.shape[name] if name in mesh.shape else 0


def _fit(mesh: Mesh, spec: tuple, shape: tuple[int, ...]) -> P:
    """Drop axis assignments that don't divide the dim (or don't exist)."""
    out = []
    for dim, ax in zip(shape, spec):
        if ax is None:
            out.append(None)
            continue
        size = _axis_size(mesh, ax)
        if size and size > 1 and dim % size == 0:
            out.append(ax)
        elif size == 1:
            out.append(None)
        else:
            # try partial tuples: ('pod','data') -> 'data'
            if isinstance(ax, tuple) and len(ax) > 1:
                for sub in (ax[1:], ax[:1]):
                    ssize = _axis_size(mesh, sub)
                    if ssize and dim % ssize == 0:
                        out.append(sub if len(sub) > 1 else sub[0])
                        break
                else:
                    out.append(None)
            else:
                out.append(None)
    return P(*out)


# --------------------------------------------------------------------------
# parameter rules
# --------------------------------------------------------------------------

# (regex on '/'-joined path, spec builder given leaf ndim)
# dims are written for the UNSTACKED leaf; a leading scan axis (stacked
# layers) gets None prepended automatically.
_PARAM_RULES: list[tuple[str, tuple]] = [
    # attention projections (column-parallel qkv, row-parallel o)
    (r"attn/wq/w$",      ("data", "model")),
    (r"attn/wk/w$",      ("data", "model")),
    (r"attn/wv/w$",      ("data", "model")),
    (r"attn/wo/w$",      ("model", "data")),
    (r"xattn/wq/w$",     ("data", "model")),
    (r"xattn/wk/w$",     ("data", "model")),
    (r"xattn/wv/w$",     ("data", "model")),
    (r"xattn/wo/w$",     ("model", "data")),
    # dense FFN
    (r"mlp/w_up/w$",     ("data", "model")),
    (r"mlp/w_gate/w$",   ("data", "model")),
    (r"mlp/w_down/w$",   ("model", "data")),
    (r"shared/w_up/w$",  ("data", "model")),
    (r"shared/w_gate/w$", ("data", "model")),
    (r"shared/w_down/w$", ("model", "data")),
    # MoE experts: E over model (expert parallelism), FSDP over data
    (r"mlp/router/w$",   (None, None)),
    (r"mlp/we_up/we$",   ("model", "data", None)),      # (E, D, F)
    (r"mlp/we_gate/we$", ("model", "data", None)),
    (r"mlp/we_down/we$", ("model", None, "data")),
    # RG-LRU (width shards over model; elementwise recurrence)
    (r"rec/w_gelu/w$",   ("data", "model")),
    (r"rec/w_rec_in/w$", ("data", "model")),
    (r"rec/wa/w$",       ("data", "model")),
    (r"rec/wx/w$",       ("data", "model")),
    (r"rec/conv_w$",     (None, "model")),
    (r"rec/conv_b$",     ("model",)),
    (r"rec/ba$",         ("model",)),
    (r"rec/bx$",         ("model",)),
    (r"rec/lambda_p$",   ("model",)),
    (r"rec/w_out/w$",    ("model", "data")),
    # Mamba-2, fused form: FSDP only (see module docstring)
    (r"ssm/in_proj/w$",  ("data", None)),
    (r"ssm/out_proj/w$", (None, "data")),
    # Mamba-2, split form (§Perf): d_inner/heads shard over 'model';
    # B/C/dt projections replicate (small)
    (r"ssm/[zx]_proj/w$",   ("data", "model")),
    (r"ssm/(b|c|dt)_proj/w$", ("data", None)),
    (r"ssm/conv_w_x$",   (None, "model")),
    (r"ssm/conv_b_x$",   ("model",)),
    (r"ssm/norm_tp/scale$", ("model",)),
    (r"ssm/out_proj_tp/w$", ("model", "data")),
    (r"ssm/.*",          (None,)),
    # embeddings / head: vocab over model
    (r"embed/table$",    ("model", "data")),
    (r"head/w$",         ("data", "model")),
    (r"dec_pos$",        (None, None)),
    # packed (1-bit) inference weights: (d_out, kw) — column-parallel
    # shard d_out; row-parallel shard the packed-word (d_in) axis.
    (r"attn/w[qkv]/w_packed$", ("model", "data")),
    (r"attn/wo/w_packed$",     ("data", "model")),
    (r"xattn/w[qkv]/w_packed$", ("model", "data")),
    (r"xattn/wo/w_packed$",    ("data", "model")),
    (r"mlp/w_(up|gate)/w_packed$", ("model", "data")),
    (r"mlp/w_down/w_packed$",  ("data", "model")),
    (r"head/w_packed$",        ("model", "data")),
    (r"attn/w[qkv]/alpha$",    ("model",)),
    (r"attn/wo/alpha$",        (None,)),
    (r"mlp/w_(up|gate)/alpha$", ("model",)),
    (r"mlp/w_down/alpha$",     (None,)),
    (r"head/alpha$",           ("model",)),
    (r"w_packed$",             (None, None)),   # fallback: replicate
    (r"alpha$",                (None,)),
]


def drop_fsdp(spec: tuple) -> tuple:
    """ZeRO-degree-0 variant: replicate over 'data' (weights + opt state
    fit per-chip); keeps TP over 'model'.  Collective cost becomes one
    grad all-reduce instead of per-layer weight all-gathers — the §Perf
    train-cell optimization."""
    return tuple(None if ax == "data" else ax for ax in spec)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_specs(params: Any, mesh: Mesh, *, fsdp: bool = True,
                replicate_embed: bool = False) -> Any:
    """PartitionSpec tree for a model/optimizer param tree.

    ``fsdp=False`` replicates parameters over the 'data' axis (ZeRO-0):
    right when optimizer state fits per-chip; see ``should_fsdp``.
    ``replicate_embed=True`` replicates the embedding table: a
    vocab-sharded table turns every lookup into masked-gather +
    all-reduce of the full (B, S, D) activation — replication trades
    ~1 GB of HBM for removing that collective (§Perf cell B v2)."""

    def spec_for(path, leaf):
        if not hasattr(leaf, "ndim") or leaf.ndim == 0:
            return P()
        pstr = _path_str(path)
        if replicate_embed and re.search(r"embed/table$", pstr):
            return P()
        # find the matching rule whose spec rank matches the trailing dims
        chosen = None
        for pat, spec in _PARAM_RULES:
            if re.search(pat, pstr) and len(spec) <= leaf.ndim:
                # prefer exact-trailing-rank match (moe 3d vs dense 2d)
                if chosen is None or len(spec) > len(chosen):
                    chosen = spec
        if chosen is None:
            return P()
        if not fsdp:
            chosen = drop_fsdp(chosen)
        # prepend None for any leading (scan-stack) axes
        full = (None,) * (leaf.ndim - len(chosen)) + tuple(chosen)
        return _fit(mesh, full, leaf.shape)

    return jax.tree_util.tree_map_with_path(spec_for, params)


def should_fsdp(cfg, mesh: Mesh, *, hbm_bytes: float = 16e9,
                budget: float = 0.6) -> bool:
    """ZeRO-degree policy: keep FSDP only if replicated-over-data
    optimizer state would overflow ``budget`` of HBM.

    Per-chip bytes without FSDP = total_params/TP x (4 master + 8 adam
    + 2 bf16 + 4 grad) = 18 B/param."""
    tp = _axis_size(mesh, "model") or 1
    total = cfg.param_counts()["total"]
    per_chip = total / tp * 18.0
    return per_chip > budget * hbm_bytes


def param_shardings(params: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params, mesh),
                        is_leaf=lambda x: isinstance(x, P))


# --------------------------------------------------------------------------
# activations / batches / caches
# --------------------------------------------------------------------------

def batch_specs(batch_like: Any, mesh: Mesh, *,
                shard_seq: bool = False) -> Any:
    """Input batch: batch dim over (pod, data); optionally the sequence
    dim instead (long-context, batch==1)."""

    def spec_for(leaf):
        if not hasattr(leaf, "ndim"):
            return P()
        if leaf.ndim == 0:
            return P()
        if shard_seq and leaf.ndim >= 2:
            return _fit(mesh, (None, DATA_AXES) + (None,) * (leaf.ndim - 2),
                        leaf.shape)
        return _fit(mesh, (DATA_AXES,) + (None,) * (leaf.ndim - 1),
                    leaf.shape)

    return jax.tree.map(spec_for, batch_like)


def cache_specs(cache: Any, mesh: Mesh, *, shard_seq: bool = False,
                kv_layout: str = "batch_heads") -> Any:
    """KV/state caches.  Layout (L, B, S, H, D) for attention K/V (leading
    scan axis), (L, B, ...) for recurrent states.

    kv_layout:
      'batch_heads' (baseline): batch over (pod, data), heads over model.
      'seq_model' (§Perf decode optimization): batch over (pod, data),
        the S axis over 'model'.  GQA head counts rarely divide the TP
        degree (kv=2..8 vs 16) so 'batch_heads' replicates attention
        across the model axis; sharding S instead always divides (32k),
        cuts the per-chip cache 16x, and GSPMD turns the softmax
        reductions into small (B, H) all-reduces — the flash-decoding
        combine, synthesized by the partitioner.
    ``shard_seq``: shard S over (pod, data) too (batch==1 long-context).
    """

    def spec_for(path, leaf):
        if not hasattr(leaf, "ndim") or leaf.ndim == 0:
            return P()
        pstr = _path_str(path)
        if re.search(r"(^|/)(k|v)$", pstr) and leaf.ndim >= 4:
            # (..., B, S, H, D) with possible leading stack axes
            lead = (None,) * (leaf.ndim - 4)
            if shard_seq:
                spec = lead + (None, DATA_AXES, "model", None)
            elif kv_layout == "seq_model":
                spec = lead + (DATA_AXES, "model", None, None)
            else:
                spec = lead + (DATA_AXES, None, "model", None)
            return _fit(mesh, spec, leaf.shape)
        if re.search(r"(k|v)_scale$", pstr) and leaf.ndim >= 3:
            # int8-KV scales: (..., B, S, H) — same layout minus head_dim
            lead = (None,) * (leaf.ndim - 3)
            if shard_seq:
                spec = lead + (None, DATA_AXES, "model")
            elif kv_layout == "seq_model":
                spec = lead + (DATA_AXES, "model", None)
            else:
                spec = lead + (DATA_AXES, None, "model")
            return _fit(mesh, spec, leaf.shape)
        # recurrent states: (..., B, ...): batch after stack axes is dim -? —
        # use: first dim that matches the batch size heuristically; simpler:
        # states replicate over model, batch over data at axis = ndim-2? Keep
        # conservative: shard nothing but the leading batch-like dim found.
        lead = (None,) * (leaf.ndim - 1)
        if leaf.ndim >= 2:
            spec = (None,) * (leaf.ndim - 2) + (DATA_AXES, None)
            # the batch dim of stacked states (L, B, ...) is axis 1
            if leaf.ndim >= 3:
                spec = (None, DATA_AXES) + (None,) * (leaf.ndim - 2)
            return _fit(mesh, spec, leaf.shape)
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, cache)


def logical_activation_spec(mesh: Mesh, ndim: int, *,
                            shard_seq: bool = False) -> P:
    if shard_seq:
        return _fit(mesh, (None, DATA_AXES) + (None,) * (ndim - 2),
                    (1 << 30,) * ndim)
    return _fit(mesh, (DATA_AXES,) + (None,) * (ndim - 1), (1 << 30,) * ndim)


# --------------------------------------------------------------------------
# Packed BCNN / BMLP forward (Espresso): C_out-parallel over 'model',
# batch-parallel over 'data'
# --------------------------------------------------------------------------
#
# Each output-channel shard of a packed stage owns its own packed weight
# rows, folded BN thresholds (tau/flip), pad-correction columns, and
# pool-mask words, so the conv + BN-sign + repack (+ bit-domain pool)
# epilogue is embarrassingly parallel along C_out (XNOR-Net's
# decomposition).  The one real seam is the C_out -> packed-word boundary
# at the re-bitpack epilogue — standalone bn_sign_pack AND the fused
# dense GEMM epilogue (ops.binary_matmul_bn_sign_packed) alike: a shard
# can only emit its own 32-bit word span if its channel range is
# word-aligned, i.e. c_out % (32 * |model|) == 0.  Sharded hidden dense
# stages therefore run the per-layer fused kernel on their local rows
# (models/cnn._dense_hidden_stack); the single-launch resident stack is
# reserved for unsharded stacks, where it composes with pure data
# parallelism (every 'data' shard runs the one-launch stack locally).
# Stages that fail the test degrade to replication over 'model' (the
# same divisibility-aware fallback philosophy as `_fit`), never to a
# wrong answer.  Packed activations are batch-sharded over 'data' and
# replicated over 'model'; the only cross-device traffic is the
# word-aligned all-gather of PACKED words at sharded stage boundaries —
# on the pure data-parallel path there are no collectives at all
# (asserted on compiled HLO by distributed/verify_sharded.py).

def packed_stage_shards(c_out: int, mesh: Mesh) -> int:
    """C_out-parallel shard count for one packed stage.

    The 'model' axis size when every shard owns whole 32-bit packed
    words (``c_out % (32·|model|) == 0``), else 1 — the stage replicates
    instead of splitting a word across devices.
    """
    from repro.core.binarize import WORD_BITS
    nm = _axis_size(mesh, "model")
    if nm > 1 and c_out % (WORD_BITS * nm) == 0:
        return nm
    return 1


def bcnn_shard_plan(packed: Any, mesh: Mesh) -> dict:
    """Per-stage shard counts for a ``pack_bcnn`` tree on ``mesh``.

    The last dense layer always replicates: its int32 output feeds the
    fp output batch-norm, not a word-packing epilogue.
    """
    conv = tuple(packed_stage_shards(p["c_out"], mesh)
                 for p in packed["convs"])
    douts = [p["w_packed"].shape[0] for p in packed["denses"]]
    dense = tuple(packed_stage_shards(d, mesh) for d in douts[:-1]) + (1,)
    return {"conv": conv, "dense": dense}


def bmlp_shard_plan(packed: Any, mesh: Mesh) -> dict:
    douts = [p["w_words"].shape[1] if "w_words" in p
             else p["w_packed"].shape[0] for p in packed["layers"]]
    layer = tuple(packed_stage_shards(d, mesh) for d in douts[:-1]) + (1,)
    return {"layer": layer}


def _is_array(leaf) -> bool:
    import numpy as np
    return isinstance(leaf, (jax.Array, np.ndarray))


def _bcnn_spec_rule(shard_plan: dict):
    """path-str + leaf -> PartitionSpec (or None for non-array statics)."""
    conv, dense = shard_plan["conv"], shard_plan["dense"]

    def rule(pstr: str, leaf) -> P | None:
        if not _is_array(leaf):
            return None
        m = re.match(r"convs/(\d+)/(w_packed|correction|rowsum)$", pstr)
        if m and conv[int(m.group(1))] > 1:
            if m.group(2) == "correction":      # (OH, OW, C_out)
                return P(None, None, "model")
            return P("model") if leaf.ndim == 1 else P("model", None)
        m = re.match(r"(folded_conv)/(\d+)/(tau|flip)$", pstr)
        if m and conv[int(m.group(2))] > 1:
            return P("model")
        m = re.match(r"pool_masks/(\d+)$", pstr)
        if m and conv[int(m.group(1))] > 1:
            return P("model")                   # (Cw,) packed-word spans
        m = re.match(r"denses/(\d+)/w_packed$", pstr)
        if m and dense[int(m.group(1))] > 1:
            return P("model", None)
        m = re.match(r"folded_dense/(\d+)/(tau|flip)$", pstr)
        if m and dense[int(m.group(1))] > 1:
            return P("model")
        return P()                              # replicate (bn_out, fallback)

    return rule


def _bmlp_spec_rule(shard_plan: dict):
    layer = shard_plan["layer"]

    def rule(pstr: str, leaf) -> P | None:
        if not _is_array(leaf):
            return None
        m = re.match(r"layers/(\d+)/(w_packed|w_words|w_rowsum)$", pstr)
        if m and layer[int(m.group(1))] > 1:
            if m.group(2) == "w_words":         # (Kw, N) word-major
                return P(None, "model")
            return P("model") if leaf.ndim == 1 else P("model", None)
        m = re.match(r"folded/(\d+)/(tau|flip)$", pstr)
        if m and layer[int(m.group(1))] > 1:
            return P("model")
        return P()

    return rule


def _packed_kind(packed: Any) -> str:
    from repro.models.cnn import packed_kind
    return packed_kind(packed)


def _packed_rule(packed: Any, mesh: Mesh):
    if _packed_kind(packed) == "bcnn":
        return _bcnn_spec_rule(bcnn_shard_plan(packed, mesh))
    return _bmlp_spec_rule(bmlp_shard_plan(packed, mesh))


def _fitted_spec(mesh: Mesh, s: P, leaf) -> P:
    """`_fit`-checked, trailing-None-normalized spec for one array leaf.

    Placement (`shard_packed`), the shard_map in_specs, and the
    advertised `packed_param_specs` map ALL go through this one
    function, so a rule whose axis cannot divide the dim degrades to
    replication everywhere consistently instead of failing to lower.
    """
    fitted = tuple(_fit(mesh, tuple(s) + (None,) * (leaf.ndim - len(s)),
                        leaf.shape))
    while fitted and fitted[-1] is None:            # P(None,..) == P()
        fitted = fitted[:-1]
    return P(*fitted)


def packed_param_specs(packed: Any, mesh: Mesh) -> dict[str, P]:
    """{'/'-joined path: PartitionSpec} for every array leaf of a packed
    BCNN/BMLP tree — exactly the specs placement and shard_map use."""
    rule = _packed_rule(packed, mesh)
    out: dict[str, P] = {}

    def visit(path, leaf):
        s = rule(_path_str(path), leaf)
        if s is not None:
            out[_path_str(path)] = _fitted_spec(mesh, s, leaf)
        return leaf

    jax.tree_util.tree_map_with_path(visit, packed)
    return out


def shard_packed(packed: Any, mesh: Mesh) -> Any:
    """device_put every array leaf of a packed tree with its
    NamedSharding (one-time placement, paper C2 spirit: pack once, place
    once).  Statics (plan geometry ints, the spec dataclass) pass
    through untouched."""
    rule = _packed_rule(packed, mesh)

    def put(path, leaf):
        s = rule(_path_str(path), leaf)
        if s is None:
            return leaf
        return jax.device_put(leaf,
                              NamedSharding(mesh, _fitted_spec(mesh, s,
                                                               leaf)))

    return jax.tree_util.tree_map_with_path(put, packed)


def reshard_packed(packed: Any, mesh: Mesh | None) -> Any:
    """Move a packed tree to a DIFFERENT mesh (elastic degradation).

    Array leaves are pulled to host first — after a (simulated) device
    loss the old placements may reference devices that no longer exist,
    so re-placement must not read through them lazily inside a jit.
    ``mesh=None`` returns the host-resident tree (the checkpoint-shaped
    view); otherwise the tree is placed via :func:`shard_packed` under
    the new mesh's own divisibility plan.  Cheap by construction: the
    paper's 32x weight compression means the bytes crossing host here
    are the packed words, not fp32 weights.
    """
    import numpy as np
    host = jax.tree.map(lambda l: np.asarray(l) if _is_array(l) else l,
                        packed)
    if mesh is None:
        return host
    return shard_packed(host, mesh)


# `shard_bcnn` / `shard_bmlp`: explicit entry points (same placement,
# kind-checked).
def shard_bcnn(packed: Any, mesh: Mesh) -> Any:
    assert _packed_kind(packed) == "bcnn"
    return shard_packed(packed, mesh)


def shard_bmlp(packed: Any, mesh: Mesh) -> Any:
    assert _packed_kind(packed) == "bmlp"
    return shard_packed(packed, mesh)


def _partition_arrays(tree: Any):
    """Split a mixed pytree into (array leaves, their paths, rebuild fn).

    ``shard_map`` can only take arrays as operands; plan statics (ints,
    pad tuples, the spec dataclass) are baked back in by ``rebuild``
    inside the traced body.  One flatten produces both the operand list
    and the path strings its specs are looked up by, so the two can
    never disagree on leaf order.
    """
    leaves_p, treedef = jax.tree_util.tree_flatten_with_path(tree)
    is_arr = [_is_array(l) for _, l in leaves_p]
    arrays = [l for (_, l), a in zip(leaves_p, is_arr) if a]
    paths = [_path_str(p) for (p, _), a in zip(leaves_p, is_arr) if a]

    def rebuild(arrs):
        it = iter(arrs)
        merged = [next(it) if a else l
                  for (_, l), a in zip(leaves_p, is_arr)]
        return jax.tree_util.tree_unflatten(treedef, merged)

    return arrays, paths, rebuild


class ShardedForward:
    """Callable wrapper around the jitted shard_map'd packed forward.

    Holds the device_put params (``.arrays``, the packed tree's array
    leaves as placed) so calls are ``fwd(x)``; exposes ``.lower(x)`` for
    HLO inspection, ``.shard_plan`` for tests, and
    the serving-facing seams ``.kind`` / ``.batch_multiple`` — the
    request queue (``train.serve.PackedInferenceServer``) sizes its
    flush buckets to multiples of ``batch_multiple`` so every flush
    satisfies the shard_map batch divisibility rule.
    """

    def __init__(self, jitted, arrays, shard_plan: dict, mesh: Mesh,
                 kind: str, telemetry=None):
        from repro import telemetry as _telemetry
        self._jitted = jitted
        self.arrays = arrays
        self.shard_plan = shard_plan
        self.mesh = mesh
        self.kind = kind
        self.telemetry = (telemetry if telemetry is not None
                          else _telemetry.default())

    @property
    def batch_multiple(self) -> int:
        """Every submitted batch must be a multiple of this (the product
        of the mesh's data-parallel axis sizes)."""
        mult = 1
        for ax in DATA_AXES:
            mult *= max(1, _axis_size(self.mesh, ax))
        return mult

    def __call__(self, x):
        tr = self.telemetry.tracer
        if not tr.enabled:
            return self._jitted(self.arrays, x)
        # Traced path only: splitting dispatch from block costs a
        # block_until_ready the async-dispatch steady state must not
        # pay, so the untraced fast path above stays one call.
        with tr.span("sharded.dispatch", mesh=list(self.mesh.shape.values()),
                     kind=self.kind):
            out = self._jitted(self.arrays, x)
        with tr.span("sharded.block"):
            jax.block_until_ready(out)
        return out

    def lower(self, x):
        return self._jitted.lower(self.arrays, x)


def make_sharded_forward(packed: Any, mesh: Mesh, *,
                         backend: str = "auto",
                         dense_stack: str = "auto",
                         telemetry=None) -> ShardedForward:
    """Shard-mapped packed BCNN/BMLP forward on a ('data', 'model') mesh.

    Batch shards over 'data'; every word-divisible stage C_out-shards
    over 'model' (see :func:`packed_stage_shards`), with per-stage
    degradation to replication otherwise.  Inside the conv stack the
    only collectives are tiled all-gathers of PACKED words at sharded
    stage seams — zero collectives on the pure data-parallel path.  The
    batch must divide the 'data' axis size.  Bit-identical to the
    single-device forward (distributed/verify_sharded.py sweeps mesh
    shapes on a forced-8-device CPU platform).

    ``dense_stack`` forwards to the model: hidden dense stages that are
    NOT model-sharded run the single-launch VMEM-resident stack (the
    residency decision is pure shape math, so every shard agrees);
    model-sharded stages always run per-layer fused kernels on their
    local word-aligned rows.
    """
    from jax.experimental.shard_map import shard_map

    from repro.models import cnn as _cnn

    kind = _packed_kind(packed)
    rule = _packed_rule(packed, mesh)
    plan = (bcnn_shard_plan(packed, mesh) if kind == "bcnn"
            else bmlp_shard_plan(packed, mesh))
    placed = shard_packed(packed, mesh)
    arrays, arr_paths, rebuild = _partition_arrays(placed)
    arr_specs = [_fitted_spec(mesh, rule(p, l), l)
                 for p, l in zip(arr_paths, arrays)]

    x_ndim = 4 if kind == "bcnn" else 2
    x_spec = logical_activation_spec(mesh, x_ndim)
    out_spec = logical_activation_spec(mesh, 2)
    model_axis = "model" if _axis_size(mesh, "model") > 1 else None

    def fwd(arrs, x):
        p = rebuild(arrs)
        if kind == "bcnn":
            return _cnn.bcnn_forward_packed(
                p, x, backend=backend, model_axis=model_axis,
                conv_shards=plan["conv"], dense_shards=plan["dense"],
                dense_stack=dense_stack)
        return _cnn.bmlp_forward_packed(
            p, x, backend=backend, model_axis=model_axis,
            layer_shards=plan["layer"], dense_stack=dense_stack)

    sm = shard_map(fwd, mesh=mesh, in_specs=(arr_specs, x_spec),
                   out_specs=out_spec, check_rep=False)
    return ShardedForward(jax.jit(sm), arrays, plan, mesh, kind,
                          telemetry=telemetry)
