"""Packed binary-LM forward: kernel routing, bit-exactness, serving.

``transformer_forward_packed`` must (a) trace its attention to the
blocked ``binary_attention`` Pallas launches and every projection to
the dense megakernels (launch-shape evidence via ``repro.analysis``),
(b) be bit-exact against the pure-jnp oracle path for registry
configs, and (c) serve through ``PackedInferenceServer`` via the
``packed_kind == 'transformer'`` seam.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops as kops
from repro.models import cnn
from repro.models import transformer as TF
from repro.train import serve as SV
from repro.analysis import pallas_launches

ARCHS = ("gemma2-9b", "starcoder2-3b")


def _packed_lm(name, *, max_len=8, seed=0):
    cfg = get_config(name, reduced=True)
    params = TF.init_binary_lm(jax.random.PRNGKey(seed), cfg)
    return TF.pack_transformer(params, cfg, max_len=max_len), cfg


def _tokens(batch, seq, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (batch, seq), dtype=np.uint8)


@pytest.mark.parametrize("name", ARCHS)
def test_pallas_matches_jnp_oracle(name):
    """Bit-exact: the integer XNOR-popcount score path and the packed
    projections make the pallas and jnp routes produce identical
    logits, not merely close ones."""
    packed, cfg = _packed_lm(name)
    toks = jnp.asarray(_tokens(2, 8))
    out_p = TF.transformer_forward_packed(packed, toks, backend="pallas")
    out_j = TF.transformer_forward_packed(packed, toks, backend="jnp")
    assert out_p.shape == (2, cfg.vocab_size)
    assert out_p.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_j))


@pytest.mark.parametrize("name", ARCHS)
def test_launch_shapes(name):
    """The forward is made of Pallas launches: one blocked attention
    per layer — grid (B·Hq, Sq tiles, KV tiles) — plus the dense
    megakernel grids for Q/K/V/O, FFN, and the LM head."""
    packed, cfg = _packed_lm(name)
    toks = jnp.asarray(_tokens(2, 8))

    def fwd(t):
        return TF.transformer_forward_packed(packed, t, backend="pallas")

    launches = pallas_launches(fwd, toks)
    attn = [l for l in launches if "attention" in l.kernel]
    assert len(attn) == cfg.num_layers
    hq = cfg.num_heads
    for l in attn:
        # S=8 fits one q tile and one kv tile; heads ride the first axis.
        assert l.grid == (2 * hq, 1, 1), l
    dense = [l for l in launches if "attention" not in l.kernel]
    # 4 attention projections + 2 FFN matmuls per layer, 1 head readout,
    # plus a bitpack launch in front of each packed matmul.
    assert len(dense) >= 6 * cfg.num_layers + 1
    kinds = {l.kernel for l in dense}
    assert any("matmul" in k or "gemm" in k or "gemv" in k for k in kinds), \
        kinds


def test_dense_stack_validated():
    packed, _ = _packed_lm("gemma2-9b")
    toks = jnp.asarray(_tokens(1, 8))
    with pytest.raises(ValueError, match="dense_stack"):
        TF.transformer_forward_packed(packed, toks, dense_stack="residnet")


# ---------------------------------------------------------------------------
# packed_kind seam (models/cnn.py)
# ---------------------------------------------------------------------------

def test_packed_tree_seam():
    packed, cfg = _packed_lm("gemma2-9b")
    assert cnn.packed_kind(packed) == "transformer"
    assert cnn.packed_input_shape(packed) == (8,)
    widths = [blk[k]["w_packed"].shape[1]
              for blk in packed["blocks"]
              for k in ("wq", "wk", "wv", "wo", "w1", "w2")]
    widths.append(packed["head"]["w_packed"].shape[1])
    assert cnn.packed_dense_kw_words(packed) == max(widths)
    fwd = cnn.make_packed_forward(packed, backend="jnp")
    out = fwd(jnp.asarray(_tokens(1, 8)))
    assert out.shape == (1, cfg.vocab_size)
    with pytest.raises(ValueError, match="pack_transformer"):
        cnn.packed_kind({"bogus": 1})


# ---------------------------------------------------------------------------
# Serving through PackedInferenceServer
# ---------------------------------------------------------------------------

def _server(**kw):
    clock = SV.SimClock()
    kw.setdefault("max_batch", 8)
    kw.setdefault("default_deadline", 0.010)
    return SV.PackedInferenceServer(clock=clock, **kw), clock


def test_serves_registry_config():
    """Any registry config serves: register the packed LM, push tokens
    through the queue, get the same logits as the direct forward, on
    the GEMV route (reduced LM widths fit the resident block)."""
    packed, cfg = _packed_lm("gemma2-9b")
    srv, _ = _server()
    srv.register("lm", packed=packed, backend="jnp")
    assert srv.engine("lm").kind == "transformer"
    xs = list(_tokens(5, 8))
    got = srv.serve(xs)
    direct = TF.transformer_forward_packed(
        packed, jnp.asarray(np.stack(xs)), backend="jnp")
    assert len(got) == 5
    for i, g in enumerate(got):
        np.testing.assert_array_equal(g, np.asarray(direct[i]))
    kw = cnn.packed_dense_kw_words(packed)
    assert srv.route_for(5) == kops.dispatch_batch(8, kw) == "gemv"


def test_register_from_params_and_spec():
    """The params+spec route: spec is the ArchConfig, params come from
    init_binary_lm; the weight cache packs once (default max_len=16)."""
    cfg = get_config("starcoder2-3b", reduced=True)
    params = TF.init_binary_lm(jax.random.PRNGKey(3), cfg)
    srv, _ = _server()
    srv.register("lm", params, cfg, kind="transformer", backend="jnp")
    assert (srv.cache.misses, srv.cache.hits) == (1, 0)
    assert cnn.packed_input_shape(srv.engine("lm").packed) == (16,)
    xs = list(_tokens(3, 16, seed=1))
    got = srv.serve(xs)
    assert len(got) == 3 and got[0].shape == (cfg.vocab_size,)
    srv.register("lm", params, cfg, kind="transformer", backend="jnp")
    assert srv.cache.misses == 1 and srv.cache.hits == 1


def test_transformer_mesh_serving_rejected():
    packed, _ = _packed_lm("gemma2-9b")
    srv, _ = _server()
    with pytest.raises(ValueError, match="mesh"):
        srv.register("lm", packed=packed, backend="jnp", mesh=object())


def test_unknown_kind_message_names_transformer():
    srv, _ = _server()
    with pytest.raises(ValueError, match="transformer"):
        srv.register("m", {}, None, kind="rnn")
