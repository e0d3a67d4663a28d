"""End-to-end behaviour tests for the whole system.

1. The paper's pipeline: train-style latent BMLP -> pack -> binary
   inference agrees; speed/memory claims structurally verified elsewhere
   (the ``repro.analysis`` report and the kernel property tests).
2. LM pipeline: train a reduced arch on the synthetic stream, checkpoint,
   kill, restore, continue — loss continues to drop and the data cursor
   resumes deterministically.
3. Serving: prefill + batched greedy decode produces deterministic
   tokens.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.configs import get_config
from repro.data.synthetic import TokenStreamConfig, token_batch
from repro.models import cnn
from repro.models import model as M
from repro.train import trainer as TR


def test_paper_pipeline_end_to_end():
    """BinaryNet-style training signature -> Espresso-style deployment."""
    key = jax.random.PRNGKey(0)
    spec = cnn.BMLPSpec(sizes=(16, 32, 10))
    params = cnn.init_bmlp(key, spec)
    x = jax.random.randint(key, (4, 16), 0, 256).astype(jnp.uint8)
    y = jax.random.randint(jax.random.fold_in(key, 1), (4,), 0, 10)

    def loss_fn(p):
        logits = cnn.bmlp_forward_float(p, x, ste=True)
        return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(4), y])

    g = jax.grad(loss_fn)(params)
    # STE gives nonzero weight grads (trainable)
    assert any(float(jnp.abs(l).max()) > 0
               for l in jax.tree.leaves(g["layers"]))
    # deploy: pack once, run packed
    packed = cnn.pack_bmlp(params, spec)
    out = cnn.bmlp_forward_packed(packed, x, backend="jnp")
    ref = cnn.bmlp_forward_float(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                               atol=1e-4)


def test_train_kill_restore_continue(tmp_path):
    cfg = get_config("starcoder2-3b", reduced=True)
    tc = TR.TrainConfig(lr=3e-3, warmup=2, total_steps=40)
    dcfg = TokenStreamConfig(vocab_size=cfg.vocab_size, seq_len=32,
                             global_batch=8)
    step = jax.jit(TR.make_train_step(cfg, tc))

    # run A: 10 steps, checkpoint at 9
    state = TR.init_train_state(jax.random.PRNGKey(0), cfg, tc)
    for i in range(10):
        state, m = step(state, token_batch(dcfg, i))
    save_checkpoint(str(tmp_path), 9, state, extra={"data_step": 10})
    for i in range(10, 15):
        state, m = step(state, token_batch(dcfg, i))
    loss_a = float(m["loss"])
    ref_leaf = np.asarray(jax.tree.leaves(state["params"])[0])

    # run B: restore at 9, replay the same data steps
    state_b = TR.init_train_state(jax.random.PRNGKey(0), cfg, tc)
    state_b, meta = load_checkpoint(str(tmp_path),
                                    latest_step(str(tmp_path)), state_b)
    assert meta["extra"]["data_step"] == 10
    for i in range(10, 15):
        state_b, m_b = step(state_b, token_batch(dcfg, i))
    loss_b = float(m_b["loss"])
    np.testing.assert_allclose(loss_a, loss_b, rtol=1e-4)
    np.testing.assert_allclose(
        ref_leaf, np.asarray(jax.tree.leaves(state_b["params"])[0]),
        rtol=1e-4, atol=1e-5)


def test_greedy_decode_deterministic():
    cfg = get_config("starcoder2-3b", reduced=True)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                              cfg.vocab_size)

    def gen():
        logits, cache = M.prefill(params, cfg, {"tokens": toks}, 16)
        tok = jnp.argmax(logits[:, 0], -1)[:, None].astype(jnp.int32)
        out = [tok]
        for t in range(7):
            logits, cache = M.decode_step(params, cfg, tok, cache,
                                          jnp.int32(8 + t))
            tok = jnp.argmax(logits[:, 0], -1)[:, None].astype(jnp.int32)
            out.append(tok)
        return np.asarray(jnp.concatenate(out, 1))

    a, b = gen(), gen()
    np.testing.assert_array_equal(a, b)


def test_data_stream_deterministic_and_learnable():
    dcfg = TokenStreamConfig(vocab_size=101, seq_len=16, global_batch=4)
    b1 = token_batch(dcfg, 5)
    b2 = token_batch(dcfg, 5)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    # labels are the next token (shifted)
    np.testing.assert_array_equal(np.asarray(b1["tokens"][:, 1:]),
                                  np.asarray(b1["labels"][:, :-1]))
