"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch starcoder2-3b \
        --reduced --steps 50 [--quant binary] [--data 2 --model 1] \
        [--microbatches 2] [--ckpt-dir /tmp/ckpt] [--compress-grads]

Full-size configs target the production mesh; on a CPU host use
--reduced for a real end-to-end run.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.configs import get_config
from repro.data.synthetic import TokenStreamConfig, token_batch
from repro.distributed import sharding as SH
from repro.launch.mesh import make_host_mesh
from repro.train import trainer as TR


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--quant", default=None)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--lr", type=float, default=1e-3)
    args = ap.parse_args()

    cfg = get_config(args.arch, quant=args.quant, reduced=args.reduced)
    tc = TR.TrainConfig(microbatches=args.microbatches,
                        compress_grads=args.compress_grads, lr=args.lr,
                        warmup=5, total_steps=args.steps)
    mesh = make_host_mesh(args.data, args.model)
    print(f"mesh {dict(mesh.shape)} arch {cfg.name} quant "
          f"{cfg.quant.mode.value}")

    state = TR.init_train_state(jax.random.PRNGKey(0), cfg, tc)
    pspecs = SH.param_specs(state["params"], mesh)
    state_specs = {"params": pspecs,
                   "opt": {"mu": pspecs, "nu": pspecs, "step": P()}}
    if tc.compress_grads:
        state_specs["ef_error"] = pspecs
    ns = lambda tree: jax.tree.map(lambda s: NamedSharding(mesh, s), tree,
                                   is_leaf=lambda x: isinstance(x, P))
    state = jax.device_put(state, ns(state_specs))

    dcfg = TokenStreamConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                             global_batch=args.batch)
    step_fn = jax.jit(TR.make_train_step(cfg, tc), donate_argnums=(0,))

    start = 0
    if args.ckpt_dir:
        last = latest_step(args.ckpt_dir)
        if last is not None:
            state, meta = load_checkpoint(args.ckpt_dir, last, state,
                                          ns(state_specs))
            start = int(meta["step"]) + 1
            print(f"restored step {last}")

    t0 = time.monotonic()
    with mesh:
        for i in range(start, args.steps):
            batch = token_batch(dcfg, i)
            bspecs = SH.batch_specs(batch, mesh)
            batch = jax.device_put(batch, ns(bspecs))
            state, metrics = step_fn(state, batch)
            if i % 5 == 0 or i == args.steps - 1:
                print(f"step {i:4d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f} "
                      f"lr {float(metrics['lr']):.2e}")
            if args.ckpt_dir and (i + 1) % args.ckpt_every == 0:
                save_checkpoint(args.ckpt_dir, i, state)
    dt = time.monotonic() - t0
    print(f"done: {args.steps - start} steps in {dt:.1f}s "
          f"({(args.steps - start) / max(dt, 1e-9):.2f} it/s)")


if __name__ == "__main__":
    main()
