"""Paper Table 2: binary MLP forward on MNIST-shaped input (batch 1).

Reports forward wall-time for the full 784-4096^3-10 BMLP across the
backend variants (paper: CPU 37.4 ms / GPU 3.2 ms / GPUopt 0.26 ms), the
first-layer bit-plane optimization on/off delta (paper: ~3x whole-net),
and the 31x memory figure (paper §6.2)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.models import cnn
from repro.utils.tree import tree_bytes


def _time(fn, *args, reps=5):
    jax.block_until_ready(fn(*args))
    t0 = time.monotonic()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.monotonic() - t0) / reps * 1e6


def rows() -> list[tuple]:
    key = jax.random.PRNGKey(0)
    spec = cnn.BMLPSpec()                     # 784-4096-4096-4096-10
    params = cnn.init_bmlp(key, spec)
    packed = cnn.pack_bmlp(params, spec)
    x = jax.random.randint(key, (1, 784), 0, 256).astype(jnp.uint8)

    out = []
    f_float = jax.jit(lambda v: cnn.bmlp_forward_float(params, v))
    out.append(("table2/bmlp_float_fwd_b1", _time(f_float, x),
                "float-sign reference (Espresso-CPU analogue)"))
    f_packed = jax.jit(lambda v: cnn.bmlp_forward_packed(packed, v,
                                                         backend="jnp"))
    out.append(("table2/bmlp_packed_fwd_b1", _time(f_packed, x),
                "packed XNOR path (GPUopt analogue, binary-jnp)"))

    # first-layer binary optimization off: first layer in float, rest
    # packed — measures the paper's ~3x first-layer claim structurally
    import repro.core.binary_layers as L

    def hybrid(p_packed, p_float, v):
        z = L.apply_bitplane_dense_float(p_float["layers"][0], v)
        h = L.apply_bn_sign_folded(p_packed["folded"][0], z)
        z = L.apply_binary_dense_packed(p_packed["layers"][1], h,
                                        backend="jnp")
        h = L.apply_bn_sign_folded(p_packed["folded"][1], z)
        z = L.apply_binary_dense_packed(p_packed["layers"][2], h,
                                        backend="jnp")
        h = L.apply_bn_sign_folded(p_packed["folded"][2], z)
        z = L.apply_binary_dense_packed(p_packed["layers"][3], h,
                                        backend="jnp")
        return L.apply_bn_affine(p_packed["bn_out"], z)

    f_hybrid = jax.jit(lambda v: hybrid(packed, params, v))
    out.append(("table2/bmlp_first_layer_float_fwd_b1",
                _time(f_hybrid, x),
                "first layer NOT binary-optimized (paper §6.2 ablation)"))

    fp_b = tree_bytes(params)
    bin_b = tree_bytes(packed)
    out.append(("table2/bmlp_param_bytes_float", float(fp_b), ""))
    out.append(("table2/bmlp_param_bytes_packed", float(bin_b),
                f"{fp_b / bin_b:.1f}x smaller (paper reports ~31x)"))
    return out


def main() -> None:
    for name, us, note in rows():
        print(f"{name},{us:.1f},{note}")


if __name__ == "__main__":
    main()
