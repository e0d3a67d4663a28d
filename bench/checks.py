"""The comparison that decides ``correct``.

The served logits of a sample of the window's requests, drawn from the
seed, are compared with the plain float reference (``bench/reference``)
run on the same images at float32 with ``highest`` matmul precision.
Two numbers are compared, each against its limit:

* ``logit_gap``: the widest gap over the sample between a served logit
  and the reference's, as a share of the largest reference logit of its
  row (at least 1).  How the limit was set, from which readings, is in
  PERF.md.
* ``missing``: requests of the sample (or of the window) that never
  came back ``ok``; limit 0.
"""
from __future__ import annotations

import numpy as np

LIMITS = {"logit_gap": 1e-3, "missing": 0}


def logit_gap(served: np.ndarray, ref: np.ndarray) -> float:
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    if served.shape != ref.shape:
        raise ValueError(f"served {served.shape} != reference {ref.shape}")
    if not np.isfinite(served).all():
        return float("inf")
    scale = np.maximum(1.0, np.abs(ref).max(axis=-1))
    return float((np.abs(served - ref).max(axis=-1) / scale).max())


def reference_logits(refmod, cfg: dict, params, xs: np.ndarray, *,
                     dtype=None, precision: str = "highest",
                     block: int = 256) -> np.ndarray:
    """The reference over ``xs`` in blocks of ``block`` rows."""
    import functools

    import jax
    import jax.numpy as jnp

    dtype = jnp.float32 if dtype is None else dtype
    fwd = jax.jit(functools.partial(refmod.forward, cfg=cfg, dtype=dtype))
    out = []
    with jax.default_matmul_precision(precision):
        for i in range(0, len(xs), block):
            out.append(np.asarray(fwd(params, xs[i:i + block]),
                                  np.float32))
    return np.concatenate(out)


def judge(gap: float, missing: int) -> tuple[bool, dict]:
    """``correct`` and each number beside its limit."""
    checks = {"logit_gap": {"value": gap, "limit": LIMITS["logit_gap"]},
              "missing": {"value": missing, "limit": LIMITS["missing"]}}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
