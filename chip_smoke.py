#!/usr/bin/env python3
"""Smoke test of the packed BCNN/BMLP serving path on a TPU.

    python chip_smoke.py                # one chip: BMLP and BCNN
    python chip_smoke.py --four-chips   # BCNN behind the server on a 2x2 mesh

One chip: each of the paper's two networks (the BMLP 784-4096x3-10 and
the CIFAR-10 BCNN, at their published widths, random weights from
``--seed``) is registered with ``PackedInferenceServer`` under
``backend="pallas"``, serves single requests (GEMV flushes) and one
burst (a GEMM flush), and every request must end ``ok`` with logits
that match the float reference forward: the same argmax and
``allclose(rtol=1e-6)``.  The jitted forward must hold compiled Pallas
kernels (``tpu_custom_call``).

``--four-chips``: the BCNN is served on a ``(data, model) = (2, 2)``
mesh of the real devices and must match the same server on one device
bit for bit, with every packed weight leaf laid out as its sharding
says on every device of the mesh.

Everything runs in this one process.  It exits non-zero when JAX finds
no TPU and when any phase fails.  The last line of standard output is
a JSON object naming the device, printed only on success.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SINGLES = 4          # requests served one at a time: bucket 1, GEMV route
BURST = 16           # requests served as one flush: bucket 16, GEMM route
RTOL = 1e-6


class SmokeFailure(RuntimeError):
    """A phase of the smoke test found a wrong result."""


def build_params(kind: str, spec, seed: int) -> dict:
    """Random weights for ``kind`` at ``spec`` with non-trivial batch norm.

    Hidden batch norms get a per-channel sign flip and a half-integer
    mean with zero shift, so the folded threshold ``tau`` is that mean
    and can never tie an integer pre-activation: the packed compare and
    the float ``sign(BN(z))`` then agree exactly.  The output batch norm
    gets continuous statistics.
    """
    import jax
    import jax.numpy as jnp

    from repro.models import cnn

    key = jax.random.PRNGKey(seed)
    init = cnn.init_bcnn if kind == "bcnn" else cnn.init_bmlp
    params = init(key, spec)
    bns = (params["conv_bns"] + params["dense_bns"] if kind == "bcnn"
           else params["bns"])
    for i, bn in enumerate(bns):
        k = jax.random.split(jax.random.fold_in(key, 1000 + i), 5)
        c = bn["gamma"].shape[0]
        sign = jnp.where(jax.random.bernoulli(k[0], 0.3, (c,)), -1.0, 1.0)
        bn["gamma"] = sign * jax.random.uniform(k[1], (c,), minval=0.3,
                                                maxval=1.5)
        bn["var"] = jax.random.uniform(k[2], (c,), minval=0.5, maxval=2.0)
        mean = 3.0 * jax.random.normal(k[3], (c,))
        if i < len(bns) - 1:
            bn["mean"], bn["beta"] = jnp.floor(mean) + 0.5, jnp.zeros((c,))
        else:
            bn["mean"], bn["beta"] = mean, jax.random.normal(k[4], (c,))
    return params


def make_inputs(example_shape: tuple[int, ...], n: int,
                seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (n, *example_shape), dtype=np.uint8)


def float_reference(kind: str, params: dict, spec, xs: np.ndarray
                    ) -> np.ndarray:
    """The plain float forward, with f32 matmuls at full precision."""
    import jax

    from repro.models import cnn

    with jax.default_matmul_precision("highest"):
        if kind == "bcnn":
            return np.asarray(cnn.bcnn_forward_float(params, xs, spec))
        return np.asarray(cnn.bmlp_forward_float(params, xs))


def new_server(params: dict, spec, kind: str, mesh=None):
    """The serving path under test: compiled Pallas kernels, never
    ``backend="auto"`` (which would pick jnp off a TPU)."""
    from repro.train import serve as SV

    srv = SV.PackedInferenceServer(max_batch=BURST, buckets=(1, BURST),
                                   default_deadline=0.0)
    srv.register(kind, params, spec, kind=kind, backend="pallas", mesh=mesh)
    return srv


def warm_up(srv) -> dict[int, float]:
    """Compile the forward for every bucket; seconds per bucket."""
    import jax

    eng = srv.engine()
    seconds = {}
    for bucket in eng.buckets:
        x = np.zeros((bucket, *eng.example_shape), np.uint8)
        t0 = time.perf_counter()
        jax.block_until_ready(eng.fwd(x))
        seconds[bucket] = time.perf_counter() - t0
    return seconds


def serve_requests(srv, xs: np.ndarray) -> list:
    """Serve ``xs[:SINGLES]`` one request per flush, then the rest as one
    burst; every request must end ``ok``.  Returns the requests in
    submission order."""
    done = []
    for x in xs[:SINGLES]:
        srv.submit(x, deadline=0.0)
        done += srv.step()
    for x in xs[SINGLES:]:
        srv.submit(x, deadline=0.0)
    done += srv.step()
    done += srv.flush()
    done.sort(key=lambda r: r.rid)
    if len(done) != len(xs):
        raise SmokeFailure(f"{len(xs)} requests submitted, "
                           f"{len(done)} completed")
    bad = [(r.rid, r.status, repr(r.error)) for r in done
           if r.status != "ok"]
    if bad:
        raise SmokeFailure(f"{len(bad)} request(s) not ok: {bad[:3]}")
    return done


def check_routes(srv) -> list[str]:
    routes = sorted({f.route for f in srv.flushes})
    if routes != ["gemm", "gemv"]:
        raise SmokeFailure(f"flush routes {routes}, want both gemv and gemm")
    return routes


def compare_to_reference(served: np.ndarray, ref: np.ndarray) -> float:
    """Exact argmax match and ``allclose(rtol=RTOL)``; returns the
    largest absolute difference."""
    if served.shape != ref.shape:
        raise SmokeFailure(f"served shape {served.shape} != reference "
                           f"{ref.shape}")
    if not np.isfinite(served).all():
        raise SmokeFailure("served logits are not all finite")
    agree = served.argmax(-1) == ref.argmax(-1)
    if not agree.all():
        raise SmokeFailure(f"argmax differs on rows "
                           f"{np.flatnonzero(~agree).tolist()}")
    if not np.allclose(served, ref, rtol=RTOL):
        raise SmokeFailure(f"logits differ from the float reference: max "
                           f"|diff| {np.abs(served - ref).max()}")
    return float(np.abs(served - ref).max())


def check_network(kind: str, spec, *, seed: int) -> dict:
    """Serve ``kind`` at ``spec`` and check it against the float forward.

    Returns the server and what was measured; raises
    :class:`SmokeFailure` on a wrong result.
    """
    params = build_params(kind, spec, seed)
    t0 = time.perf_counter()
    srv = new_server(params, spec, kind)
    register_s = time.perf_counter() - t0
    compile_s = warm_up(srv)
    xs = make_inputs(srv.engine().example_shape, SINGLES + BURST, seed)
    done = serve_requests(srv, xs)
    routes = check_routes(srv)
    served = np.stack([np.asarray(r.result) for r in done])
    max_diff = compare_to_reference(served,
                                    float_reference(kind, params, spec, xs))
    return {"server": srv, "x": xs[:1], "register_s": register_s,
            "compile_s": compile_s, "routes": routes,
            "single_ms": [r.latency * 1e3 for r in done[:SINGLES]],
            "burst_ms": [r.latency * 1e3 for r in done[SINGLES:]],
            "max_abs_diff": max_diff}


def check_kernels(fwd, x: np.ndarray) -> int:
    """The compiled forward must launch Pallas kernels; returns how many
    times ``tpu_custom_call`` appears in its compiled text."""
    n = fwd.lower(x).compile().as_text().count("tpu_custom_call")
    if not n:
        raise SmokeFailure("compiled forward holds no tpu_custom_call")
    return n


def one_chip(seed: int) -> None:
    from repro.models import cnn

    for kind, spec in (("bmlp", cnn.BMLPSpec()), ("bcnn", cnn.BCNNSpec())):
        rep = check_network(kind, spec, seed=seed)
        srv = rep["server"]
        n_calls = check_kernels(srv.engine().fwd, rep["x"])
        comp = " ".join(f"bucket{b}={s:.2f}s"
                        for b, s in rep["compile_s"].items())
        print(f"{kind}: registered (pack + fold) in {rep['register_s']:.2f}s;"
              f" compile+first run {comp}")
        print(f"{kind}: {len(rep['single_ms'])} single requests "
              f"p50={statistics.median(rep['single_ms']):.3f}ms "
              f"max={max(rep['single_ms']):.3f}ms; burst of "
              f"{len(rep['burst_ms'])} p50="
              f"{statistics.median(rep['burst_ms']):.3f}ms")
        print(f"{kind}: flush routes {rep['routes']} "
              f"{[(f.bucket, f.route) for f in srv.flushes]}; all "
              f"{len(rep['single_ms']) + len(rep['burst_ms'])} requests ok")
        print(f"{kind}: argmax match, allclose rtol={RTOL}, max |served - "
              f"float| = {rep['max_abs_diff']!r}; tpu_custom_call x{n_calls}")


def check_placement(fwd, specs: dict) -> int:
    """Every placed packed leaf has one shard on each device of the mesh,
    of the shape its sharding gives; returns how many leaves are split
    over the ``model`` axis (must be as many as ``specs`` shards)."""
    devices = set(fwd.mesh.devices.flat)
    split = 0
    for leaf in fwd.arrays:
        shards = leaf.addressable_shards
        if {s.device for s in shards} != devices:
            raise SmokeFailure(f"leaf {leaf.shape} is not on every device")
        want = leaf.sharding.shard_shape(leaf.shape)
        if any(s.data.shape != want for s in shards):
            raise SmokeFailure(f"leaf {leaf.shape}: shards "
                               f"{[s.data.shape for s in shards]} != {want}")
        split += want != leaf.shape
    want_split = sum("model" in tuple(p) for p in specs.values())
    if split != want_split or not split:
        raise SmokeFailure(f"{split} leaves split over 'model', the shard "
                           f"plan says {want_split}")
    return split


def check_mesh(spec, *, seed: int) -> dict:
    """Serve the BCNN at ``spec`` on a ``(2, 2)`` mesh of the first four
    devices and on one device; the two must agree bit for bit, and the
    mesh server's packed leaves must sit on the mesh as their shardings
    say.  Returns what was measured; raises :class:`SmokeFailure`."""
    import jax

    from repro.distributed.sharding import packed_param_specs
    from repro.launch.mesh import make_mesh

    if len(jax.devices()) < 4:
        raise SmokeFailure(f"the 2x2 mesh needs 4 devices, found "
                           f"{len(jax.devices())}")
    params = build_params("bcnn", spec, seed)
    mesh = make_mesh((2, 2), ("data", "model"))
    sharded = new_server(params, spec, "bcnn", mesh=mesh)
    single = new_server(params, spec, "bcnn")
    compile_s = {name: warm_up(srv) for name, srv in
                 (("mesh", sharded), ("one device", single))}
    xs = make_inputs(sharded.engine().example_shape, SINGLES + BURST, seed)
    got = np.stack([r.result for r in serve_requests(sharded, xs)])
    want = np.stack([r.result for r in serve_requests(single, xs)])
    if not np.array_equal(got, want):
        raise SmokeFailure(f"2x2 mesh differs from one device: max |diff| "
                           f"{np.abs(got - want).max()}")
    eng = sharded.engine()
    return {"server": sharded, "x": xs[:eng.buckets[0]],
            "compile_s": compile_s, "requests": len(xs),
            "routes": check_routes(sharded),
            "split": check_placement(eng.fwd,
                                     packed_param_specs(eng.packed, mesh))}


def four_chips(seed: int) -> None:
    from repro.models import cnn

    rep = check_mesh(cnn.BCNNSpec(), seed=seed)
    srv = rep["server"]
    n_calls = check_kernels(srv.engine().fwd, rep["x"])
    for name, secs in rep["compile_s"].items():
        print(f"bcnn {name}: compile+first run " + " ".join(
            f"bucket{b}={s:.2f}s" for b, s in secs.items()))
    print(f"bcnn (2,2) mesh: {rep['requests']} requests ok, flush routes "
          f"{[(f.bucket, f.route) for f in srv.flushes]}, bit-exact vs one "
          f"device; {rep['split']} packed leaves split over 'model', each "
          f"on all 4 devices; tpu_custom_call x{n_calls}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="serve the BCNN on a 2x2 mesh of four chips and "
                         "compare it with one device (this phase only)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    from repro.utils.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache: {cache}", flush=True)
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chips(args.seed)
        else:
            one_chip(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"total {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
