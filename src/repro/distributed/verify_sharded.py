"""Sharded packed-forward verifier: 8 forced CPU devices, own process.

Proves, without hardware, that ``make_sharded_forward`` is

* **bit-identical** to the single-device packed forward for every mesh
  shape (data, model) in {(8,1), (4,2), (2,4)}, for both evaluation
  networks (BCNN and BMLP), including a non-word-divisible stage that
  exercises the per-stage replication fallback, and (one cell) the
  Pallas backend in interpret mode under shard_map;
* **collective-free on the data-parallel path**: the compiled HLO of the
  (8, 1) mesh contains zero collectives;
* **packed-words-only on the model path**: sharded meshes emit only
  all-gathers (no all-reduce — the conv stack never crosses devices with
  a partial sum or an un-packed int32 activation).

Both rules come from ``analysis.collectives`` (``check_mesh``), the
shared analyzers the merged analysis report also consumes.

Usage (the CI sharding job and tests/test_sharded_forward.py run this):

    PYTHONPATH=src python -m repro.distributed.verify_sharded [--json]

NOTE: the XLA_FLAGS line below must execute before ANY other import
touches jax — keep it immediately after the docstring.
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.collectives import check_mesh, check_model_parallel
from repro.distributed import sharding as SH
from repro.launch.mesh import make_mesh
from repro.models import cnn

MESH_SHAPES = ((8, 1), (4, 2), (2, 4))
BATCH = 8

# Small nets that still hit every seam: stages word-divisible at every
# model degree here (128 % (32·4) == 0 shards the bit-plane first stage
# 4-ways; 64 % (32·2) == 0 shards only 2-ways), a stage (48, and 96 in
# the MLP) that is NOT word-divisible for model > 1 (-> replication
# fallback), a pooled sharded stage (bit-domain pool masks sharded), and
# the grouped conv->dense flatten.
BCNN_SPEC = cnn.BCNNSpec(
    input_hw=(8, 8), c_in=3,
    stages=(cnn.ConvStage(128), cnn.ConvStage(48, pool=True),
            cnn.ConvStage(64, pool=True)),
    dense=(128, 10))
BMLP_SIZES = (784, 128, 96, 10)


def _build(kind: str):
    key = jax.random.PRNGKey(0)
    if kind == "bcnn":
        params = cnn.init_bcnn(key, BCNN_SPEC)
        packed = cnn.pack_bcnn(params, BCNN_SPEC)
        x = jax.random.randint(jax.random.fold_in(key, 1),
                               (BATCH, *BCNN_SPEC.input_hw, BCNN_SPEC.c_in),
                               0, 256).astype(jnp.uint8)
        want = cnn.bcnn_forward_packed(packed, x, backend="jnp")
    else:
        spec = cnn.BMLPSpec(sizes=BMLP_SIZES)
        params = cnn.init_bmlp(key, spec)
        packed = cnn.pack_bmlp(params, spec)
        x = jax.random.randint(jax.random.fold_in(key, 1),
                               (BATCH, BMLP_SIZES[0]), 0,
                               256).astype(jnp.uint8)
        want = cnn.bmlp_forward_packed(packed, x, backend="jnp")
    return packed, x, np.asarray(want)


def run_cells(backends=("jnp",), pallas_cell: bool = True) -> list[dict]:
    assert len(jax.devices()) == 8, jax.devices()
    built = {kind: _build(kind) for kind in ("bcnn", "bmlp")}
    cells = []
    for kind in ("bcnn", "bmlp"):
        for shape in MESH_SHAPES:
            for backend in backends:
                cells.append((kind, shape, backend, *built[kind]))
    if pallas_cell:
        # Interpret-mode Pallas cells: the kernels themselves run
        # per-shard under shard_map with local C_out/batch shapes —
        # (4, 2) shards the conv stack (incl. the bit-plane stage 0 and
        # a pooled stage) AND the hidden dense stage (the fused dense
        # GEMM epilogue on word-aligned local rows), (2, 4) shards
        # stage 0 four ways.  The BMLP cell runs the single-launch
        # VMEM-resident hidden stack per shard (its 96-wide hidden
        # layer replicates at the pack seam) under a sharded first
        # layer.
        cells.append(("bcnn", (4, 2), "pallas", *built["bcnn"]))
        cells.append(("bcnn", (2, 4), "pallas", *built["bcnn"]))
        cells.append(("bmlp", (4, 2), "pallas", *built["bmlp"]))

    results = []
    for kind, shape, backend, packed, x, want in cells:
        mesh = make_mesh(shape, ("data", "model"))
        fwd = SH.make_sharded_forward(packed, mesh, backend=backend)
        t0 = time.monotonic()
        got = np.asarray(jax.block_until_ready(fwd(x)))
        t_first = time.monotonic() - t0
        t0 = time.monotonic()
        np.asarray(jax.block_until_ready(fwd(x)))
        t_steady = time.monotonic() - t0
        bitexact = bool((got == want).all())
        # Data-parallel meshes must be collective-free; model meshes may
        # emit packed-word all-gathers only (a partial sum crossing
        # chips would surface as an all-reduce).  The rules live in
        # analysis.collectives so the analysis report applies the same ones.
        coll = check_mesh(fwd.lower(x).compile().as_text(), shape)
        rec = {
            "kind": kind, "mesh": list(shape), "backend": backend,
            "bitexact": bitexact,
            "shard_plan": {k: list(v) for k, v in fwd.shard_plan.items()},
            "collective_bytes": coll.total_bytes,
            "collective_kinds": coll.kinds,
            "collective_violations": list(coll.violations),
            "fwd_first_us": t_first * 1e6, "fwd_us": t_steady * 1e6,
            "ok": bitexact and coll.ok,
        }
        results.append(rec)
    results.append(serve_cell(built))
    results.append(degrade_cell(built))
    return results


def serve_cell(built: dict) -> dict:
    """Serving-over-mesh cell: a ``PackedInferenceServer`` with the
    (4, 2) mesh behind its queue (``train/serve.py``).

    Ragged submits + a deadline flush + a second full-window flush must
    return rows bit-identical to the single-device forward, the flush
    buckets must honor the mesh's ``batch_multiple`` (= 4 here), and
    the engine's compiled HLO obeys the same all-gather-only rule as
    the bare sharded forward.
    """
    from repro.train import serve as SV

    packed, x, want = built["bcnn"]
    mesh = make_mesh((4, 2), ("data", "model"))
    clock = SV.SimClock()
    srv = SV.PackedInferenceServer(max_batch=BATCH,
                                   default_deadline=0.005, clock=clock)
    srv.register("bcnn-serve", packed=packed, backend="jnp", mesh=mesh)
    eng = srv.engine()
    assert all(b % eng.batch_multiple == 0 for b in eng.buckets), eng.buckets
    # Ragged arrivals: 5 requests ride the deadline flush (padded up to
    # the 8 bucket), the remaining 3 arrive later and flush on their own
    # deadline (bucket 4) — no head-of-line blocking either way.
    rids = [srv.submit(np.asarray(x[i])) for i in range(5)]
    assert srv.step() == []                 # deadline still in the future
    clock.advance(1.0)
    done = srv.step()
    rids += [srv.submit(np.asarray(x[i])) for i in range(5, BATCH)]
    clock.advance(1.0)
    done += srv.step()
    by = {r.rid: r.result for r in done}
    got = np.stack([by[rid] for rid in rids])
    bitexact = bool((got == np.asarray(want)).all())
    t0 = time.monotonic()
    srv.serve([np.asarray(x[i]) for i in range(BATCH)])
    t_steady = time.monotonic() - t0
    hlo = eng.fwd.lower(np.zeros((eng.buckets[-1], *eng.example_shape),
                                 np.uint8)).compile().as_text()
    coll = check_model_parallel(hlo)
    return {
        "kind": "bcnn", "mesh": [4, 2], "backend": "serve",
        "bitexact": bitexact,
        "shard_plan": {k: list(v) for k, v in eng.fwd.shard_plan.items()},
        "collective_bytes": coll.total_bytes,
        "collective_kinds": coll.kinds,
        "collective_violations": list(coll.violations),
        "fwd_first_us": t_steady * 1e6, "fwd_us": t_steady * 1e6,
        "ok": (bitexact and coll.ok
               and [f.bucket for f in srv.flushes[:2]] == [8, 4]
               and [f.route for f in srv.flushes[:2]] == ["gemv", "gemv"]),
    }


def degrade_cell(built: dict) -> dict:
    """Elastic-degradation cell: a supervised server on the (4, 2) mesh
    loses 4 of its 8 devices mid-flight (``runtime.faults`` injection).

    The ``ServingSupervisor`` must remesh onto the 4 survivors
    (``remesh_plan`` -> (2, 2)), re-place the packed weights, rebuild
    the engine UNDER the queue, and serve the requeued window — rows
    bit-identical to the single-device forward, with the shrunken
    engine's compiled HLO still obeying the all-gather-only rule
    (a degrade must not smuggle in an all-reduce).
    """
    from repro.runtime.faults import FaultInjector, FaultPlan, FaultSpec
    from repro.runtime.supervisor import ServingSupervisor
    from repro.train import serve as SV

    packed, x, want = built["bcnn"]
    clock = SV.SimClock()
    srv = SV.PackedInferenceServer(max_batch=BATCH,
                                   default_deadline=0.005, clock=clock)
    srv.register("bcnn-degrade", packed=packed, backend="jnp",
                 mesh=make_mesh((4, 2), ("data", "model")))
    sup = ServingSupervisor(srv, "bcnn-degrade", backend="jnp")
    FaultInjector(FaultPlan.of(
        FaultSpec("device_loss", survivors=4))).attach(srv)
    rids = [srv.submit(np.asarray(x[i])) for i in range(BATCH)]
    t0 = time.monotonic()
    done = sup.step()           # loss -> degrade -> requeued window served
    t_first = time.monotonic() - t0
    by = {r.rid: r for r in done}
    bitexact = (all(by[rid].status == "ok" for rid in rids) and
                bool((np.stack([by[rid].result for rid in rids])
                      == np.asarray(want)).all()))
    eng = srv.engine("bcnn-degrade")
    t0 = time.monotonic()
    srv.serve([np.asarray(x[i]) for i in range(BATCH)])
    t_steady = time.monotonic() - t0
    hlo = eng.fwd.lower(np.zeros((eng.buckets[-1], *eng.example_shape),
                                 np.uint8)).compile().as_text()
    coll = check_model_parallel(hlo)
    m = srv.telemetry.metrics
    return {
        "kind": "bcnn", "mesh": [2, 2], "backend": "degrade",
        "bitexact": bitexact,
        "shard_plan": {k: list(v) for k, v in eng.fwd.shard_plan.items()},
        "collective_bytes": coll.total_bytes,
        "collective_kinds": coll.kinds,
        "collective_violations": list(coll.violations),
        "fwd_first_us": t_first * 1e6, "fwd_us": t_steady * 1e6,
        "ok": (bitexact and coll.ok
               and sup.events[0].mesh_shape == (2, 2)
               and tuple(eng.fwd.mesh.shape.values()) == (2, 2)
               and len(eng.fwd.mesh.devices.flatten()) == 4
               and m.value("serve.degraded") == 1
               and m.value("serve.degraded_state") == 0),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output only")
    args = ap.parse_args()
    results = run_cells()
    if args.json:
        print(json.dumps(results))
    else:
        for r in results:
            print(f"{r['kind']} mesh={tuple(r['mesh'])} {r['backend']:6s} "
                  f"bitexact={r['bitexact']} "
                  f"coll={r['collective_kinds'] or 'none'} "
                  f"shards={r['shard_plan']} "
                  f"{'OK' if r['ok'] else 'FAIL'}")
    bad = [r for r in results if not r["ok"]]
    if bad:
        raise SystemExit(f"{len(bad)} sharded-forward cells failed")


if __name__ == "__main__":
    main()
