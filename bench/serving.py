"""The benchmark's only contact with the program under test: build a
``PackedInferenceServer`` for a configuration and a traffic mix, and
warm it.  Everything here is generic over configurations: the program
spec is built from the configuration file's fields."""
from __future__ import annotations

import dataclasses
import time

import numpy as np


def program_spec(cfg: dict):
    """The program's spec dataclass named in ``cfg["program"]["spec"]``,
    filled from the configuration's fields of the same names (lists of
    objects become tuples of the default's element type)."""
    from repro.models import cnn

    cls = getattr(cnn, cfg["program"]["spec"])
    kw = {}
    for f in dataclasses.fields(cls):
        if f.name not in cfg:
            continue
        v = cfg[f.name]
        if isinstance(v, list):
            if v and isinstance(v[0], dict):
                elem = type(f.default[0])
                v = tuple(elem(**d) for d in v)
            else:
                v = tuple(v)
        kw[f.name] = v
    return cls(**kw)


def build_server(cfg: dict, mix: dict, params):
    """A server with the mix's queue settings, the configuration
    registered on the Pallas backend, ``perf_counter`` as its clock (so
    its completion stamps are on the benchmark's clock), and its tracer
    off until the harness turns it on."""
    from repro.telemetry import Telemetry
    from repro.train.serve import PackedInferenceServer

    s = mix["server"]
    tel = Telemetry()
    srv = PackedInferenceServer(max_batch=s["max_batch"],
                                buckets=tuple(s["buckets"]),
                                default_deadline=s["default_deadline_s"],
                                clock=time.perf_counter, telemetry=tel)
    kind = cfg["program"]["kind"]
    srv.register(kind, params, program_spec(cfg), kind=kind,
                 backend="pallas")
    return srv


def warm(srv, example: np.ndarray) -> dict[int, float]:
    """Compile (or load) and run the forward of every bucket the server
    can flush through, then send one full window of real requests
    through the queue; seconds per bucket."""
    import jax

    eng = srv.engine()
    seconds = {}
    for bucket in eng.buckets:
        t0 = time.perf_counter()
        for _ in range(2):
            x = np.broadcast_to(example, (bucket, *example.shape)).copy()
            jax.block_until_ready(eng.fwd(x))
        seconds[bucket] = time.perf_counter() - t0
    for bucket in eng.buckets:
        srv.serve([example] * bucket, deadline=0.0)
    srv.flushes.clear()
    return seconds
