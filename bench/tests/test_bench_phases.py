"""Flush phases matched to device executions (``phases.py``) and the
runtime readers, on the chip trace recorded with host events
(``data/bmlp_interactive.xplane.pb.gz``: its host plane stands in for
the server's stamps) and on the hand-written ``synthetic_trace.txtpb``."""
import collections
import gzip
import os
import types

import pytest

import _paths
import phases
import run
import trace_reduce

N = 291            # flushes (and executions) in the recorded trace


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    with gzip.open(os.path.join(_paths.DATA,
                                "bmlp_interactive.xplane.pb.gz")) as f:
        return ProfileData.from_serialized_xspace(f.read())


@pytest.fixture(scope="module")
def host(recorded):
    """The host plane's events by name, (start, end) in order."""
    ev = collections.defaultdict(list)
    for plane in recorded.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    ev[e.name].append((float(e.start_ns),
                                       float(e.start_ns + e.duration_ns)))
    return {k: sorted(v) for k, v in ev.items()}


@pytest.fixture(scope="module")
def modules(recorded):
    return sorted((float(e.start_ns), float(e.start_ns + e.duration_ns))
                  for p in recorded.planes if p.name.startswith("/device:")
                  for line in p.lines if line.name == "XLA Modules"
                  for e in line.events)


def _stand_ins(host, ready_event):
    """Stamps from the host plane: the harness's step brackets the
    flush, the execute call's linkage is the dispatch, ``ready_event``'s
    end is ready and ``np.asarray``'s end is on host."""
    out = []
    for i in range(N):
        step, call = host["bench.step"][i], \
            host["PJRT_LoadedExecutable_Execute linkage"][i]
        asarray = host["np.asarray(jax.Array)"][i]
        ready = host[ready_event][i][1] if ready_event else asarray[1]
        out.append({"batch": 1, "start_ns": step[0], "popped_ns": call[0],
                    "routed_ns": call[0], "packed_ns": call[0],
                    "called_ns": call[0], "dispatched_ns": call[1],
                    "ready_ns": ready, "on_host_ns": asarray[1],
                    "done_ns": step[1], "compiles": 0})
    return out


def test_every_flush_matches_an_execution(recorded, host, modules):
    assert len(modules) == N
    for name in ("PJRT_LoadedExecutable_Execute linkage",
                 "np.asarray(jax.Array)", "bench.step"):
        assert len(host[name]) == N, name
    ph, why = phases.analyse(recorded, _stand_ins(host, None))
    assert why == "" and ph.flushes == ph.executions == N
    assert ph.note == "1 execution(s) per flush"
    assert ph.admitted == 1.0


def test_offset_bracket(recorded, host):
    """Each execution starts after its execute call and ends before its
    ``np.asarray`` returns: the device clock runs 1.000-1.937 ms behind
    the host's, not in step with it."""
    ph, _ = phases.analyse(recorded, _stand_ins(host, None))
    lo, hi = ph.bracket_ms
    assert lo == pytest.approx(1.000, abs=5e-4)
    assert hi == pytest.approx(1.937, abs=5e-4)
    assert ph.offset_ms == pytest.approx((lo + hi) / 2)


def test_readers_agree_with_hand_counts(recorded, host, modules):
    """With the runtime's execution-done event as ready: wait, readback
    and the idle they cause, by hand from the events, against the
    per-flush analysis (means) and the per-layer readers (medians)."""
    from repro.train.serve import STAMPS

    fl = _stand_ins(host, "tpu::System::Execute=>Done")
    for f in fl:
        assert [f[k] for k in STAMPS] == sorted(f[k] for k in STAMPS)
    wait = [f["ready_ns"] - f["dispatched_ns"] - (me - ms)
            for f, (ms, me) in zip(fl, modules)]
    readback = [f["on_host_ns"] - f["ready_ns"] for f in fl]
    want_wait_ms = sum(wait) / N * 1e-6
    want_readback_ms = sum(readback) / N * 1e-6
    # the readers take the device's mean time per flush off each wait
    device_mean = sum(me - ms for ms, me in modules) / N
    by_mean = sorted(f["ready_ns"] - f["dispatched_ns"] - device_mean
                     for f in fl)
    assert N % 2 == 1
    want_wait_median_ms = by_mean[N // 2] * 1e-6
    want_readback_median_ms = sorted(readback)[N // 2] * 1e-6

    ph, _ = phases.analyse(recorded, fl)
    assert ph.mean("wait") == pytest.approx(want_wait_ms, rel=1e-9)
    assert ph.mean("readback") == pytest.approx(want_readback_ms, rel=1e-9)
    for r in ph.rows:                       # launch + notice = wait
        assert r["launch"] + r["notice"] == pytest.approx(r["wait"])
        assert r["bucket_pad"] + r["routing"] + r["pack"] + \
            r["dispatch"] + r["wait"] + r["device"] + r["readback"] + \
            r["complete"] == pytest.approx(r["wall"])

    red = trace_reduce.reduce(recorded)
    ctx = types.SimpleNamespace(trace=red, flushes=fl)

    def read(name):
        return run.metric_reader(name).read(ctx)

    # the readers' device time is busy plus idle inside executions,
    # which is the executions' extent to within the ops outside them
    assert read("runtime.wait_ms.interactive") == \
        pytest.approx(want_wait_median_ms, rel=1e-4)
    assert read("runtime.readback_ms.interactive") == \
        pytest.approx(want_readback_median_ms, rel=1e-9)
    assert read("device.idle_in_runtime.offline") == pytest.approx(
        100 * (sum(wait) + sum(readback)) * 1e-9 / red.window_s, rel=1e-4)


NAMES = ("runtime.wait_ms.interactive", "runtime.readback_ms.interactive",
         "device.idle_in_runtime.offline")


@pytest.mark.parametrize("name", NAMES)
def test_readers_none_without_a_match(recorded, host, name, capsys):
    """None, with the reason on stderr, where the records carry no
    stamps (a server that predates them), no ready stamp (neither a
    profiler trace nor the server's tracer was on), or account for less
    device time than the trace holds (fewer flushes than executions)."""
    red = trace_reduce.reduce(recorded)
    reader = run.metric_reader(name)
    bare = [{"batch": 1, "bucket": 1, "route": "gemv"}] * N
    assert reader.read(types.SimpleNamespace(trace=red, flushes=bare)) \
        is None
    assert "no phase stamps" in capsys.readouterr().err
    unready = [dict(f, ready_ns=0) for f in _stand_ins(host, None)]
    assert reader.read(types.SimpleNamespace(trace=red, flushes=unready)) \
        is None
    assert "no ready stamp" in capsys.readouterr().err
    few = _stand_ins(host, None)[:N // 3]
    assert reader.read(types.SimpleNamespace(trace=red, flushes=few)) \
        is None
    assert "executions outside the flushes" in capsys.readouterr().err


def test_analysis_none_on_count_mismatch(recorded, host):
    ph, why = phases.analyse(recorded, _stand_ins(host, None)[:-1])
    assert ph is None and "290 flushes" in why
    ph, why = phases.analyse(recorded, [{"batch": 1}] * N)
    assert ph is None and "no phase stamps" in why


# -- named gaps on the hand-written trace --------------------------------------
# Executions [2000, 8800] and [11500, 12000] (device ns); idle gaps
# [1500, 2000] and [8800, 11500] between executions, [3500, 6500] inside.
# The host's stamps run 10,000 ns ahead of the device clock.

FLUSH_1 = {"batch": 1, "start_ns": 11_000, "popped_ns": 11_400,
           "routed_ns": 11_400, "packed_ns": 11_950, "called_ns": 11_950,
           "dispatched_ns": 11_990, "ready_ns": 18_850}


@pytest.mark.parametrize("rest,second_gap", [
    # readback 18,850-20,500 takes most of the host's [18,800, 21,500]
    ({"on_host_ns": 20_500, "done_ns": 20_600}, "serve.readback"),
    # the server sits idle from 19,000 to the next flush's start at 21,000
    ({"on_host_ns": 18_900, "done_ns": 19_000}, "outside a flush"),
])
def test_gaps_named_by_phase(rest, second_gap):
    from jax.profiler import ProfileData

    with open(os.path.join(_paths.DATA, "synthetic_trace.txtpb")) as f:
        pd = ProfileData.from_text_proto(f.read())
    flush_2 = {"batch": 1, "start_ns": 21_000, "popped_ns": 21_100,
               "routed_ns": 21_100, "packed_ns": 21_200,
               "called_ns": 21_200, "dispatched_ns": 21_300,
               "ready_ns": 22_100, "on_host_ns": 22_200, "done_ns": 22_300}
    ph, why = phases.analyse(pd, [dict(FLUSH_1, **rest), flush_2])
    assert why == ""
    # flush 1 admits offsets 9,950-10,050 (called - 2,000, ready - 8,800),
    # flush 2 admits 9,700-10,100
    assert ph.bracket_ms == pytest.approx((9_950e-6, 10_050e-6))
    assert ph.offset_ms == pytest.approx(10_000e-6)
    # [1500, 2000] is host [11,500, 12,000]: 450 ns of pack, 40 of
    # dispatch, 10 of launch
    assert [(n, pytest.approx(s)) for n, s in ph.gaps] == [
        (trace_reduce.INSIDE, 3_000e-9), (second_gap, 2_700e-9),
        ("serve.pack", 500e-9)]
    red = trace_reduce.reduce(pd)
    assert sum(ph.gap_s_by_name.values()) == \
        pytest.approx(sum(red.gap_s_by_kind.values()))
    # each phase's part of the gaps, not only the largest: flush 1's
    # pack and dispatch in the first gap, flush 2's in the second
    took = {k: pytest.approx(v) for k, v in ph.idle_s_by_phase.items()}
    assert took["serve.pack"] == (450 + 100) * 1e-9
    assert took["serve.dispatch"] == (40 + 100) * 1e-9
    assert took[trace_reduce.INSIDE] == 3_000e-9
    assert sum(ph.idle_s_by_phase.values()) == \
        pytest.approx(sum(red.gap_s_by_kind.values()))


def test_name_gap_takes_the_largest_overlap():
    segs = [(0, 10, "serve.pack"), (10, 12, "serve.dispatch"),
            (12, 30, phases.LAUNCH), (30, 40, phases.EXECUTION),
            (50, 60, "serve.complete")]
    starts = [s for s, _, _ in segs]
    assert phases.name_gap(5, 11, segs, starts) == "serve.pack"
    assert phases.name_gap(11, 29, segs, starts) == phases.LAUNCH
    # between two executions of one flush: its next launch
    assert phases.name_gap(31, 39, segs, starts) == phases.LAUNCH
    assert phases.name_gap(38, 52, segs, starts) == phases.OUTSIDE
    assert phases.name_gap(55, 90, segs, starts) == phases.OUTSIDE


def test_host_flush_ms_of_the_spans_is_the_stamps():
    """The spans a traced server makes from its stamps give
    ``host.flush_ms`` exactly the stamps' host phases, at the magnitude
    of a host's ``perf_counter_ns`` (every phase span must fall inside
    its ``serve.flush`` after the ns -> us division)."""
    import numpy as np

    import readers
    from repro.models import cnn
    from repro.telemetry import Telemetry, Tracer
    from repro.train.serve import PackedInferenceServer

    steps = iter(np.random.default_rng(5).integers(300, 90_000, 10**5))
    now = [987_654_321_012_345]

    def clock():                    # a host's perf_counter_ns, irregular
        now[0] += int(next(steps))
        return now[0]

    tel = Telemetry(tracer=Tracer(enabled=True, clock_ns=clock))
    srv = PackedInferenceServer(max_batch=4, telemetry=tel)
    params, spec, kind = cnn.demo_model("bmlp", smoke=True)
    srv.register("m", params, spec, kind=kind, backend="jnp")
    x = np.zeros(srv.engine().example_shape, np.uint8)
    for n in (1, 3, 4, 2, 1, 1, 4, 3) * 8:
        srv.serve([x] * n)
    want = [((f.popped_ns - f.start_ns) + (f.packed_ns - f.routed_ns) +
             (f.dispatched_ns - f.called_ns) + (f.done_ns - f.on_host_ns))
            * 1e-6 for f in srv.flushes]
    ctx = types.SimpleNamespace(spans=[e for e in tel.tracer.events
                                       if e["ph"] == "X"])
    assert readers.host_flush_ms(ctx) == \
        pytest.approx(sum(want) / len(want), rel=1e-9)
