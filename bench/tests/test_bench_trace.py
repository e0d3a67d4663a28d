"""Trace reduction and the per-layer readers, on a hand-written trace
in the layout of a TPU profile (``data/synthetic_trace.txtpb``: its
header gives every expected number)."""
import json
import os

import pytest

import _paths
import peaks
import run
import trace_reduce
from reference import bmlp


def _bmlp_cfg() -> dict:
    with open(os.path.join(_paths.BENCH, "configs", "bmlp.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    with open(os.path.join(_paths.DATA, "synthetic_trace.txtpb")) as f:
        return trace_reduce.reduce(ProfileData.from_text_proto(f.read()),
                                   families=_bmlp_cfg()["kernel_families"])


def test_window_and_busy(reduced):
    assert reduced.window_s == pytest.approx(11_500e-9)
    assert reduced.busy_s == pytest.approx(5_300e-9)
    assert reduced.devices == 1


def test_device_time_by_kernel(reduced):
    assert reduced.op_s["_gemv_kernel"] == pytest.approx(1_800e-9)
    assert reduced.op_s["_dense_stack_kernel"] == pytest.approx(2_000e-9)
    assert reduced.op_s["fusion"] == pytest.approx(1_700e-9)
    assert reduced.op_count == {"_gemv_kernel": 3, "_dense_stack_kernel": 1,
                                "fusion": 2}
    # the layer: its kernels and fusion.7 after a kernel in the same
    # execution; fusion.9 ran outside any execution
    assert reduced.family_s == {"dense": pytest.approx(4_500e-9),
                                "other": pytest.approx(1_000e-9)}


def test_ops_between_kernels_go_to_the_layer_around_them():
    fam = {"conv": ["_conv_kernel"], "dense": ["_gemm_kernel"]}
    ops = [("convert", 0, 1), ("fusion", 1, 2), ("_conv_kernel", 2, 5),
           ("pad", 5, 6), ("_conv_kernel", 6, 8), ("reduce-window", 8, 9),
           ("_gemm_kernel", 9, 10), ("fusion", 10, 11),
           ("copy", 20, 21),                       # between executions
           ("convert", 30, 31), ("_conv_kernel", 31, 32),
           ("fusion", 40, 41)]                     # an execution, no kernel
    modules = [(0, 11), (30, 32), (40, 41)]
    assert trace_reduce.op_families(ops, modules, fam) == [
        "conv", "conv", "conv", "conv", "conv", "conv", "dense", "dense",
        "other", "conv", "conv", "other"]


def test_idle_gaps_named_by_annotation(reduced):
    """Gaps are named by where they fall: between two executions of the
    program (the host's turn) or inside one (the device waiting)."""
    assert [(n, pytest.approx(s)) for n, s in reduced.gaps] == [
        ("inside an execution", 3_000e-9),
        ("between executions", 2_700e-9),
        ("between executions", 500e-9)]
    assert reduced.gap_s_by_kind == {
        "between executions": pytest.approx(3_200e-9),
        "inside an execution": pytest.approx(3_000e-9)}
    assert reduced.busy_s + sum(s for _, s in reduced.gaps) == \
        pytest.approx(reduced.window_s)


def test_kernel_label_from_name_or_stats():
    class E:
        def __init__(self, name, stats=()):
            self.name, self.stats = name, stats

    assert trace_reduce.op_label(E("_conv_bn_sign_kernel.3")) == \
        "_conv_bn_sign_kernel"
    assert trace_reduce.op_label(E("custom-call.4", [
        ("long_name", "custom-call.4 = tpu_custom_call(...) "
                      "kernel_name=_gemm_kernel")])) == "_gemm_kernel"
    assert trace_reduce.op_label(E("fusion.12", [("hlo_op", 3)])) == "fusion"
    assert trace_reduce.op_label(E(
        "%_bitpack_kernel.8 = u32[8,28]{1,0} custom-call(f32[8,896] %pad.61), "
        'custom_call_target="tpu_custom_call"')) == "_bitpack_kernel"
    assert trace_reduce.op_label(E(
        "%pad.20 = u32[8,256,34,34,1]{4,3,2,1,0} pad(u32[8,256,32,32,1] "
        "%copy.36, u32[] %constant.69)")) == "pad"


def test_no_device_op_in_window_raises():
    from jax.profiler import ProfileData

    txt = ('planes { id: 1 name: "/host:CPU" lines { id: 1 name: "py" '
           'timestamp_ns: 0 events { metadata_id: 1 duration_ps: 1000 } } '
           'event_metadata { key: 1 value { id: 1 name: "bench.window" } } }')
    with pytest.raises(ValueError, match="no device operation"):
        trace_reduce.reduce(ProfileData.from_text_proto(txt))


def test_readers(reduced):
    cfg = _bmlp_cfg()
    spans = [
        {"name": "serve.flush", "ts": 0.0, "dur": 3.0},
        {"name": "serve.bucket_pad", "ts": 0.0, "dur": 0.25},
        {"name": "serve.pack", "ts": 0.5, "dur": 0.25},
        {"name": "serve.dispatch", "ts": 0.75, "dur": 0.25},
        {"name": "serve.compute", "ts": 1.0, "dur": 1.5},
        {"name": "serve.complete", "ts": 2.5, "dur": 0.5},
        {"name": "serve.flush", "ts": 10.0, "dur": 5.0},
        {"name": "serve.pack", "ts": 10.0, "dur": 1.0},
        {"name": "serve.compute", "ts": 11.0, "dur": 2.0},
        {"name": "serve.complete", "ts": 13.0, "dur": 1.0},
        {"name": "serve.queue_wait", "ts": 0.0, "dur": 4.0},
        {"name": "serve.queue_wait", "ts": 9.0, "dur": 2.0},
    ]
    flushes = [{"batch": 1, "bucket": 1, "route": "gemv"},
               {"batch": 3, "bucket": 4, "route": "gemv"}]
    ctx = run.LayerContext(trace=reduced, spans=spans, flushes=flushes,
                           images=4, cfg=cfg, reference=bmlp,
                           device_kind="TPU v5 lite")

    def read(name):
        return run.metric_reader(name).read(ctx)

    assert read("server.queue_wait_ms") == pytest.approx(3e-3)
    # the host phases inside each flush, 1.25 and 2 microseconds, in ms;
    # the rest of a flush (the tracer's own records) is not counted
    assert read("host.flush_ms.interactive") == pytest.approx(1.625e-3)
    assert read("device.flush_ms.interactive") == pytest.approx(5_300e-9 / 2
                                                                * 1e3)
    assert read("device.idle_share.offline") == pytest.approx(
        100 * (1 - 5_300 / 11_500))
    least = sum(peaks.least_time(*bmlp.work(cfg, b)["dense"], "TPU v5 lite")
                for b in (1, 4))
    dense_s = reduced.family_s["dense"]
    assert read("dense_roofline.interactive") == \
        pytest.approx(100 * least / dense_s)
    assert read("conv_roofline.offline") is None      # the BMLP has no convs
    ops = 2 * 4 * 36_806_656
    assert read("mfu.interactive") == pytest.approx(
        100 * ops / 5_300e-9 / 393e12)
    assert read("mfu.offline") == pytest.approx(100 * ops / 11_500e-9
                                                / 393e12)


@pytest.mark.parametrize("flushes,share", [
    ([(64, 64), (16, 16), (1, 1)], 0.0),
    ([(20, 64)], 68.75),
    ([(20, 64), (64, 64)], 100 * 44 / 128),
    ([], None)], ids=["full", "one_20_in_64", "mixed", "none"])
def test_padded_share(flushes, share):
    ctx = run.LayerContext(
        trace=None, spans=[], cfg={}, reference=None, device_kind="",
        flushes=[{"batch": b, "bucket": k, "route": "gemm"}
                 for b, k in flushes],
        images=sum(b for b, _ in flushes))
    got = run.metric_reader("server.padded_share").read(ctx)
    assert got == (None if share is None else pytest.approx(share))


@pytest.fixture(scope="module")
def recorded():
    """A 3 s traced window of ``bmlp.interactive`` recorded on one
    TPU v5 lite (291 flushes) by ``bench/run.py --trace 1 --keep-trace
    PATH``.  The numbers below were read off the device plane's events
    directly: the ``XLA Ops`` line's first start and last end, the union
    of its intervals, and each kernel's events."""
    import gzip

    from jax.profiler import ProfileData

    path = os.path.join(_paths.DATA, "bmlp_interactive.xplane.pb.gz")
    with gzip.open(path) as f:
        return trace_reduce.reduce(ProfileData.from_serialized_xspace(f.read()),
                                   families=_bmlp_cfg()["kernel_families"])


def test_recorded_chip_trace(recorded):
    assert recorded.devices == 1
    assert recorded.window_s == pytest.approx(2_979_949_014e-9)
    assert recorded.busy_s == pytest.approx(283_612_809e-9)
    want_ns = {"_gemv_kernel": 275_508_507, "_dense_stack_kernel": 6_894_288,
               "_bitpack_kernel": 569_948, "_bn_sign_pack_kernel": 446_606}
    for kernel, ns in want_ns.items():
        assert recorded.op_s[kernel] == pytest.approx(ns * 1e-9), kernel
    assert recorded.op_count["_gemv_kernel"] == 3_201
    assert recorded.op_count["_bitpack_kernel"] == 4_656
    # the device idles mostly between flushes, while the harness waits
    # for the next arrival
    assert max(recorded.gap_s_by_kind, key=recorded.gap_s_by_kind.get) == \
        "between executions"
    assert recorded.busy_s + sum(recorded.gap_s_by_kind.values()) == \
        pytest.approx(recorded.window_s)
    # every op of the BMLP's forward is of its one family, dense
    assert sum(recorded.family_s.values()) == \
        pytest.approx(sum(recorded.op_s.values()))
    assert recorded.family_s["dense"] >= \
        0.99 * sum(recorded.op_s.values())
