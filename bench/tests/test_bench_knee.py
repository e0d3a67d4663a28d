"""The knee sweep: its rule on made-up windows, one short sweep of two
tiny rates through a real server on the CPU at a small size, and the
lateness that tells the open loop's own stalls from waits for a flush."""
import json
import os
import types

import pytest

import _paths
import knee
import run

SEED = 2**33 + 91


def _fake(capacity: float):
    """Windows whose p95 crosses the limit above ``capacity``."""
    def measure(rate, seed):
        return {"rate_per_s": rate, "seed": seed, "failed": 0,
                "p95_ms": 5.0 if rate <= capacity else 40.0}
    return measure


@pytest.mark.parametrize("capacity,want,failing", [
    (360.0, 351.5625, 2), (330.0, 312.5, 4), (100.0, None, 2)],
    ids=["bisect_holds", "bisect_fails", "start_fails"])
def test_sweep_rule(capacity, want, failing):
    """200, 250, 312.5 hold, 390.625 fails, then 351.5625 is tried."""
    rows, got = knee.sweep(_fake(capacity), start=200.0,
                           seeds=[1, 2, 3])
    assert got == want
    # every seed of a rate that holds; a failing rate stops at its first
    # seed, which fails twice
    held = [r for r in rows if r["p95_ms"] <= knee.LIMIT_MS]
    assert len(held) % 3 == 0
    assert all(r["rate_per_s"] <= capacity for r in held)
    assert len(rows) - len(held) == failing
    bad = [(r["rate_per_s"], r["seed"]) for r in rows if r not in held]
    assert all(bad.count(b) == 2 and b[1] == 1 for b in bad)


def test_sweep_fails_a_rate_on_any_failed_request():
    def measure(rate, seed):
        return {"rate_per_s": rate, "seed": seed, "p95_ms": 1.0,
                "failed": int(rate > 250)}
    _, got = knee.sweep(measure, start=200.0, seeds=[1])
    assert got == 250.0


def test_sweep_reruns_a_window_that_fails_once():
    """A stall that one window meets and the rerun of its seed does not
    leaves the rate holding; the knee is set where failures repeat."""
    calls = []

    def measure(rate, seed):
        calls.append((rate, seed))
        stalled = (rate, seed) == (250.0, 2) and calls.count((rate, seed)) == 1
        return {"rate_per_s": rate, "seed": seed, "failed": 0,
                "p95_ms": 400.0 if stalled or rate > 300 else 5.0}
    rows, got = knee.sweep(measure, start=200.0, seeds=[1, 2, 3])
    assert got == 281.25
    assert calls.count((250.0, 2)) == 2 and calls.count((250.0, 3)) == 1


def test_two_tiny_rates_on_bcnn_small():
    _, cell, _, mix = knee.load("bcnn", "serve-knee")
    with open(os.path.join(_paths.DATA, "bcnn_small.json")) as f:
        cfg = json.load(f)
    ready = run.setup(cell, cfg, mix, require_tpu=False)
    shape = tuple(cfg["input_shape"])
    rows, got = knee.sweep(
        lambda rate, seed: knee.probe(ready.srv, mix, shape, rate, 1.0, seed),
        start=16.0, seeds=[SEED], limit_ms=1e6, max_rates=2)
    assert [r["rate_per_s"] for r in rows] == [16.0, 20.0] and got == 20.0
    for r in rows:
        assert r["attempted"] == round(r["rate_per_s"]) and r["failed"] == 0
        assert 0 < r["p50_ms"] <= r["p95_ms"] <= r["p99_ms"]
        assert r["flushes"] >= 1 and 1 <= r["rows_per_flush"] <= 64
        assert sum(r["flushes_by_bucket"].values()) == r["flushes"]
        assert set(r["flushes_by_bucket"]) <= {1, 4, 16, 64}
        assert 0 <= r["padded_share"] < 100


def _window(lateness, flushes):
    return run.Window(
        t0=100.0, t1=101.0, attempted=len(lateness), failed=0,
        latencies_s=[], lateness_s=lateness, images=0, sample_x=None,
        sample_y=None, spans=[],
        flushes=[types.SimpleNamespace(start_ns=round(a * 1e9), at=b)
                 for a, b in flushes])


def test_lateness_outside_flushes():
    """Requests due while a flush ran waited for it; the others' lateness
    is the open loop's own."""
    late = [(0.05, 0.001), (0.15, 0.05), (0.3, 0.002), (0.55, 0.04),
            (0.7, 0.7)]
    w = _window(late, [(100.1, 100.2), (100.5, 100.6)])
    assert run.lateness_outside_flushes(w) == [0.001, 0.002, 0.7]
    assert run.lateness_outside_flushes(_window(late, [])) == \
        [x for _, x in late]
    assert run.lateness_outside_flushes(_window([], [])) == []
