"""The load generator: seeded, fixed work, full batches."""
import hashlib

import numpy as np
import pytest

import _paths  # noqa: F401
import loadgen

BIG_SEED = 2**33 + 12345


def test_open_poisson_same_seed_same_schedule():
    a = loadgen.open_poisson(100, 20, BIG_SEED)
    b = loadgen.open_poisson(100, 20, BIG_SEED)
    np.testing.assert_array_equal(a, b)


def test_open_poisson_other_seed_other_order_same_work():
    a = loadgen.open_poisson(100, 20, BIG_SEED)
    b = loadgen.open_poisson(100, 20, BIG_SEED + 1)
    assert not np.array_equal(a, b)
    assert len(a) == len(b) == 2000
    # the same gaps, in another order (each schedule drops its last gap)
    common = np.intersect1d(np.round(np.diff(a), 8), np.round(np.diff(b), 8))
    assert len(common) >= len(a) - 2


@pytest.mark.parametrize("rate,seconds", [(100, 20), (37.5, 8)])
def test_open_poisson_mean_gap_and_window(rate, seconds):
    due = loadgen.open_poisson(rate, seconds, 7)
    assert due[0] == 0.0 and np.all(np.diff(due) > 0)
    assert due[-1] < seconds
    assert np.diff(due).mean() == pytest.approx(1 / rate, rel=0.01)
    gaps = np.diff(due)
    # exponential: the standard deviation is about the mean
    assert gaps.std() == pytest.approx(gaps.mean(), rel=0.15)


def test_closed_batch_full_batches_in_turn():
    mix = {"arrivals": "closed_batch", "batch": 256, "pool_batches": 3}
    t = loadgen.make(mix, 10, BIG_SEED, (32, 32, 3))
    gen = loadgen.closed_batch(t.pool)
    seen = [next(gen) for _ in range(7)]
    assert [k for k, _ in seen] == list(range(7))
    for k, batch in seen:
        assert batch.shape == (256, 32, 32, 3) and batch.dtype == np.uint8
        np.testing.assert_array_equal(batch, t.pool[k % 3])


def _digest(a: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()[:16]


def test_existing_kinds_draw_what_they_drew_before_open_onoff():
    """The schedules and pools of ``open_poisson`` and ``closed_batch``
    are pinned to their bytes (times rounded to the nanosecond), so that
    no change to the generator moves a cell's traffic unseen."""
    due = loadgen.open_poisson(100, 20, BIG_SEED)
    assert _digest(np.round(due, 9)) == "1b7b6fa2346c523c"
    t = loadgen.make({"arrivals": "open_poisson", "rate_per_s": 100,
                      "pool_images": 8}, 20, BIG_SEED, (784,))
    assert _digest(np.round(t.due, 9)) == "1b7b6fa2346c523c"
    assert _digest(t.pool) == "9f6deec65181ca8b"
    assert _digest(t.image_of) == "0ead4aef325ad6bb"
    t = loadgen.make({"arrivals": "closed_batch", "batch": 256,
                      "pool_batches": 3}, 10, BIG_SEED, (32, 32, 3))
    assert _digest(t.pool) == "440b925ded3bdc23"


@pytest.mark.parametrize("arrivals", ["open_poisson"])
def test_make_is_seeded(arrivals):
    mix = {"arrivals": arrivals, "rate_per_s": 100, "pool_images": 8}
    a = loadgen.make(mix, 2, BIG_SEED, (784,))
    b = loadgen.make(mix, 2, BIG_SEED, (784,))
    c = loadgen.make(mix, 2, BIG_SEED + 1, (784,))
    np.testing.assert_array_equal(a.pool, b.pool)
    np.testing.assert_array_equal(a.image_of, b.image_of)
    assert not np.array_equal(a.pool, c.pool)
