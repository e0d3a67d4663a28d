"""The one load generator: every traffic mix is a data file under
``bench/traffic/`` that names one of the arrival kinds below and their
parameters.

* ``open_poisson`` -- independent single-image callers.  A run of
  ``seconds`` at ``rate_per_s`` holds exactly ``round(rate * seconds)``
  requests, and every seed gets the same multiset of exponential gaps
  (their quantiles), in its own order: Poisson-like arrivals whose
  total work does not change with the seed.
* ``closed_batch`` -- one client that sends its next batch of ``batch``
  images as soon as the previous one returns, cycling over
  ``pool_batches`` pre-made batches.

Images are uniform random uint8 from the seed.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator

import numpy as np


def images(rng: np.random.Generator, n: int,
           shape: tuple[int, ...]) -> np.ndarray:
    return rng.integers(0, 256, (n, *shape), dtype=np.uint8)


def open_poisson(rate_per_s: float, seconds: float, seed: int) -> np.ndarray:
    """Due times in ``[0, seconds)``, sorted, mean gap ``1 / rate``."""
    n = max(1, round(rate_per_s * seconds))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate_per_s
    np.random.default_rng([seed, 1]).shuffle(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due * (seconds / gaps.sum())


def closed_batch(pool: np.ndarray) -> Iterator[tuple[int, np.ndarray]]:
    """``(k, batch)`` for k = 0, 1, ...: the pre-made batches in turn."""
    k = 0
    while True:
        yield k, pool[k % len(pool)]
        k += 1


@dataclasses.dataclass
class Traffic:
    """What one run sends: ``due`` (seconds into the window) and the pool
    image of each request for an open loop; ``pool`` (batches) for a
    closed one."""
    arrivals: str
    pool: np.ndarray
    due: np.ndarray | None = None
    image_of: np.ndarray | None = None


def make(mix: dict, seconds: float, seed: int,
         example_shape: tuple[int, ...]) -> Traffic:
    rng = np.random.default_rng([seed, 0])
    if mix["arrivals"] == "open_poisson":
        due = open_poisson(mix["rate_per_s"], seconds, seed)
        pool = images(rng, mix["pool_images"], example_shape)
        return Traffic("open_poisson", pool, due,
                       rng.integers(0, len(pool), len(due)))
    if mix["arrivals"] == "closed_batch":
        pool = images(rng, mix["pool_batches"] * mix["batch"], example_shape)
        return Traffic("closed_batch",
                       pool.reshape(mix["pool_batches"], mix["batch"],
                                    *example_shape))
    raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
