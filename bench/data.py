"""Seeded data generators and result arithmetic of the benchmark.

The tweet stream, the range arithmetic and ``convergence_tick`` are copied
from the engine's ``dataflow/datasets.py`` and ``dataflow/metrics.py`` so
that a change to the program cannot move the yardstick; the generators
take the run's seed, and the §7.2 per-location counts are as in the
engine's copy.  The order prices follow TPC-H's dbgen, not the engine's
generator.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

# --------------------------------------------------------------------- #
# Tweets (W1, paper §7.2)                                                #
# --------------------------------------------------------------------- #
NUM_LOCATIONS = 56
CA, TX, IL, AZ, WV = 6, 48, 17, 4, 54


def tweet_counts(scale: float = 1.0) -> np.ndarray:
    """Per-location tweet counts; the paper's ratios, CA = 26,000 at 1.0."""
    rng = np.random.default_rng(7)
    counts = np.maximum((rng.zipf(1.7, NUM_LOCATIONS) * 40).astype(np.int64), 120)
    counts = np.minimum(counts, 2_400)
    counts[CA] = 26_000
    counts[TX] = 20_000
    counts[IL] = round(26_000 / 4.05)      # 6,420
    counts[AZ] = round(26_000 / 6.85)      # 3,796
    counts[WV] = 600                        # the small key sharing CA's worker
    return np.maximum((counts * scale).astype(np.int64), 1)


def tweets_stream(scale: float, order_seed: int,
                  value_seed: int) -> Tuple[np.ndarray, np.ndarray]:
    """(location, value) stream: the same per-location counts always,
    shuffled by ``order_seed``, with values uniform on [0, 1) drawn from
    ``value_seed``."""
    counts = tweet_counts(scale)
    keys = np.repeat(np.arange(NUM_LOCATIONS, dtype=np.int64), counts)
    np.random.default_rng(order_seed).shuffle(keys)
    vals = np.random.default_rng(value_seed).random(keys.size)
    return keys, vals


# --------------------------------------------------------------------- #
# TPC-H orders (W3, paper §7.10)                                         #
# --------------------------------------------------------------------- #
def tpch_orders(n: int, seed: int, scale_factor: float = 1.0) -> np.ndarray:
    """``o_totalprice`` of ``n`` orders by dbgen's formula (TPC-H spec
    4.2.3): each order has 1-7 lineitems; a lineitem's part key is uniform
    on [1, SF * 200,000], its quantity on [1, 50], its discount on
    [0.00, 0.10] and its tax on [0.00, 0.08]; its extended price is the
    quantity times the part's retail price, (90,000 + (partkey / 10) mod
    20,001 + 100 * (partkey mod 1,000)) / 100; the order's total is the sum
    of extendedprice * (1 - discount) * (1 + tax) over its lineitems, in
    whole cents as dbgen sums it."""
    rng = np.random.default_rng(seed)
    lines = rng.integers(1, 8, n)
    total = int(lines.sum())
    part = rng.integers(1, int(scale_factor * 200_000) + 1, total)
    quantity = rng.integers(1, 51, total)
    discount = rng.integers(0, 11, total)             # percent
    tax = rng.integers(0, 9, total)                   # percent
    retail = 90_000 + (part // 10) % 20_001 + 100 * (part % 1_000)   # cents
    cents = quantity * retail * (100 - discount) // 100 * (100 + tax) // 100
    order = np.repeat(np.arange(n), lines)
    return np.bincount(order, weights=cents, minlength=n) / 100.0


def price_ranges(num_ranges: int, lo: float, hi: float) -> np.ndarray:
    """Equal-width range boundaries (the naive partitioner that skews)."""
    return np.linspace(lo, hi, num_ranges + 1)[1:-1]


def range_ids(vals: np.ndarray, bounds: np.ndarray) -> np.ndarray:
    return np.searchsorted(bounds, vals).astype(np.int64)


# --------------------------------------------------------------------- #
# Representativeness (paper §7.2)                                        #
# --------------------------------------------------------------------- #
def convergence_tick(series: Sequence[Tuple[int, np.ndarray]], key_a: int,
                     key_b: int, actual: float,
                     tol: float = 0.10) -> Optional[int]:
    """First snapshot tick from which the visible ``a/b`` ratio stays
    within ``tol`` of the actual ratio (the paper's 'reached the actual
    ratio' moment); None if it never settles."""
    good_from: Optional[int] = None
    for tick, counts in series:
        if counts[key_b] <= 0:
            continue
        if abs(counts[key_a] / counts[key_b] - actual) <= tol * actual:
            if good_from is None:
                good_from = tick
        else:
            good_from = None
    return good_from
