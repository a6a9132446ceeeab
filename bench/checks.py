"""The comparisons that decide ``correct``: program outputs against a plain
reference computed with numpy and the standard library alone.

Each comparison returns one number; a configuration's ``limits`` give the
largest value that still counts as correct.  Integer results compare
exactly (limit 0).  Float sums compare by their largest relative error
against ``math.fsum`` of the same values, per key.
"""
from __future__ import annotations

import math
from typing import Sequence, Tuple

import numpy as np

Series = Sequence[Tuple[int, np.ndarray]]


def ref_counts(keys: np.ndarray, num_keys: int) -> np.ndarray:
    return np.bincount(keys, minlength=num_keys).astype(np.int64)


def ref_sums(keys: np.ndarray, vals: np.ndarray, num_keys: int,
             dtype=np.float64) -> np.ndarray:
    """Per-key sums correctly rounded to ``dtype`` (fsum of the values as
    that dtype holds them)."""
    order = np.argsort(keys, kind="stable")
    bounds = np.searchsorted(keys[order], np.arange(num_keys + 1))
    v = vals[order].astype(dtype).tolist()
    return np.array([math.fsum(v[bounds[k]:bounds[k + 1]])
                     for k in range(num_keys)], dtype=dtype)


def count_mismatch(counts: np.ndarray, ref: np.ndarray) -> int:
    """Keys whose final count differs from the reference's."""
    counts = np.asarray(counts)
    if counts.shape != ref.shape:
        return int(ref.size)
    return int(np.count_nonzero(counts != ref))


def sums_rel_err(sums: np.ndarray, ref: np.ndarray) -> float:
    """Largest per-key relative error of the sums (absolute where the
    reference sum is 0)."""
    sums = np.asarray(sums, dtype=np.float64)
    if sums.shape != ref.shape:
        return math.inf
    err = np.abs(sums - ref) / np.where(ref != 0, np.abs(ref), 1.0)
    return float(np.max(err, initial=0.0))


def prefix_series_violations(series: Series, keys: np.ndarray,
                             emit_rate: int, snapshot_every: int,
                             final: np.ndarray) -> int:
    """Snapshots that no execution of a pipelined (non-blocking) plan
    could show.  The snapshot at tick ``t`` follows that tick's data pass,
    when the source has emitted its first ``(t + 1) * emit_rate`` tuples,
    so every key's visible count is at most that key's count in the
    emitted prefix.  Counts never fall and ticks never go back; a snapshot
    off the ``snapshot_every`` grid is the END one and shows the final
    counts, as does the last snapshot."""
    if not series:
        return 1
    bad = 0
    K = final.size
    prev_tick, prev = -1, np.zeros(K, dtype=np.int64)
    for tick, counts in series:
        counts = np.asarray(counts)
        if tick < prev_tick or np.any(counts < prev):
            bad += 1
        if tick % snapshot_every and not np.array_equal(counts, final):
            bad += 1
        n = min(keys.size, (tick + 1) * emit_rate)
        if np.any(counts > np.bincount(keys[:n], minlength=K)):
            bad += 1
        prev_tick, prev = tick, counts
    if not np.array_equal(series[-1][1], final):
        bad += 1
    return bad


def blocking_series_violations(series: Series, final: np.ndarray) -> int:
    """Snapshots that a blocking operator upstream of the sink cannot
    produce: the sink shows nothing until the operator's END, then the
    whole result at once; the last snapshot is the final result."""
    if not series:
        return 1
    bad = 0
    prev_tick = -1
    for tick, counts in series:
        counts = np.asarray(counts)
        if tick < prev_tick:
            bad += 1
        if counts.any() and not np.array_equal(counts, final):
            bad += 1
        prev_tick = tick
    if not np.array_equal(series[-1][1], final):
        bad += 1
    return bad
