"""Pallas TPU kernels for the routing-table partition (dataflow exchange).

The paper's data plane hot spot: given a chunk of record keys, the current
row-stochastic routing table (the partition function Reshape rewrites) and
per-key running counters, compute each record's destination worker and the
per-worker histogram (the workload metric phi feeding skew detection).
:func:`partition_scatter` additionally emits each record's
*within-destination rank* (its arrival index among same-destination
records) from the same VMEM-scratch running per-worker counters that
accumulate the histogram, so the host exchange can place every record at
``cumsum(hist)[dest] + rank`` with one vectorized add — the full
partition→rank→scatter pipeline in a single kernel pass, no host sort.
:func:`partition_scatter_fold` goes one stage further for the
device-resident exchange plane (:mod:`repro.dataflow.device`): the same
pass also accumulates the downstream GroupByAgg bincount fold (per-key
record counts + val sums) in VMEM scratch, with a validity mask so the
plane's padded, masked chunks never perturb ranks, histogram or fold.
The row-state edges of that plane (HashJoinBuild / RangeSort ingests
under ``device_use_kernel=True``) reuse the same kernel: dest/rank/hist
drive the ring scatter and the per-key count column doubles as the
chunk's key-arrival stats fold, so a monitored build/sort edge pays no
separate stats pass.

TPU adaptation of a hash-exchange: records lie along the lanes (every
per-record vector is a lane-dense ``[1, bn]`` row) and nothing is
gathered — Mosaic has no vector gather from VMEM.  Each record's CDF row
is *selected* on the MXU instead: a key one-hot ``[K, bn]`` times the
transposed table ``[W, K]``.  The float32 table is split host-side into
three bfloat16 parts (``hi + mid + lo == cdf`` exactly), so each product
picks one bfloat16 value per cell exactly and the float32 sum
``(hi + mid) + lo`` rebuilds the row bit for bit, whatever precision the
matmul unit runs at.  Destinations are then a ``u >= row`` compare summed
over the W sublanes (VPU), the histogram a lane sum of the destination
one-hot, and the within-destination rank an exact 0/1 matmul of that
one-hot with a strictly-upper-triangular ``[bn, bn]`` matrix (integer
counts below 2**24 are exact in float32).  Grid tiles the record stream;
the table parts stay resident in VMEM; the histogram (and fold)
accumulate in VMEM scratch across the grid.  The resident ``[W, K]`` and
``[K, bn]`` tiles bound K to the dense key spaces the device plane wires.

The low-discrepancy threshold is the *fixed-point* golden-ratio Weyl
sequence of :mod:`repro.core.partitioner` — 32-bit wrapping integer
arithmetic whose top 24 bits convert to float32 losslessly — and the CDF is
taken as a float32 input (the host computes it once per table version), so
kernel destinations are bit-identical to the numpy exchange backend.

Chunks of arbitrary length are padded internally to a multiple of a
128-lane block; padded lanes are masked out of the histogram and sliced
off the returned destinations.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.ops import ld_thresholds, saturated_cdf32

_LANES = 128


def default_interpret() -> bool:
    """Interpret the kernels everywhere but on a TPU backend."""
    return jax.default_backend() != "tpu"


def _split_cdf(cdf: jnp.ndarray) -> Tuple[jnp.ndarray, ...]:
    """``[K, W]`` float32 CDF -> three ``[W, K]`` bfloat16 parts with
    ``(hi + mid) + lo == cdf.T`` exactly in float32 (each rounding
    remainder is exact, and the last one fits bfloat16's 8 bits)."""
    x = cdf.astype(jnp.float32).T
    hi = x.astype(jnp.bfloat16)
    r = x - hi.astype(jnp.float32)
    mid = r.astype(jnp.bfloat16)
    lo = (r - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _route(keys, counters, hi_ref, mid_ref, lo_ref, *, n_workers: int):
    """One block's destinations ``[1, bn]`` and key one-hot ``[K, bn]``."""
    n_keys = hi_ref.shape[1]
    bn = keys.shape[1]
    u = ld_thresholds(counters)                          # [1, bn] in [0, 1)
    keyhot = keys == jax.lax.broadcasted_iota(jnp.int32, (n_keys, bn), 0)
    kh = keyhot.astype(jnp.bfloat16)

    def pick(ref):
        return jnp.dot(ref[...], kh, preferred_element_type=jnp.float32)

    rows = (pick(hi_ref) + pick(mid_ref)) + pick(lo_ref)  # [W, bn] cdf[keys]
    dest = jnp.sum((u >= rows).astype(jnp.int32), axis=0, keepdims=True,
                   dtype=jnp.int32)
    return jnp.minimum(dest, n_workers - 1), keyhot


def _dest_onehot(dest, live, n_workers: int):
    """Live lanes' destination one-hot ``[W, bn]`` (int32)."""
    wid = jax.lax.broadcasted_iota(jnp.int32, (n_workers, dest.shape[1]), 0)
    return ((dest == wid) & live).astype(jnp.int32)


def _rank(onehot, hist_acc):
    """Within-destination ranks ``[1, bn]`` (running VMEM counters carried
    in from earlier blocks + exclusive in-block prefix) and the counter
    advance; the prefix is an exact 0/1 matmul with ``j < b``."""
    bn = onehot.shape[1]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 0)
           < jax.lax.broadcasted_iota(jnp.int32, (bn, bn), 1))
    before = jnp.dot(onehot.astype(jnp.bfloat16), tri.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32).astype(jnp.int32)
    prev = hist_acc[...]                                 # [W, 1]
    rank = jnp.sum((before + prev) * onehot, axis=0, keepdims=True,
                   dtype=jnp.int32)
    hist_acc[...] = prev + jnp.sum(onehot, axis=1, keepdims=True,
                                   dtype=jnp.int32)
    return rank


def _lane_index(i, bn: int):
    return i * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)


def _partition_kernel(keys_ref, counters_ref, hi_ref, mid_ref, lo_ref,
                      dest_ref, hist_ref, hist_acc, *, bn: int,
                      n_workers: int, n_blocks: int, n_valid: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        hist_acc[...] = jnp.zeros_like(hist_acc)

    dest, _ = _route(keys_ref[...], counters_ref[...], hi_ref, mid_ref,
                     lo_ref, n_workers=n_workers)
    dest_ref[...] = dest
    # Mask padded lanes (global index >= n_valid) out of the histogram.
    onehot = _dest_onehot(dest, _lane_index(i, bn) < n_valid, n_workers)
    hist_acc[...] += jnp.sum(onehot, axis=1, keepdims=True, dtype=jnp.int32)

    @pl.when(i == n_blocks - 1)
    def _finish():
        hist_ref[...] = hist_acc[...]


def _partition_scatter_kernel(keys_ref, counters_ref, hi_ref, mid_ref,
                              lo_ref, dest_ref, rank_ref, hist_ref, hist_acc,
                              *, bn: int, n_workers: int, n_blocks: int,
                              n_valid: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        hist_acc[...] = jnp.zeros_like(hist_acc)

    dest, _ = _route(keys_ref[...], counters_ref[...], hi_ref, mid_ref,
                     lo_ref, n_workers=n_workers)
    dest_ref[...] = dest
    # Padded lanes advance neither the histogram nor any later rank.
    onehot = _dest_onehot(dest, _lane_index(i, bn) < n_valid, n_workers)
    rank_ref[...] = _rank(onehot, hist_acc)

    @pl.when(i == n_blocks - 1)
    def _finish():
        hist_ref[...] = hist_acc[...]


def _partition_scatter_fold_kernel(keys_ref, counters_ref, vals_ref,
                                   valid_ref, hi_ref, mid_ref, lo_ref,
                                   dest_ref, rank_ref, hist_ref, cnt_ref,
                                   sum_ref, hist_acc, cnt_acc, sum_acc, *,
                                   bn: int, n_workers: int, n_blocks: int,
                                   n_valid: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        hist_acc[...] = jnp.zeros_like(hist_acc)
        cnt_acc[...] = jnp.zeros_like(cnt_acc)
        sum_acc[...] = jnp.zeros_like(sum_acc)

    dest, keyhot = _route(keys_ref[...], counters_ref[...], hi_ref, mid_ref,
                          lo_ref, n_workers=n_workers)
    dest_ref[...] = dest
    # A lane is live iff the caller's validity mask is set *and* it is not
    # suffix padding; dead lanes advance neither ranks nor any fold.
    live = (valid_ref[...] != 0) & (_lane_index(i, bn) < n_valid)  # [1, bn]
    rank_ref[...] = _rank(_dest_onehot(dest, live, n_workers), hist_acc)
    # Downstream GroupByAgg bincount fold, fused into the same pass: the
    # chunk's per-key record counts and val sums (live lanes only).
    keyhot = keyhot & live                               # [K, bn]
    cnt_acc[...] += jnp.sum(keyhot.astype(jnp.int32), axis=1, keepdims=True,
                            dtype=jnp.int32)
    sum_acc[...] += jnp.sum(jnp.where(keyhot, vals_ref[...], 0.0), axis=1,
                            keepdims=True, dtype=jnp.float32)

    @pl.when(i == n_blocks - 1)
    def _finish():
        hist_ref[...] = hist_acc[...]
        cnt_ref[...] = cnt_acc[...]
        sum_ref[...] = sum_acc[...]


def _blocks(n: int, block_n: int) -> Tuple[int, int]:
    """(lanes per block, padded length): 128-lane multiples."""
    def up(x):
        return -(-x // _LANES) * _LANES
    bn = min(up(block_n), up(n))
    return bn, -(-n // bn) * bn


def _rows(x: jnp.ndarray, n_pad: int, dtype) -> jnp.ndarray:
    """A record column as one zero-padded lane-dense ``[1, n_pad]`` row."""
    x = x.astype(dtype)
    return jnp.pad(x, (0, n_pad - x.shape[0]))[None, :]


def _call(kernel, columns, cdf, *, n: int, bn: int, n_pad: int,
          n_out_rows: int, col_outs, scratch, interpret: Optional[bool]):
    """Shared pallas_call plumbing: record columns stream in ``[1, bn]``
    blocks, the CDF parts stay resident, ``n_out_rows`` per-record outputs
    stream out, and the ``[R, 1]`` column outputs (histogram, folds) are
    written once from VMEM scratch."""
    W, K = cdf[0].shape
    row = pl.BlockSpec((1, bn), lambda i: (0, i))
    table = pl.BlockSpec((W, K), lambda i: (0, 0))
    if interpret is None:
        interpret = default_interpret()
    call = pl.pallas_call(
        functools.partial(kernel, bn=bn, n_workers=W, n_blocks=n_pad // bn,
                          n_valid=n),
        grid=(n_pad // bn,),
        in_specs=[row] * len(columns) + [table] * 3,
        out_specs=([row] * n_out_rows
                   + [pl.BlockSpec((r, 1), lambda i: (0, 0))
                      for r, _ in col_outs]),
        out_shape=([jax.ShapeDtypeStruct((1, n_pad), jnp.int32)] * n_out_rows
                   + [jax.ShapeDtypeStruct((r, 1), dt) for r, dt in col_outs]),
        scratch_shapes=[pltpu.VMEM((r, 1), dt) for r, dt in scratch],
        interpret=interpret,
    )
    # The kernels are 32-bit programs: traced under a caller's x64 scope,
    # the index maps' literal block indices would turn int64, which
    # Mosaic refuses.
    with jax.enable_x64(False):
        return call(*columns, *cdf)


def partition(
    keys: jnp.ndarray,              # [N] int32
    counters: jnp.ndarray,          # [N] int32 per-key running index
    weights: jnp.ndarray,           # [K, W] row-stochastic routing table
    *,
    cdf: Optional[jnp.ndarray] = None,   # [K, W] float32 row-CDF override
    block_n: int = 512,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (dest [N] int32, histogram [W] int32).

    ``cdf`` lets the caller supply the host-computed float32 row-CDF
    (``RoutingTable.cdf32``) so host and device rounding agree bit-exactly;
    by default it is derived from ``weights`` here.  ``N`` may be any
    length — the chunk is padded to a block multiple internally and padded
    records never reach the histogram.  ``interpret=None`` interprets off
    TPU only.
    """
    N = keys.shape[0]
    K, W = weights.shape
    if cdf is None:
        cdf = saturated_cdf32(weights)
    if N == 0:
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((W,), jnp.int32))
    bn, n_pad = _blocks(N, block_n)
    cols = [_rows(keys, n_pad, jnp.int32), _rows(counters, n_pad, jnp.int32)]
    dest, hist = _call(_partition_kernel, cols, _split_cdf(cdf), n=N, bn=bn,
                       n_pad=n_pad, n_out_rows=1,
                       col_outs=[(W, jnp.int32)], scratch=[(W, jnp.int32)],
                       interpret=interpret)
    return dest[0, :N], hist[:, 0]


def partition_scatter(
    keys: jnp.ndarray,              # [N] int32
    counters: jnp.ndarray,          # [N] int32 per-key running index
    weights: jnp.ndarray,           # [K, W] row-stochastic routing table
    *,
    cdf: Optional[jnp.ndarray] = None,   # [K, W] float32 row-CDF override
    block_n: int = 512,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fused exchange: (dest [N], rank [N], histogram [W]) — all int32.

    ``rank[i]`` is record *i*'s arrival index among the chunk's records
    with the same destination (``#{j < i : dest[j] == dest[i]}``), so the
    stable destination-grouped position of record *i* is
    ``exclusive_cumsum(hist)[dest[i]] + rank[i]`` — equivalent to a stable
    sort by destination without sorting.  Destinations and histogram are
    bit-identical to :func:`partition`; padding as there.
    """
    N = keys.shape[0]
    K, W = weights.shape
    if cdf is None:
        cdf = saturated_cdf32(weights)
    if N == 0:
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32),
                jnp.zeros((W,), jnp.int32))
    bn, n_pad = _blocks(N, block_n)
    cols = [_rows(keys, n_pad, jnp.int32), _rows(counters, n_pad, jnp.int32)]
    dest, rank, hist = _call(
        _partition_scatter_kernel, cols, _split_cdf(cdf), n=N, bn=bn,
        n_pad=n_pad, n_out_rows=2, col_outs=[(W, jnp.int32)],
        scratch=[(W, jnp.int32)], interpret=interpret)
    return dest[0, :N], rank[0, :N], hist[:, 0]


def partition_scatter_fold(
    keys: jnp.ndarray,              # [N] int32
    counters: jnp.ndarray,          # [N] int32 per-key running index
    vals: jnp.ndarray,              # [N] float32 payload column
    weights: jnp.ndarray,           # [K, W] row-stochastic routing table
    *,
    valid: Optional[jnp.ndarray] = None,  # [N] mask (None = all live)
    cdf: Optional[jnp.ndarray] = None,    # [K, W] float32 row-CDF override
    block_n: int = 512,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Fully fused exchange + downstream fold, one kernel pass.

    Returns ``(dest [N] i32, rank [N] i32, hist [W] i32,
    fold_counts [K] i32, fold_sums [K] f32)``: the :func:`partition_scatter`
    outputs plus the chunk's per-key GroupByAgg bincount fold (record count
    and val sum per key), accumulated in the same VMEM scratch sweep that
    builds the histogram — the device-resident exchange plane's streaming
    fast path, where a chunk is partitioned, placed *and* folded into
    keyed aggregates in a single dispatch with no host round-trip.

    ``valid`` marks live lanes (the device plane carries padded, masked
    chunks between fused operators); dead lanes still get a destination
    (garbage, unread) but advance neither ranks, histogram nor fold.
    Per-key fold rather than per-(worker, key): under owner routing a
    key's records all land on its owner, so ``fold[k]`` *is* worker
    ``owner[k]``'s fold — the general budget-gated/scattered form lives
    in the jnp step of :mod:`repro.dataflow.device`.
    """
    N = keys.shape[0]
    K, W = weights.shape
    if cdf is None:
        cdf = saturated_cdf32(weights)
    if N == 0:
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32),
                jnp.zeros((W,), jnp.int32), jnp.zeros((K,), jnp.int32),
                jnp.zeros((K,), jnp.float32))
    if valid is None:
        valid = jnp.ones((N,), jnp.int32)
    bn, n_pad = _blocks(N, block_n)
    cols = [_rows(keys, n_pad, jnp.int32), _rows(counters, n_pad, jnp.int32),
            _rows(vals, n_pad, jnp.float32), _rows(valid, n_pad, jnp.int32)]
    outs = [(W, jnp.int32), (K, jnp.int32), (K, jnp.float32)]
    dest, rank, hist, cnt, sm = _call(
        _partition_scatter_fold_kernel, cols, _split_cdf(cdf), n=N, bn=bn,
        n_pad=n_pad, n_out_rows=2, col_outs=outs, scratch=outs,
        interpret=interpret)
    return dest[0, :N], rank[0, :N], hist[:, 0], cnt[:, 0], sm[:, 0]
