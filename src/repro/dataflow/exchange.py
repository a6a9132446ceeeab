"""The columnar exchange subsystem: fused one-pass routing for every edge.

An :class:`Exchange` owns the data-plane side of one partitioned edge.  Per
chunk it asks its pluggable :class:`PartitionBackend` for a single fused
:class:`ScatterPlan` — destination worker per record, the per-worker
histogram (the workload metric phi), and the *scatter placement* that
groups the chunk by destination — then materializes each worker's
contiguous slice with one fancy-index pass per column.  There is no
separate sort stage: the plan's placement is produced by the partition
itself (Pallas: in-kernel running per-worker counters; numpy: a two-pass
counting permutation, with identity fast paths when the chunk is already
grouped).

Scatter-plan protocol
---------------------
``ScatterPlan`` carries exactly one of three placements, applied by
:meth:`ScatterPlan.take`:

``order=None, pos=None``  identity — the chunk is already destination-
                          grouped (single live destination; e.g. every
                          edge into a 1-worker Sink).  ``take`` returns
                          the input array untouched: zero copies.
``order``                 gather indices: ``grouped = arr[order]``.
``pos``                   scatter slots ``bounds[dest] + rank`` (rank =
                          within-destination arrival index, the fused
                          counting-scatter form): ``grouped[pos] = arr``.

All three are *stable*: each worker receives its records in stream
(arrival) order, bit-identical to a stable ``argsort(dest)`` — the
contract that keeps per-worker FIFO replay and the fairness of initial
results (paper §4) identical across backends and the reference plane.

Backends
--------
``numpy``   (default) the host plane: ``RoutingTable.advance_counters``
            + the canonical fixed-point inverse-CDF rule, pure numpy.
            Its grouping permutation comes from :func:`scatter_order`:
            numpy's stable integer argsort on the int16-cast
            destinations, which for small integers *is* a two-pass
            counting (radix) scatter — O(n + W), not a comparison sort.
            Past ``MAX_RADIX_WORKERS`` a full-width stable argsort keeps
            correctness (one-time RuntimeWarning: it is a comparison
            sort again).
``pallas``  the device-resident plane.  Per *eligible* edge — a
            single-upstream destination from the full paper operator
            set: Filter / Project / GroupByAgg / Sink plus the
            row-state HashJoinBuild / HashJoinProbe / RangeSort — the
            engine promotes the whole edge into
            :mod:`repro.dataflow.device`: chunks, ring queues, the
            float32 row-CDF, per-key split counters and the downstream
            keyed state live as ``jnp`` arrays across a ``batch_ticks``
            super-tick, advanced by one persistent jitted step (donated
            buffers) that fuses partition → within-destination rank →
            ring scatter → budgeted pop → a kind-specific tail in a
            single dispatch per edge: a vectorized keyed fold (GroupBy /
            Sink), a stateless map (Filter / Project), a segment append
            into a device row store mirroring ``ScopeRows`` with
            owned/scattered flags and amortized doubling (build / sort),
            or a capacity-bounded probe expansion emitting each record
            ``match_count`` times as a padded masked DeviceChunk
            (HashJoinProbe; the build side is a dense [W, K] match-count
            table summing owned + scattered rows).  The host reads back
            only O(num_workers) control metrics per dispatch and
            materializes state at the boundaries
            ``Engine._fusible_ticks`` already computes (sink snapshots,
            controller metric rounds, checkpoints, END, rewrites).
            Consecutive jit edges whose RoutingTables are provably
            routing-equivalent (``RoutingTable.routing_token``: one-hot
            tables over the same key space with identical
            primaries/owners) additionally fuse into a *chain*: the
            whole Filter/Project/Probe → … → GroupBy/Sink/Build/Sort
            run advances in one dispatch per super-tick sharing the
            head edge's placement (a probe chains like a map stage — it
            repeats records without re-keying), falling back per-edge
            the moment a rewrite voids the token
            (``Engine(device_chain=False)`` / ``REPRO_DEVICE_CHAIN=0``
            disables).  On TPU the partition core is the fused Pallas
            :func:`repro.kernels.partition.partition_scatter` /
            ``partition_scatter_fold`` kernel; off TPU the plane runs
            its validation twin (``Engine(device_executor=...)`` /
            ``REPRO_DEVICE_EXECUTOR``: ``"jit"`` forces the jitted step
            through XLA/interpret for correctness runs, ``"host"`` — the
            off-TPU default — executes the identical canonical rule via
            the fused numpy exchange, which the backend-equivalence
            suite proves bit-identical).  Ineligible edges — a second
            upstream, 2-D payloads, a probe whose worst-case fanout
            would blow the emit buffer — fall back to this per-chunk
            :class:`PallasPartitionBackend`, whose ``partition_scatter``
            kernel emits each record's within-destination rank so the
            host does no sort.

Both planes route through the same per-key counters owned by the edge's
``RoutingTable`` (device-resident counters are materialized on demand via
``RoutingTable.sync_counters``), so backends can be swapped mid-run — or
compared record for record — without perturbing the low-discrepancy
sequence.

Select a backend per engine (``Engine(partition_backend=...)``), per edge,
or globally via the ``REPRO_PARTITION_BACKEND`` environment variable.
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from typing import Optional, Tuple, Union

import numpy as np

from ..core.partitioner import RoutingTable
from .tuples import Chunk

#: Largest worker count the int16 radix cast in :func:`scatter_order` can
#: represent; beyond it the cast would wrap around silently and scatter
#: records to the wrong workers.
MAX_RADIX_WORKERS = int(np.iinfo(np.int16).max)


#: set once the first wide (> MAX_RADIX_WORKERS) fallback has warned.
_WARNED_WIDE_FALLBACK = False


def scatter_order(dest: np.ndarray, hist: np.ndarray) -> Optional[np.ndarray]:
    """Stable counting-scatter permutation grouping ``dest`` by worker.

    Returns gather indices ``order`` such that ``dest[order]`` is
    non-decreasing with equal destinations kept in arrival order, or
    ``None`` when the chunk is already grouped (at most one destination
    received records — the identity fast path, which makes every edge
    into a single-worker operator sort-free and copy-free).

    The general path is numpy's stable argsort of the int16-cast
    destinations: for bounded small integers numpy selects its radix
    sort, i.e. a two-pass counting scatter in O(n + W) — benchmarked
    faster than one-hot-cumsum rank composition at every (n, W) this
    engine runs.  The cast is guarded: ``hist.size`` (== num_workers)
    must fit int16 or worker ids would silently wrap; past the limit the
    full-width stable argsort keeps correctness (O(n log n) comparison
    sort) and a one-time :class:`RuntimeWarning` flags the perf cliff.
    """
    if np.count_nonzero(hist) <= 1:
        return None
    if hist.size > MAX_RADIX_WORKERS:  # int16 would wrap: fall back wide
        global _WARNED_WIDE_FALLBACK
        if not _WARNED_WIDE_FALLBACK:
            _WARNED_WIDE_FALLBACK = True
            warnings.warn(
                f"scatter_order: {hist.size} workers exceeds the int16 "
                f"radix-sort limit ({MAX_RADIX_WORKERS}); falling back to "
                f"a full-width stable argsort (correct, but O(n log n) "
                f"per chunk instead of the counting scatter). "
                f"(warned once)", RuntimeWarning, stacklevel=2)
            # Module-level site (no engine in scope): the process-wide
            # incident log keeps the cliff queryable, once per process
            # exactly like the warning.
            from . import resilience
            resilience.GLOBAL.record(
                "radix-cliff",
                cause=f"{hist.size} workers > int16 radix limit "
                      f"({MAX_RADIX_WORKERS})",
                action="full-width stable argsort")
        return np.argsort(dest, kind="stable")
    return np.argsort(dest.astype(np.int16), kind="stable")


@dataclasses.dataclass
class ScatterPlan:
    """One chunk's fused routing decision: destinations + placement.

    ``bounds[w] : bounds[w + 1]`` is worker ``w``'s slice of the grouped
    chunk; ``hist`` doubles as the per-worker traffic metric.  Exactly one
    of ``order`` / ``pos`` is set (or neither: identity, already grouped).
    """

    dest: np.ndarray                     # [n] destination worker ids
    hist: np.ndarray                     # [W] records per worker
    bounds: np.ndarray                   # [W + 1] slice boundaries
    order: Optional[np.ndarray] = None   # gather: grouped = arr[order]
    pos: Optional[np.ndarray] = None     # scatter: grouped[pos] = arr

    def take(self, arr: np.ndarray) -> np.ndarray:
        """Group one column by destination (stable; zero-copy if identity)."""
        if self.order is not None:
            return arr[self.order]
        if self.pos is not None:
            out = np.empty_like(arr)
            out[self.pos] = arr
            return out
        return arr

    def gather_indices(self) -> Optional[np.ndarray]:
        """Placement as gather indices (``None`` = identity).

        A ``pos``-form plan (Pallas ranks) is inverted once — a single
        O(n) scatter of ``arange`` — so consumers can gather each worker's
        slice ``order[bounds[w]:bounds[w+1]]`` straight into its queue.
        """
        if self.order is None and self.pos is not None:
            self.order = np.empty(self.pos.size, dtype=np.int64)
            self.order[self.pos] = np.arange(self.pos.size, dtype=np.int64)
        return self.order


def _bounds_of(hist: np.ndarray) -> np.ndarray:
    bounds = np.zeros(hist.size + 1, dtype=np.int64)
    np.cumsum(hist, out=bounds[1:])
    return bounds


class PartitionBackend:
    """Computes the fused routing decision for one chunk.

    Implementations must consume ``routing.advance_counters(keys)`` exactly
    once per chunk so the deterministic low-discrepancy sequence advances
    identically under every backend.  ``partition`` returns the raw
    (destinations, histogram) pair; ``partition_scatter`` additionally
    returns the grouping placement as a :class:`ScatterPlan` — the default
    implementation derives it on the host via :func:`scatter_order`, and
    backends that can compute within-destination ranks during the
    partition itself (the Pallas kernel) override it.
    """

    name = "abstract"

    def partition(self, routing: RoutingTable,
                  keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        """Returns (dest [n] int64, hist [num_workers] int64)."""
        raise NotImplementedError

    def partition_scatter(self, routing: RoutingTable,
                          keys: np.ndarray) -> ScatterPlan:
        """One-pass fused partition + grouping placement for a chunk."""
        dest, hist = self.partition(routing, keys)
        return ScatterPlan(dest, hist, _bounds_of(hist),
                           order=scatter_order(dest, hist))


class NumpyPartitionBackend(PartitionBackend):
    """Host path: fixed-point inverse-CDF routing in pure numpy."""

    name = "numpy"

    def partition(self, routing, keys):
        counters = routing.advance_counters(keys)
        dest = routing.route_lowdiscrepancy(keys, counters)
        hist = np.bincount(dest, minlength=routing.num_workers)
        return dest, hist


class PallasPartitionBackend(PartitionBackend):
    """Device path: the Pallas exchange kernel (histogram + ranks for free).

    The host still owns the per-key counters (one ``advance_counters`` per
    chunk); the kernel receives the counters plus the host-computed float32
    row-CDF, so its destinations match the numpy backend bit for bit.  The
    fused ``partition_scatter`` path also reads back each record's
    within-destination rank (accumulated in VMEM scratch alongside the
    histogram), so the scatter placement costs the host one vectorized
    add — no sort.
    """

    name = "pallas"

    def __init__(self, *, block_n: int = 512,
                 interpret: Optional[bool] = None):
        self.block_n = int(block_n)
        self.interpret = interpret

    def _device_call(self, routing, keys, fn_name: str):
        import importlib

        import jax.numpy as jnp
        from ..kernels import ops as kops
        kpart = importlib.import_module("repro.kernels.partition")

        counters = routing.advance_counters(keys)
        interpret = (self.interpret if self.interpret is not None
                     else kpart.default_interpret())
        if interpret:
            # Off-TPU validation path: call the kernel module directly so
            # shapes of odd-sized tail chunks don't churn the jit cache.
            return getattr(kpart, fn_name)(
                jnp.asarray(keys.astype(np.int32)),
                jnp.asarray(counters.astype(np.int32)),
                jnp.asarray(routing.weights),
                cdf=jnp.asarray(routing.cdf32),
                block_n=self.block_n, interpret=True)
        return getattr(kops, fn_name)(
            jnp.asarray(keys.astype(np.int32)),
            jnp.asarray(counters.astype(np.int32)),
            jnp.asarray(routing.weights),
            jnp.asarray(routing.cdf32), block_n=self.block_n)

    def partition(self, routing, keys):
        dest, hist = self._device_call(routing, keys, "partition")
        return (np.asarray(dest, dtype=np.int64),
                np.asarray(hist, dtype=np.int64))

    def partition_scatter(self, routing, keys):
        dest, rank, hist = self._device_call(routing, keys,
                                             "partition_scatter")
        dest = np.asarray(dest, dtype=np.int64)
        hist = np.asarray(hist, dtype=np.int64)
        bounds = _bounds_of(hist)
        # Fused placement: each record's slot is its destination's base
        # offset plus its within-destination arrival rank (kernel output).
        pos = bounds[dest] + np.asarray(rank, dtype=np.int64)
        return ScatterPlan(dest, hist, bounds, pos=pos)


_BACKENDS = {
    "numpy": NumpyPartitionBackend,
    "pallas": PallasPartitionBackend,
}

BackendSpec = Union[None, str, PartitionBackend]


def get_backend(spec: BackendSpec = None) -> PartitionBackend:
    """Resolve a backend: instance, name, or None (env var, then numpy)."""
    if isinstance(spec, PartitionBackend):
        return spec
    if spec is None:
        spec = os.environ.get("REPRO_PARTITION_BACKEND", "numpy")
    try:
        return _BACKENDS[spec]()
    except KeyError:
        raise ValueError(
            f"unknown partition backend {spec!r}; "
            f"choose from {sorted(_BACKENDS)}") from None


class Exchange:
    """Fused chunk routing for one edge (the data-plane hot path).

    ``send`` asks the backend for one :class:`ScatterPlan` (partition +
    placement in a single fused pass), groups each column with one
    fancy-index application, and hands every worker its contiguous slice;
    the plan histogram doubles as the slice boundaries and as the
    per-worker traffic metric (``sent_per_worker``).
    """

    def __init__(self, routing: RoutingTable, dst, backend: BackendSpec = None):
        self.routing = routing
        self.dst = dst
        self.backend = get_backend(backend)
        self.tuples_sent = 0
        self.sent_per_worker = np.zeros(routing.num_workers, dtype=np.int64)
        #: partition+scatter placements computed on this edge (one per
        #: chunk here; the device plane's chain fusion drives the same
        #: counter to 0 on every fused non-head edge).
        self.placements = 0

    def send(self, chunk: Chunk) -> None:
        keys, vals = chunk
        n = int(keys.size)
        if n == 0:
            return
        plan = self.backend.partition_scatter(self.routing, keys)
        self.placements += 1
        self.tuples_sent += n
        self.sent_per_worker += plan.hist
        receive = getattr(self.dst, "receive_scatter", None)
        if receive is not None:
            # Fused delivery: gather each worker's records straight into
            # its ring-buffer segment — no intermediate grouped array.
            receive(keys, vals, plan)
        else:  # minimal receive_sorted-only targets (test doubles)
            self.dst.receive_sorted(plan.take(keys), plan.take(vals),
                                    plan.bounds)


class DeviceExchange:
    """Device-plane edge: ``send`` stages the chunk on the accelerator.

    The heavy lifting happens in the destination operator's fused
    device step (:class:`repro.dataflow.device.DeviceOpRuntime`): one
    jitted dispatch per super-tick performs partition → rank → ring
    scatter → budgeted pop → fold for this edge.  ``send`` only stages —
    a host chunk is uploaded once (padded + masked), a
    :class:`~repro.dataflow.device.DeviceChunk` from an upstream device
    operator is adopted zero-copy, so consecutive device edges never
    round-trip through the host.  ``account`` is fed by the runtime's
    O(num_workers) per-dispatch metric readback, keeping
    ``tuples_sent`` / ``sent_per_worker`` exact for checkpoints and
    controllers.
    """

    def __init__(self, routing: RoutingTable, dst, runtime):
        self.routing = routing
        self.dst = dst
        self.runtime = runtime
        self.tuples_sent = 0
        self.sent_per_worker = np.zeros(routing.num_workers, dtype=np.int64)

    @property
    def placements(self):
        """Placement executions on this edge: the runtime counts one per
        ingested chunk; a fused chain's non-head edges stay at 0 (they
        reuse the head edge's placement — the whole point)."""
        return self.runtime.placements

    def account(self, hist: np.ndarray) -> None:
        self.tuples_sent += int(hist.sum())
        self.sent_per_worker += hist

    def send(self, chunk) -> None:
        self.runtime.stage(chunk)
