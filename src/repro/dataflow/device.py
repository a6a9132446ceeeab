"""The device-resident exchange plane: fused super-tick steps per edge.

This module keeps one edge's *entire* data plane on the accelerator
between host boundaries: the chunk in flight, the per-worker ring
queues, the routing constants (float32 row-CDF, primaries, split mask,
owners), the per-key split counters and the downstream keyed fold all
live as ``jnp`` arrays, and a single **persistent jitted step** per edge
advances them — partition → within-destination rank → ring scatter →
budgeted pop → vectorized fold (GroupByAgg / Sink) or stateless map
(Filter / Project) — in **one dispatch per edge per super-tick**, with
the mutable state pytree donated so the device can reuse the buffers in
place.

Host readback is confined to

  * O(num_workers) control metrics per dispatch (histogram / popped /
    emitted counts) that keep the host mirrors — queue lengths,
    ``sent_per_worker``, worker stats — exact without touching record
    data, and
  * full materialization **only at the boundaries the batched scheduler
    already computes** (:meth:`Engine._fusible_ticks`): sink snapshots,
    controller metric rounds, checkpoint cuts, END markers and routing
    rewrites, via :meth:`DeviceOpRuntime.sync_host`.

Record payloads (keys / vals / dest / rank) never cross the host
boundary between those points; chunks handed from one device operator to
the next stay on the device as padded, validity-masked
:class:`DeviceChunk` buffers, so consecutive fused edges share one
residency domain.

Flat map pops
-------------
A Filter / Project stage maps lane by lane, so it pops its rings into
one flat window (:func:`_pop_flat`) instead of the ``[W, B]``
rectangle the other kinds pop: each ring's take, worker-major and in
stream order within a worker — the rectangle's live lanes in its
row-major order, less the padding between and after the rows.  The host
sizes the window per dispatch from what it knows (resident ring lanes
plus the lanes the dispatch brings in, at most ``W * budget``), rounded
up to a power of two and never wider than ``W * B``, so a skewed or
sparse window costs its live lanes, not ``W * B``.  Downstream steps see
the same live lanes in the same order, so routing, ranks, ring contents
and folds are unchanged.

Row-state operators (HashJoin / Sort)
-------------------------------------
The full paper operator set runs on this plane, not just keyed folds:

``rows``  (HashJoinBuild, RangeSort) — keyed *row* state lives in a
          device-resident segment store mirroring
          :class:`~repro.dataflow.state.ScopeRows`: per worker a flat
          ``[W, rcap]`` (key, val, owned) row log in arrival order plus
          a host length mirror, with amortized-doubling capacity growth.
          The fused step appends every popped lane at
          ``row_len + within-pop-rank`` with an owned/scattered flag
          frozen at fold time (``owner[key] == worker``), so SBR splits
          park overflow rows exactly where the host plane's
          ``_append_segments`` would.  Boundary materialization regroups
          the log by key (one stable counting pass per worker) into the
          operator's ``ScopeRows`` state/scattered pair — bit-identical
          scope arrays, because both planes preserve per-scope arrival
          order — and the upload inverse (``ScopeRows.export_rows``)
          round-trips it.  With ``device_use_kernel=True`` a split-table
          ingest runs the fused Pallas ``partition_scatter_fold`` kernel:
          dest/rank/hist feed the ring scatter and the kernel's per-key
          count column doubles as the key-arrival stats fold.
``probe`` (HashJoinProbe) — the installed build side is immutable, so
          the probe is stateless per tuple given a dense ``[W, K]``
          match-count table (owned + scattered build rows summed,
          refreshed from host state whenever a migration marks it
          stale).  The step pops a budgeted window and *expands* it
          (:func:`repro.kernels.ref.match_expand`): each lane emitted
          ``mcounts[w, key]`` times into a padded, masked
          ``[W, B * M]`` DeviceChunk, where ``M`` bounds the per-tuple
          fanout (the max match count, a static spec field) — so the
          emit buffer always covers the worst case and no mid-super-tick
          host round-trip or carry-over is ever needed; edges whose
          ``W * B * M`` would exceed ``MAX_EMIT_CELLS`` demote to the
          host path instead of risking an unbounded buffer.  Because a
          probe preserves its input keys, a token-equal probe edge joins
          multi-edge chain fusion like a map stage (below).

Multi-edge chain fusion
-----------------------
Consecutive device edges with *routing-equivalent* tables collapse into
one fused dispatch.  The common exploratory shape — a stateless Filter /
Project sandwiched between two edges over the same key space — would
otherwise re-run a partition + scatter on the second edge that is
provably identical to the first: a record sits on worker *w* of the map
stage exactly because ``primary_A[key] == w``, and when edge B's table
routes the same key space through the same primaries
(``RoutingTable.routing_token()`` equality; tokens exist only for
one-hot tables, whose destinations are counter-independent), every
surviving record's destination on edge B *is* the worker it already
occupies.  So the map step hands its downstream stage **pre-placed**
lanes, each tagged with the ring it belongs to, and the downstream
ingest (:func:`_push_placed`) is a rank-by-segment-cumsum ring append:
no partition, no inverse-CDF, no one-hot rank matrix.  The whole chain
(map stages plus the final fold / sink / map tail) advances in **one**
jitted dispatch per super-tick (:func:`_make_step_chain`, trace-cached
on the tuple of per-stage :class:`StepSpec`\\ s), and per-super-tick
placement work drops from one-per-edge to one-per-chain.

Fusibility is re-checked every dispatch (`DeviceOpRuntime.
_chain_for_dispatch`), so the engine **falls back to per-edge placement
the moment it cannot prove equivalence**: any rewrite that splits or
moves a key changes (or voids) a table's token — including
mid-super-tick rewrites, whose listener-triggered sync flushes staged
chunks under the pre-rewrite constants first — and demotions, END,
manual ticks with non-scheduler budgets, or an explicit
``Engine(device_chain=False)`` / ``REPRO_DEVICE_CHAIN=0`` all disable
fusion while every stage keeps its exact host mirrors.  Chains require
every non-tail map stage to preserve keys: Filter does by construction;
Project must declare ``preserves_keys=True``.

The control plane (device-resident skew controller)
---------------------------------------------------
With ``Engine(device_controller=True)`` / ``REPRO_DEVICE_CONTROLLER=1``
an attached :class:`~repro.core.controller.ReshapeController` is *armed*
onto its monitored edge (:class:`DeviceController`): per-key arrival
stats, the workload tracker, the skew test, helper choice and the
phase-1 / phase-2 split-ratio math are compiled into a ``ctrl_step``
that runs all metric rounds a super-tick covers in one jitted call, and
a detection rewrites the routing constants (cdf32 / primary / split
mask / owner) with a bumped epoch, so the very next window dispatches
rebalanced without a round of the host controller.  The ``ctrl_step``
runs on the host's CPU backend (:func:`_ctrl_device`): a TPU has no
IEEE binary64, and its float64 emulation rounds the observation scaling
and the split weights unlike the host controller (which can move a
float32 CDF entry by an ulp), so the bit-exact twin would be lost.  On
a TPU every super-tick with a metric round therefore crosses the host
boundary: it reads the arrivals back from the chip and uploads the
routing constants (O(K + W) values each way).  The host controller
stays the **bit-exact twin and arbitration point**: each round's
observation window (phi, owner-attributed arrivals) is logged on
device, and at the next boundary :meth:`DeviceController.drain` replays
those windows through the untouched host ``ReshapeController`` — events,
tau trajectory, mitigation phases and the routing table must reproduce
the device's decisions exactly (on any mismatch the host wins, with a
``RuntimeWarning`` and a re-upload), which keeps the host path the A/B
oracle and checkpoints host-authoritative.

Only decisions expressible without state migration run in-dispatch:
eligibility (``DeviceController.ineligible_reason``) requires SBR +
SCATTERED (GroupByAgg / RangeSort traits), a single helper, zero
control delay, full phase-1 partitions, unbounded migration rate and no
pinned helpers — MARKERS / REPLICATE operators (HashJoinProbe) and
multi-helper or delayed-control configs refuse up front and stay
host-stepped.  An armed controller *demotes* back to host stepping the
moment device-held state stops being authoritative: a host-side state
mutation (``mark_state_stale``; at END it instead *retires* before the
scattered-state merge, drained and without an incident), an
out-of-band routing rewrite (another writer bumping ``table.version``),
or a checkpoint restore carrying mitigation state the jit twin cannot
represent (anything outside PHASE_ONE / PHASE_TWO, or pending delayed
messages) — each drains first, so no decision is lost.

Epoch rules vs ``routing_token``: in-dispatch rewrites advance a
device-side epoch ahead of the host table's ``version``; while the two
disagree (``routing_dirty``) the runtime's ``_live_token()`` returns
``None``, so chain fusion and the placement-epoch reuse guard treat the
table as unprovable until a drain reconciles ``version``/consts — a
fused chain therefore can never dispatch under a stale proof of routing
equivalence.  Scheduling: :meth:`Engine._fusible_ticks` stops cutting
windows at metric rounds for armed edges (rounds no longer need a host
boundary), so monitored workflows keep full-width fused spans.

Executors
---------
``jit``   the real device plane as described above.  Default on TPU;
          forced off-TPU with ``Engine(device_executor="jit")`` or
          ``REPRO_DEVICE_EXECUTOR=jit`` (the correctness/CI mode — the
          equivalence and checkpoint tests run it).  With
          ``device_use_kernel=True`` the partition core inside the step
          additionally runs the fused Pallas ``partition_scatter`` /
          ``partition_scatter_fold`` kernels (interpret mode off TPU).
``host``  the validation twin on accelerator-less boxes: the identical
          canonical fixed-point routing rule executed by the fused numpy
          exchange (the backend-equivalence suite proves the planes
          bit-identical), so off-TPU benchmark rows measure the plane
          architecture instead of XLA:CPU's serial scatter/sort lowering
          (measured 10-30x slower than numpy's radix sort / bincount for
          the placement primitives on this class of box).

Bit-exactness: destinations, ranks, histograms, queue contents, split
counters and every integer metric are identical across the jit step, the
host twin and the reference plane (the routing core is the canonical
rule of :mod:`repro.core.partitioner`; placement and budgeted pops are
integer arithmetic).  Float64 val payloads round-trip untouched through
rings, maps and row stores: the device carries them as their int64 bit
patterns (a TPU has no binary64 -- XLA stores float64 there as a pair
of float32 and would round every value on upload) and reads them as
float64 only where it computes on them (predicates, sums).  Float sums
may differ: the *summation order* of keyed float folds differs from
numpy's sequential weighted ``bincount`` (XLA scatter-add), and a TPU's
float64 arithmetic is emulated, which is why the engine's cross-plane
contract is stated on ``Sink.series`` / ``Sink.counts`` (integers) and
checkpoint counters.  That contract holds only while every Filter
predicate decides as numpy would, and two cases break it, because the
predicate computes on the float64 XLA gives it: every XLA backend (the
CPU too) reads a subnormal value as zero; and on a TPU the float32 pair
keeps about 48 significant bits and float32's exponent range, so a value
within about 2**-48 (relative) of a threshold, or of magnitude below
about 1.2e-38 or above 3.4e38, can be kept or dropped unlike on the
numpy plane.  Sink counts and series then differ with it.

Memory tiering (watermark spill of cold device state)
-----------------------------------------------------
With a device budget armed (``Engine(device_budget=cells)`` or
``REPRO_DEVICE_BUDGET``; see :mod:`repro.dataflow.spill`) each edge
bounds its *resident* device entries: the budget is split evenly across
workers (``SpillConfig.per_worker``), and crossing ``high_wm`` of that
share triggers eviction of **cold spans** down to ``low_wm`` — for
rings, the spans *behind the pop cursor's window* (the newest resident
records: everything beyond ``max(low, budget)`` entries from the head,
which the next pops cannot reach); for row stores, the oldest rows (a
per-worker prefix — row logs are append-only and only read back at
boundaries).  Evicted spans become checksummed host
:class:`~repro.dataflow.spill.SpillSegment`\\ s ordered so that per
worker the logical record sequence is always ``[resident][spilled]``.

Prefetch contract: before every dispatch, ``_spill_refill`` re-uploads
logically-next segments until the resident count covers the pop budget
— so the fused dispatch's ``take`` equals the host plane's
``min(budget, total)`` *exactly* and never blocks on a cold read; a
double-buffered prefetcher (``SpillState.prefetch``) keeps the next
two segments per worker pre-uploaded between dispatches.  Fresh pushes
that land behind spilled spans are re-tiered to the spill tail right
after the dispatch (``_spill_demote_fresh``), preserving the ordering
invariant; fused chains are gated off (``_spill_gate``) whenever an
edge holds spilled spans or projects a watermark crossing, so chain
dispatches never need to evict.  The ``lens`` / ``rows_len`` mirrors
keep counting resident **plus** spilled records, which keeps workloads,
backlog, END detection and every controller decision bit-identical to
an unspilled run.

Pressure is a structured signal: the first crossing of the high
watermark per worker records a ``mem-pressure`` incident and calls
``ReshapeController.note_memory_pressure`` on the attached controller
(a mitigation trigger — splitting the fat worker sheds the hot
partition's growth); the signal re-arms below the low watermark.
Degradation replaces the old cliffs: probe edges whose ``W * B * M``
would blow ``MAX_EMIT_CELLS`` now emit in chunked sub-budget dispatches
(``_tick_probe_chunked``, bit-exact: prefix pops compose and chunk
splitting preserves per-lane expansion order) instead of demoting, and
ring/row-store regrowth past the budget-implied allocation cap records
a one-time ``regrow-capped`` incident instead of doubling silently.

Invariants (machine-checked by ``repro.analysis``)
--------------------------------------------------
The conventions this plane depends on are enforced by the plane-contract
analyzer (``python -m repro.analysis src/``, wired into tier-1 as
``tests/test_analysis.py``) and, at runtime, by ``REPRO_SANITIZE=1``:

``stale-capture``     jitted step bodies (the ``_make_step*`` /
                      ``_make_ctrl_step`` closures) capture only
                      parameters, spec fields and module constants —
                      anything else is invisible to the trace-cache key
                      and goes stale after the first trace.
``donation-unsafe``   a donated state pytree (``donate_argnums``) is
                      never read after the dispatch that donated it;
                      the only safe pattern is rebind-from-the-result.
``dtype-drift``       every ``jnp`` constructor here and in
                      ``kernels/**`` pins its dtype explicitly, and no
                      bare ``np.int64``/``float64`` appears inside a
                      jitted body (host-side ``np.int64`` dispatch
                      scalars are the deliberate trace-signature pin).
``unpaired-warning``  every one-time ``RuntimeWarning`` pairs with a
                      structured ``Incident`` (PR 7's convention).
``mirror-write``      the exact host mirrors (``lens`` / ``received`` /
                      ``rows_len`` / worker stats / exchange counters)
                      are written only at the registered accounting
                      sites: dispatch fold-metrics, materialization
                      boundaries, restore and demotion back-out.

Runtime sanitizers (``REPRO_SANITIZE=1``): a retrace sentinel asserts
each ``StepSpec`` compiles exactly once per process
(``sanitize-retrace`` incident + failure on drift), and every
``sync_host`` boundary cross-checks mirrors against materialized device
truth (``sanitize-mirror``) and guards fold sums against NaN/inf
(``sanitize-nan``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Optional, Union

import numpy as np

from .. import obs
from ..analysis import sanitize as _sanitize
from . import spill as spill_tier
from .resilience import InjectedDispatchFault
from .tuples import Chunk, ring_span

__all__ = ["DeviceChunk", "DeviceOpRuntime", "resolve_executor", "wireable"]

#: fold-state ceiling: skip device wiring when W * K explodes.
MAX_FOLD_CELLS = 1 << 22

#: pop-window ceiling: a ring-backed operator's per-super-tick budget
#: bounds the static window width B; "effectively unbounded" service
#: rates (the Sink idiom, 2**31-1) would demand an absurd window, so
#: such operators stay on the host path (the Sink itself bypasses rings
#: and is unaffected).
MAX_SERVICE_RATE = 1 << 20

#: routing works on blocks of lanes whose [lanes, K] or [lanes, W]
#: temporaries hold at most this many cells; split counters count with
#: one-hot prefix sums while K is at most ``ONEHOT_MAX_KEYS`` and sort
#: the whole window beyond.  A TPU compiles a 2**20-lane sort in about
#: 90 s, the blocked prefix sums in about 4 s; so an armed edge traces
#: the split-aware step up front only up to ``ONEHOT_MAX_KEYS`` keys, and
#: beyond it only while its uploaded consts split a key.
ONEHOT_BLOCK_CELLS = 1 << 21
ONEHOT_MAX_KEYS = 2048

#: the kinds that pop into a flat window (:func:`_pop_flat`)
MAP_KINDS = ("filter", "project")

#: probe-expand ceiling: the emit buffer is W * B * M lanes (M = the max
#: per-tuple build-match fanout, so it always covers the worst case and
#: carry-over never has to defer outputs past the host plane's tick);
#: a build table skewed enough to blow this demotes the edge instead.
MAX_EMIT_CELLS = 1 << 22


def _jnp():
    import jax.numpy as jnp
    return jnp


def _note_trace(kind, spec, args) -> None:
    """Retrace sentinel: first statement of every jitted step body, so
    it executes exactly once per *trace* (compiled executions never
    re-enter Python).  The sanitizer counts compilations per
    (kind, spec, arg-signature); under ``REPRO_SANITIZE=1`` a second
    trace of an already-compiled key is a ``sanitize-retrace`` incident
    plus a hard failure (rule id: sanitize-retrace)."""
    _sanitize.note_step_trace(kind, spec, args)


def _x64():
    import jax
    return jax.enable_x64(True)


def _ctrl_device():
    """Where the controller step runs and keeps its state: the host's CPU
    backend.  XLA lowers float64 on a TPU to pairs of float32 (about 48
    significant bits), so the step's non-integer arithmetic -- observation
    scaling, means, stderr, shares, split ratios -- would not round like
    the host controller's IEEE binary64, and the bit-exact twin would be
    lost.  Per super-tick the step moves O(K + W) values each way.
    None when ``JAX_PLATFORMS`` leaves the CPU backend out."""
    import jax
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


def _data_device():
    """The device the data-plane steps run on (JAX's default)."""
    import jax
    return jax.devices()[0]


def _readback(x, site: str) -> np.ndarray:
    """Device -> host: ``np.asarray`` of a data-plane array.  An array on
    the data device is counted (``device.readbacks``) and timed
    (``device.readback``, naming its ``site``); host arrays pass through
    uncounted."""
    import jax
    if not (isinstance(x, jax.Array) and x.devices() == {_data_device()}):
        return np.asarray(x)
    obs.count("device.readbacks")
    with obs.span("device.readback", site=site):
        return np.asarray(x)


def _val_bits(vals) -> np.ndarray:
    """Host float64 values -> the int64 bit patterns the device carries."""
    return np.asarray(vals, np.float64).view(np.int64)


def _val_floats(bits) -> np.ndarray:
    """Device-carried value bits -> host float64 values."""
    return np.asarray(bits).view(np.float64)


def _as_f64(bits):
    """In a step: carried value bits -> float64, for arithmetic only.
    (On a TPU the result is the float32-pair emulation, and a predicate
    on it can decide unlike numpy near a threshold -- see the module's
    bit-exactness notes; the carried bits are never rewritten from it.)"""
    import jax
    return jax.lax.bitcast_convert_type(bits, _jnp().float64)


def _as_bits(x):
    """In a step: computed float64 -> carried bits (a Project's output).
    XLA cannot lower this bitcast on a TPU, so :func:`wireable` keeps
    Project on the host plane there."""
    import jax
    jnp = _jnp()
    return jax.lax.bitcast_convert_type(x.astype(jnp.float64), jnp.int64)


def _trace_errors() -> tuple:
    """The errors a user fn that cannot be traced raises while a step
    traces — the only first-dispatch failures that demote an edge.
    Lowering and compile refusals, runtime faults, OOM and a tracer
    leaked by the plane's own step code propagate."""
    import jax
    e = jax.errors
    return (e.ConcretizationTypeError, e.TracerArrayConversionError,
            e.TracerIntegerConversionError, e.NonConcreteBooleanIndexError)


def resolve_executor(requested: Optional[str]) -> str:
    """Pick the device-plane executor: ``jit`` on TPU, else the host twin.

    ``requested`` (constructor arg) or ``REPRO_DEVICE_EXECUTOR`` force a
    choice — ``"jit"`` off-TPU is the correctness mode tests run.
    """
    import os

    import jax
    ex = requested or os.environ.get("REPRO_DEVICE_EXECUTOR")
    if ex in ("jit", "host"):
        return ex
    if ex is not None:
        raise ValueError(f"unknown device executor {ex!r}")
    return "jit" if jax.default_backend() == "tpu" else "host"


def wireable(op, num_keys: int) -> bool:
    """Is ``op`` a device-wireable destination for an edge of ``num_keys``?

    Exact types only (a subclass may override ``process``); the dense
    per-(worker, key) structures — keyed folds, the probe match table —
    keep wide key spaces host-side.  This is the full paper operator
    set: Filter / Project / GroupByAgg / Sink plus the row-state
    HashJoinBuild / HashJoinProbe / RangeSort -- less Project on a TPU,
    where its computed values cannot be turned back into the bit
    patterns the plane carries.
    """
    import jax

    from .operators import (Filter, GroupByAgg, HashJoinBuild,
                            HashJoinProbe, Project, RangeSort, Sink)
    if type(op) not in (Filter, Project, GroupByAgg, Sink,
                        HashJoinBuild, HashJoinProbe, RangeSort):
        return False
    if type(op) is Project and jax.default_backend() == "tpu":
        return False
    # Row-state operators keep no dense [W, K] structure (their state is
    # a [W, rcap] row log), so only the K-sized routing consts gate them
    # — wide key spaces stay wireable and rely on the spill tier for
    # memory pressure instead of refusing up front.
    if type(op) in (HashJoinBuild, RangeSort):
        cells_ok = num_keys <= MAX_FOLD_CELLS
    else:
        cells_ok = op.num_workers * num_keys <= MAX_FOLD_CELLS
    return (cells_ok
            and (type(op) is Sink or op.service_rate <= MAX_SERVICE_RATE))


# --------------------------------------------------------------------- #
# Device chunks                                                          #
# --------------------------------------------------------------------- #
@dataclasses.dataclass
class DeviceChunk:
    """A padded, validity-masked chunk resident on the device.

    ``n_live`` is the host-known number of live lanes (exact: it comes
    from the emitting step's O(W) metric readback), so the engine makes
    control decisions — skip empty sends, END detection — without
    reading the mask back.
    """

    keys: object                 # [NB] int64 jnp
    vals: object                 # [NB] int64 jnp: float64 bit patterns
    valid: object                # [NB] bool jnp
    n_live: int

    def to_host(self) -> Chunk:
        """Materialize + compact (the device -> host plane boundary)."""
        m = _readback(self.valid, "chunk")
        return (_readback(self.keys, "chunk")[m],
                _val_floats(_readback(self.vals, "chunk"))[m])


@dataclasses.dataclass(frozen=True)
class StepSpec:
    """The static half of a jitted step (hashable: keys the trace cache)."""

    kind: str        # "fold" | "filter" | "project" | "sink" | "probe" | "rows"
    W: int                       # destination workers
    K: int                       # key-space size
    cap: int                     # ring capacity (power of two)
    B: int                       # pop-window width (max budget)
    any_split: bool              # routing table carries split keys
    may_scatter: bool            # owned/scattered fold split armed
    track_stats: bool            # per-key arrival stats fold armed
    use_kernel: bool             # partition core via the Pallas kernel
    fn: Optional[Callable] = None   # Filter predicate / Project map
    M: int = 1                   # probe: max per-tuple match fanout
    rcap: int = 0                # rows: segment-store capacity (pow2)
    P: int = 0                   # filter / project: flat pop width


# --------------------------------------------------------------------- #
# Step building blocks (pure jnp; caller holds the x64 context)           #
# --------------------------------------------------------------------- #
def _split_counters(spec: StepSpec, consts, count, keys, valid):
    """Device twin of ``RoutingTable.advance_counters``: per-record
    running split-key counters (within-chunk occurrence + persistent
    count) and the advanced persistent counts.  Dead lanes and one-hot
    keys consume nothing."""
    import jax
    jnp = _jnp()
    live = valid & consts["is_split"][keys]
    if spec.K <= ONEHOT_MAX_KEYS:
        occ, seen = _occurrences(keys, live, spec.K)
        counters = jnp.where(live, count[keys] + occ.astype(count.dtype), 0)
        return counters, count + seen.astype(count.dtype)
    n = keys.shape[0]
    arange = jnp.arange(n, dtype=count.dtype)
    sent = jnp.where(live, keys, spec.K)          # dead lanes sort last
    order = jnp.argsort(sent, stable=True)
    sk = sent[order]
    starts = jnp.concatenate([jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    seg_start = jax.lax.cummax(jnp.where(starts, arange, 0))
    occ = jnp.zeros(n, count.dtype).at[order].set(arange - seg_start)
    counters = jnp.where(live, count[keys] + occ, 0)
    new_count = count.at[keys].add(live.astype(count.dtype))
    return counters, new_count


def _lane_blocks(width: int, *cols):
    """Pad lane columns of length n and fold them to ``[nblk, b]`` blocks,
    b chosen so a ``[b, width]`` temporary stays within
    ``ONEHOT_BLOCK_CELLS``: a padded chunk may hold tens of millions of
    lanes, and a whole-chunk ``[n, width]`` temporary would not fit the
    chip's memory.  Padding lanes must be masked dead by the caller."""
    jnp = _jnp()
    n = cols[0].shape[0]
    b = min(n, max(1, ONEHOT_BLOCK_CELLS // width))
    nblk = -(-n // b)
    return tuple(jnp.pad(c, (0, nblk * b - n)).reshape(nblk, b)
                 for c in cols)


def _occurrences(ids, live, n_ids: int):
    """For each live lane, how many live lanes with its id come before it
    (the rank a stable sort by id would give), and the live count per id
    -- without a sort: one-hot prefix sums over blocks of lanes, carrying
    the per-id counts from block to block."""
    import jax
    jnp = _jnp()
    n = ids.shape[0]
    every = jnp.arange(n_ids, dtype=ids.dtype)

    def block(seen, blk):
        i, m = blk
        oh = ((i[:, None] == every[None, :]) & m[:, None]).astype(jnp.int32)
        before = jnp.cumsum(oh, axis=0) - oh + seen[None, :]
        return (seen + oh.sum(axis=0, dtype=jnp.int32),
                (before * oh).sum(axis=1, dtype=jnp.int32))

    seen, occ = jax.lax.scan(block, jnp.zeros(n_ids, jnp.int32),
                             _lane_blocks(n_ids, ids, live))
    return occ.reshape(-1)[:n], seen


def _inverse_cdf(cdf, keys, u):
    """The canonical rule's raw destination, ``#{w : u >= cdf[key, w]}``,
    lane by lane over blocks of lanes."""
    import jax
    jnp = _jnp()
    n = keys.shape[0]
    dest = jax.lax.map(
        lambda ku: jnp.sum(ku[1][:, None] >= cdf[ku[0]], axis=1,
                           dtype=jnp.int32),
        _lane_blocks(cdf.shape[1], keys, u))
    return dest.reshape(-1)[:n]


def _advance_and_route(spec: StepSpec, consts, count, keys, valid):
    """``_split_counters`` + the canonical inverse-CDF rule:
    (dest, rank, hist, new_count); dead lanes advance neither the split
    counters nor anyone's rank."""
    jnp = _jnp()
    from ..core.ops import ld_thresholds

    if spec.any_split:
        counters, new_count = _split_counters(spec, consts, count, keys,
                                              valid)
        if spec.use_kernel:
            # Fused Pallas partition core: bit-identical destinations by
            # the canonical rule (interpret mode off TPU).
            import importlib
            kpart = importlib.import_module("repro.kernels.partition")
            kdest, _, _ = kpart.partition_scatter(
                keys.astype(jnp.int32), counters.astype(jnp.int32),
                consts["cdf"], cdf=consts["cdf"])
            dest = kdest.astype(keys.dtype)
        else:
            u = ld_thresholds(counters)
            dest = _inverse_cdf(consts["cdf"], keys, u).astype(keys.dtype)
            dest = jnp.minimum(dest, spec.W - 1)
            dest = jnp.where(consts["is_split"][keys], dest,
                             consts["primary"][keys])
    else:
        # One-hot table: destinations are counter-independent and the
        # low-discrepancy sequence is not consumed (host policy).
        dest = consts["primary"][keys]
        new_count = count
    rank, hist = _occurrences(dest, valid, spec.W)
    return (dest, rank.astype(count.dtype), hist.astype(count.dtype),
            new_count)


def _push(spec: StepSpec, state, keys, vals, valid, dest, rank, hist):
    jnp = _jnp()
    pos = (state["tail"][dest] + rank) % spec.cap
    flat = jnp.where(valid, dest * spec.cap + pos, spec.W * spec.cap)
    rk = state["rk"].reshape(-1).at[flat].set(
        keys, mode="drop").reshape(spec.W, spec.cap)
    rv = state["rv"].reshape(-1).at[flat].set(
        vals, mode="drop").reshape(spec.W, spec.cap)
    return dict(state, rk=rk, rv=rv, tail=state["tail"] + hist)


def _pop(spec: StepSpec, state, budget):
    jnp = _jnp()
    lens = state["tail"] - state["head"]
    take = jnp.minimum(budget, lens)                       # [W]
    iot = jnp.arange(spec.B, dtype=lens.dtype)
    idx = (state["head"][:, None] + iot[None, :]) % spec.cap
    wmask = iot[None, :] < take[:, None]                   # [W, B]
    wk = jnp.take_along_axis(state["rk"], idx, axis=1)
    wv = jnp.take_along_axis(state["rv"], idx, axis=1)
    return wk, wv, wmask, take, dict(state, head=state["head"] + take)


def _pop_flat(spec: StepSpec, state, budget):
    """Pop each ring's ``take = min(budget, len)`` into one flat window of
    ``spec.P`` lanes: worker ``w`` owns lanes ``bounds[w] <= j <
    bounds[w + 1]`` (``bounds`` the exclusive cumsum of ``take``), in
    stream order, and lanes past ``bounds[W]`` are dead.  The host sizes
    ``P`` to cover every take.  Returns the flat carry ``(keys, vals,
    live, wid, bounds)``, ``take`` and the popped state."""
    jnp = _jnp()
    lens = state["tail"] - state["head"]
    take = jnp.minimum(budget, lens)                       # [W]
    bounds = jnp.concatenate([jnp.zeros((1,), lens.dtype),
                              jnp.cumsum(take)])           # [W + 1]
    start = bounds[:-1]
    lane = jnp.arange(spec.P, dtype=lens.dtype)
    # A marker at each worker's first lane, summed up to a lane, names the
    # lane's worker; a worker that takes nothing shares the next one's
    # start, and a start past the window is dropped.
    marks = jnp.zeros((spec.P,), jnp.int32).at[start].add(1, mode="drop")
    wid = jnp.cumsum(marks) - 1
    slot = (state["head"][wid] + lane - start[wid]) % spec.cap
    flat = wid.astype(lens.dtype) * spec.cap + slot
    wk = state["rk"].reshape(-1)[flat]
    wv = state["rv"].reshape(-1)[flat]
    live = lane < bounds[-1]
    return ((wk, wv, live, wid, bounds), take,
            dict(state, head=state["head"] + take))


def _rows_as_flat(ok, ov, keep):
    """A ``[W, n]`` block (a probe's expansion) as a flat carry: row ``w``
    is worker ``w``'s segment."""
    jnp = _jnp()
    W, n = keep.shape
    wid = jnp.arange(W * n, dtype=jnp.int32) // n
    bounds = jnp.arange(W + 1, dtype=jnp.int64) * n
    return ok.reshape(-1), ov.reshape(-1), keep.reshape(-1), wid, bounds


def _segment_counts(keep, bounds):
    """``(before, counts)`` of a flat carry's kept lanes: ``before[j]``
    kept lanes precede lane ``j`` (``[n + 1]``), ``counts[w]`` lie in
    worker ``w``'s segment."""
    jnp = _jnp()
    before = jnp.concatenate([jnp.zeros((1,), bounds.dtype),
                              jnp.cumsum(keep, dtype=bounds.dtype)])
    return before, before[bounds[1:]] - before[bounds[:-1]]


def _fold_stats(spec: StepSpec, state, keys, valid):
    if not spec.track_stats:
        return state
    one = valid.astype(state["arrived"].dtype)
    return dict(state,
                arrived=state["arrived"].at[keys].add(one),
                totals=state["totals"].at[keys].add(one))


def _ingest(spec: StepSpec, consts, state, chunk):
    """Route + ring-scatter one staged chunk (the partition half)."""
    if spec.kind == "rows" and spec.use_kernel and spec.any_split:
        return _ingest_rows_kernel(spec, consts, state, chunk)
    keys, vals, valid = chunk
    dest, rank, hist, count = _advance_and_route(
        spec, consts, state["count"], keys, valid)
    state = _push(spec, dict(state, count=count), keys, vals, valid,
                  dest, rank, hist)
    return _fold_stats(spec, state, keys, valid), hist


def _ingest_rows_kernel(spec: StepSpec, consts, state, chunk):
    """Row-state ingest through the fused Pallas ``partition_scatter_fold``
    kernel (``device_use_kernel=True``, split table): one kernel pass
    yields dest + within-destination rank + histogram for the ring
    scatter *and* the chunk's per-key live-lane counts, which are exactly
    the key-arrival stats fold — a monitored build/sort edge pays no
    separate stats pass.  Destinations are bit-identical to the jnp path
    (the canonical rule; one-hot rows resolve to their primary under the
    saturated CDF for every u < 1)."""
    import importlib
    jnp = _jnp()
    keys, vals, valid = chunk
    counters, new_count = _split_counters(spec, consts, state["count"],
                                          keys, valid)
    kpart = importlib.import_module("repro.kernels.partition")
    kdest, krank, khist, kcnt, _ = kpart.partition_scatter_fold(
        keys.astype(jnp.int32), counters.astype(jnp.int32),
        _as_f64(vals).astype(jnp.float32), consts["cdf"],
        valid=valid.astype(jnp.int32), cdf=consts["cdf"])
    dest = kdest.astype(keys.dtype)
    rank = krank.astype(keys.dtype)
    hist = khist.astype(state["count"].dtype)
    state = _push(spec, dict(state, count=new_count), keys, vals, valid,
                  dest, rank, hist)
    if spec.track_stats:
        cnt = kcnt.astype(state["arrived"].dtype)
        state = dict(state, arrived=state["arrived"] + cnt,
                     totals=state["totals"] + cnt)
    return state, hist


def _push_placed(spec: StepSpec, state, carry):
    """Ring-scatter a *pre-placed* flat carry: worker ``w``'s kept lanes
    append to ring ``w`` in lane (stream) order.  This is the fused
    chain's ingest — the records were placed by the upstream edge's
    partition, and routing-token equality proves edge B would place them
    identically, so within-destination rank degenerates to a segment
    cumsum and no partition runs at all.  Returns the state and the
    per-ring pushed counts."""
    jnp = _jnp()
    ok, ov, keep, wid, bounds = carry
    before, hist = _segment_counts(keep, bounds)
    rank = before[:-1] - before[bounds[wid]]
    pos = (state["tail"][wid] + rank) % spec.cap
    flat = jnp.where(keep, wid.astype(pos.dtype) * spec.cap + pos,
                     spec.W * spec.cap)
    rk = state["rk"].reshape(-1).at[flat].set(
        ok, mode="drop").reshape(spec.W, spec.cap)
    rv = state["rv"].reshape(-1).at[flat].set(
        ov, mode="drop").reshape(spec.W, spec.cap)
    hist = hist.astype(state["tail"].dtype)
    return dict(state, rk=rk, rv=rv, tail=state["tail"] + hist), hist


def _map_stage(spec: StepSpec, wk, wv, wmask):
    """Apply a Filter predicate / Project map to popped lanes of value
    bits; returns (out_keys, out_vals, keep)."""
    if spec.kind == "filter":
        keep = wmask & spec.fn(wk, _as_f64(wv)).astype(bool)
        ok, ov = wk, wv
    else:                                   # project
        ok, ov = spec.fn(wk, _as_f64(wv))
        ok = ok.astype(wk.dtype)
        ov = _as_bits(ov)
        keep = wmask
    return ok, ov, keep


def _expand_stage(spec: StepSpec, state, wk, wv, wmask):
    """Hash-join probe expansion of a popped ``[W, B]`` window: each live
    lane emitted ``mcounts[w, key]`` times (owned + scattered build rows
    summed) into a padded ``[W, B * M]`` block, lanes in stream order —
    the device twin of ``np.repeat(keys, matches)`` per worker.  ``M``
    bounds the per-tuple fanout (max match count, static), so the emit
    buffer covers the worst case and nothing ever carries over."""
    import importlib
    kref = importlib.import_module("repro.kernels.ref")
    return kref.match_expand(wk, wv, wmask, state["mcounts"],
                             spec.B * spec.M)


def _fold_rows(spec: StepSpec, consts, state, wk, wv, wmask, take):
    """Segment-append of a popped ``[W, B]`` window into the device row
    store (the HashJoinBuild / RangeSort tail): lane *j* of worker *w*
    lands at ``row_len[w] + rank_j`` (within-pop arrival rank) carrying
    its key and an owned flag frozen at fold time — the device mirror of
    ``_RowStateOp._append_segments``'s owned/scattered routing, kept as
    one flat arrival-order log and regrouped by key only at host
    boundaries."""
    jnp = _jnp()
    dt = state["rlen"].dtype
    wid = jnp.arange(spec.W, dtype=wk.dtype)[:, None]
    owned = consts["owner"][wk] == wid
    kin = wmask.astype(dt)
    rank = jnp.cumsum(kin, axis=1) - kin
    pos = state["rlen"][:, None] + rank
    flat = jnp.where(wmask, wid.astype(dt) * spec.rcap + pos,
                     spec.W * spec.rcap).reshape(-1)
    bk = state["bk"].reshape(-1).at[flat].set(
        wk.reshape(-1), mode="drop").reshape(spec.W, spec.rcap)
    bv = state["bv"].reshape(-1).at[flat].set(
        wv.reshape(-1), mode="drop").reshape(spec.W, spec.rcap)
    bo = state["bo"].reshape(-1).at[flat].set(
        (wmask & owned).reshape(-1), mode="drop").reshape(spec.W, spec.rcap)
    return dict(state, bk=bk, bv=bv, bo=bo, rlen=state["rlen"] + take)


def _fold_popped(spec: StepSpec, consts, state, wk, wv, wmask):
    """Owned/scattered keyed fold of a popped ``[W, B]`` window (the
    GroupByAgg tail of the fold and chain steps)."""
    jnp = _jnp()
    wid = jnp.arange(spec.W, dtype=wk.dtype)[:, None]
    owned = (consts["owner"][wk] == wid) if spec.may_scatter else wmask
    m_own = wmask & owned
    m_scat = wmask & ~owned
    flat = (wid * spec.K + wk).reshape(-1)
    wvf = _as_f64(wv).reshape(-1)

    def fold(cnt, sm, pres, m):
        mf = m.reshape(-1)
        cnt = cnt.reshape(-1).at[flat].add(
            mf.astype(cnt.dtype)).reshape(spec.W, spec.K)
        sm = sm.reshape(-1).at[flat].add(
            jnp.where(mf, wvf, 0.0)).reshape(spec.W, spec.K)
        pres = pres.reshape(-1).at[flat].max(mf).reshape(spec.W, spec.K)
        return cnt, sm, pres

    cnt, sm, pres = fold(state["counts"], state["sums"],
                         state["present"], m_own)
    scnt, ssm, spres = fold(state["scat_counts"], state["scat_sums"],
                            state["scat_present"], m_scat)
    return dict(state, counts=cnt, sums=sm, present=pres,
                scat_counts=scnt, scat_sums=ssm, scat_present=spres)


def _named(kind: str):
    """Name a step function ``<kind>_step``, so that its jitted module reads
    ``jit_<kind>_step`` in a profiler trace."""
    def name(fn):
        fn.__name__ = fn.__qualname__ = f"{kind}_step"
        return fn
    return name


def _make_step_fold(kind: str):
    import jax

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
    @_named(kind)
    def step(spec: StepSpec, consts, state, chunk, budget):
        _note_trace("fold", spec, (consts, state, chunk, budget))
        jnp = _jnp()
        if chunk is not None:
            state, hist = _ingest(spec, consts, state, chunk)
        else:
            hist = jnp.zeros((spec.W,), state["tail"].dtype)
        wk, wv, wmask, take, state = _pop(spec, state, budget)
        if spec.kind == "rows":
            state = _fold_rows(spec, consts, state, wk, wv, wmask, take)
        else:
            state = _fold_popped(spec, consts, state, wk, wv, wmask)
        return state, (hist, take)

    return step


def _make_step_map(kind: str):
    import jax

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
    @_named(kind)
    def step(spec: StepSpec, consts, state, chunk, budget):
        _note_trace("map", spec, (consts, state, chunk, budget))
        jnp = _jnp()
        if chunk is not None:
            state, hist = _ingest(spec, consts, state, chunk)
        else:
            hist = jnp.zeros((spec.W,), state["tail"].dtype)
        if spec.kind == "probe":
            wk, wv, wmask, take, state = _pop(spec, state, budget)
            ok, ov, keep = _expand_stage(spec, state, wk, wv, wmask)
            emitted = keep.sum(axis=1, dtype=take.dtype)
            ok, ov, keep = ok.reshape(-1), ov.reshape(-1), keep.reshape(-1)
        else:
            (wk, wv, live, _, bounds), take, state = _pop_flat(
                spec, state, budget)
            ok, ov, keep = _map_stage(spec, wk, wv, live)
            emitted = _segment_counts(keep, bounds)[1].astype(take.dtype)
        return state, (ok, ov, keep), (hist, take, emitted)

    return step


def _make_step_chain(kind: str):
    """One jitted dispatch advancing a whole fused chain: the head's
    ingest runs the chain's *single* partition + scatter; every later
    stage receives its predecessor's pre-placed survivors as a flat
    carry (:func:`_push_placed` — no placement), pops its own budget, and
    maps / folds.  Per-stage ``(hist, take, emitted)`` metrics feed the
    same host mirrors the per-edge dispatches keep."""
    import jax

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
    @_named(kind)
    def step(specs, consts_t, states_t, chunk, budgets):
        _note_trace("chain", specs, (consts_t, states_t, chunk, budgets))
        jnp = _jnp()
        states = list(states_t)
        metrics = []
        carry = None
        for i, spec in enumerate(specs):
            consts = consts_t[i]
            st = states[i]
            if i == 0:
                if chunk is not None:
                    st, hist = _ingest(spec, consts, st, chunk)
                else:
                    hist = jnp.zeros((spec.W,), st["tail"].dtype)
            else:
                ok, ov, keep, _, bounds = carry
                st = _fold_stats(spec, st, ok, keep)
                if spec.kind == "sink":
                    hist = _segment_counts(keep, bounds)[1].astype(
                        st["count"].dtype)
                    states[i] = dict(
                        st,
                        counts=st["counts"].at[ok].add(
                            keep.astype(st["counts"].dtype)),
                        sums=st["sums"].at[ok].add(
                            jnp.where(keep, _as_f64(ov), 0.0)))
                    metrics.append((hist, None, None))
                    carry = None
                    continue
                st, hist = _push_placed(spec, st, carry)
            carry = None
            if spec.kind in MAP_KINDS:
                (wk, wv, live, wid, bounds), take, st = _pop_flat(
                    spec, st, budgets[i])
                ok, ov, keep = _map_stage(spec, wk, wv, live)
                carry = (ok, ov, keep, wid, bounds)
                emitted = _segment_counts(keep, bounds)[1].astype(take.dtype)
            else:
                wk, wv, wmask, take, st = _pop(spec, st, budgets[i])
                emitted = None
                if spec.kind == "probe":
                    ok, ov, keep = _expand_stage(spec, st, wk, wv, wmask)
                    carry = _rows_as_flat(ok, ov, keep)
                    emitted = keep.sum(axis=1, dtype=take.dtype)
                elif spec.kind == "rows":       # build / sort tail
                    st = _fold_rows(spec, consts, st, wk, wv, wmask, take)
                else:                           # fold tail
                    st = _fold_popped(spec, consts, st, wk, wv, wmask)
            metrics.append((hist, take, emitted))
            states[i] = st
        # a map or probe tail emits its lanes downstream
        out = None if carry is None else carry[:3]
        return tuple(states), out, tuple(metrics)

    return step


def _make_step_sink(kind: str):
    import jax

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(2,))
    @_named(kind)
    def step(spec: StepSpec, consts, state, chunk):
        _note_trace("sink", spec, (consts, state, chunk))
        jnp = _jnp()
        keys, bits, valid = chunk
        vals = _as_f64(bits)
        state = _fold_stats(spec, state, keys, valid)
        if spec.use_kernel:
            # Fused partition_scatter_fold kernel: per-key counts + sums
            # in the same pass that certifies dest/hist (W == 1, so the
            # one-column CDF routes everything to worker 0).
            import importlib
            kpart = importlib.import_module("repro.kernels.partition")
            ones = jnp.ones((spec.K, 1), jnp.float32)
            _, _, _, kcnt, ksm = kpart.partition_scatter_fold(
                keys.astype(jnp.int32), jnp.zeros(keys.shape, jnp.int32),
                vals.astype(jnp.float32), ones,
                valid=valid.astype(jnp.int32), cdf=ones)
            counts = state["counts"] + kcnt.astype(state["counts"].dtype)
            sums = state["sums"] + ksm.astype(state["sums"].dtype)
        else:
            one = valid.astype(state["counts"].dtype)
            counts = state["counts"].at[keys].add(one)
            sums = state["sums"].at[keys].add(jnp.where(valid, vals, 0.0))
        return dict(state, counts=counts, sums=sums), ()

    return step


_STEP_CACHE = {}


def _step_for(kind: str):
    """One persistent jitted step per kind, named ``<kind>_step``; the
    cache is module-global so repeated engine builds retrace only on a
    genuinely new :class:`StepSpec` (shape growth, rewrite arming, new
    user fn)."""
    if kind not in _STEP_CACHE:
        _STEP_CACHE[kind] = {"fold": _make_step_fold,
                             "rows": _make_step_fold,
                             "filter": _make_step_map,
                             "project": _make_step_map,
                             "probe": _make_step_map,
                             "sink": _make_step_sink,
                             "chain": _make_step_chain,
                             "ctrl": _make_ctrl_step}[kind](kind)
    return _STEP_CACHE[kind]


def _pow2(n: int) -> int:
    p = 256
    while p < n:
        p <<= 1
    return p


# --------------------------------------------------------------------- #
# The device-resident skew controller (in-dispatch control plane)         #
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class CtrlSpec:
    """Static half of the jitted controller step (hashable; a changed
    spec retraces once, like :class:`StepSpec` for the data plane)."""

    W: int                     # workers
    K: int                     # key space
    window: int                # estimator sample window
    R: int                     # observation-log capacity (windows)
    KMAX: int                  # widest covered window (tick-loop bound)
    eta: float
    metric_period: int
    initial_delay: int
    adaptive_tau: bool
    eps_lower: float
    eps_upper: float
    tau_increase: float
    max_tau_adjustments: int
    catchup_tolerance: float
    retire_window: int         # 0 = never retire
    enable_phase1: bool
    horizon: float             # tracker prediction horizon (tuples)


def _make_ctrl_step(kind: str):
    """Build the jitted ``controller_step``.

    One call covers one super-tick window ``[t0, t0+k)``: for every
    metric round inside it, replay the host controller's exact round —
    tracker update, mitigation state machine, adaptive tau, detection,
    and the phase-1/phase-2 routing rewrites — against the device-held
    controller state, bumping ``epoch`` whenever the weights changed and
    rebuilding the routing consts once at the end.  Every float
    reduction goes through the canonical sequential order
    (:func:`repro.core.estimator.seq_sum` / ``kernels.ref.seq_sum_vec``)
    so decisions are bit-identical to :class:`ReshapeController`.

    A round's work follows its live slots, not ``W``: the mitigation
    advance visits only active mitigations, the helper assignment only
    skewed workers, and each visit builds only the rewrite it applies.
    The step returns ``(state, drained arrivals, visits)``, where
    ``visits`` counts the slots visited over every round of the window.
    """
    import jax
    jnp = _jnp()
    from ..kernels import ref as kref

    PH1 = 2                    # MitigationPhase.PHASE_ONE.value
    PH2 = 3                    # MitigationPhase.PHASE_TWO.value

    @functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(1,))
    @_named(kind)
    def ctrl_step(cs: CtrlSpec, c, arrived, phi, t0, k, tuples_left, rate):
        _note_trace("ctrl", cs, (c, arrived, phi, t0, k,
                                 tuples_left, rate))
        i32 = jnp.int32
        W = cs.W
        idx = jnp.arange(W, dtype=jnp.int64)
        BIG = jnp.iinfo(jnp.int32).max

        def est_stats(c, w):
            return kref.ring_mean_stderr(
                c["obs"][w], c["obs_n"][w], c["obs_pos"][w])

        def predicted_shares(c):
            means, _ = jax.vmap(kref.ring_mean_stderr)(
                c["obs"], c["obs_n"], c["obs_pos"])
            total = kref.seq_sum_vec(means)
            return jnp.where(total <= 0, 1.0 / W,
                             means / jnp.where(total <= 0, 1.0, total))

        def apply_phase1(c, s, h):
            # plan_phase1 (full partition): every key owned by S with any
            # S-mass hands that mass to H (row sums preserved).
            w = c["weights"]
            col_s = w[:, s]
            col_h = w[:, h]
            sel = (c["owner"] == s.astype(c["owner"].dtype)) & (col_s > 0.0)
            new_w = (w.at[:, h].set(jnp.where(sel, col_h + col_s, col_h))
                      .at[:, s].set(jnp.where(sel, 0.0, col_s)))
            return new_w, jnp.any(sel)

        def apply_phase2(c, s, h):
            # plan_phase2 (SBR, single helper): every key owned by S gets
            # the same fresh row [S: 1-r, H: r] from the predicted shares.
            shares = predicted_shares(c)
            r = kref.phase2_fraction(shares[s], shares[h])
            row = (jnp.zeros(W, c["weights"].dtype)
                   .at[s].set(1.0 - r).at[h].add(r))
            owned = c["owner"] == s.astype(c["owner"].dtype)
            new_w = jnp.where(owned[:, None], row[None, :], c["weights"])
            return new_w, jnp.any(owned)

        def keep_weights(c, s, h):
            return c["weights"], jnp.bool_(False)

        def round_fn(st):
            c, arr, visits = st
            # ---- tracker.update (one metric round) ---------------------
            total = kref.seq_sum_vec(arr)
            has = total > 0
            scale = cs.horizon / jnp.where(has, total, 1.0)
            obs = jnp.where(has,
                            c["obs"].at[idx, c["obs_pos"]].set(arr * scale),
                            c["obs"])
            obs_n = jnp.where(has,
                              jnp.minimum(c["obs_n"] + 1, cs.window),
                              c["obs_n"])
            obs_pos = jnp.where(has, (c["obs_pos"] + 1) % cs.window,
                                c["obs_pos"])
            c = dict(c, obs=obs, obs_n=obs_n, obs_pos=obs_pos)
            arr = jnp.zeros_like(arr)   # the adapter drains every round

            # ---- _advance_mitigations (insertion order == seq order) ---
            # One iteration per live slot: the loop runs while an active
            # slot is unprocessed (``retire`` clears only the slot just
            # processed), so a dead slot costs nothing.
            def adv_cond(st):
                c, processed, _ = st
                return jnp.any(c["mit_active"] & ~processed)

            def adv_body(st):
                c, processed, visits = st
                s = jnp.argmin(jnp.where(c["mit_active"] & ~processed,
                                         c["mit_seq"], BIG))
                h = c["mit_helper"][s]
                phase = c["mit_phase"][s]
                q_s = phi[s]
                q_h = phi[h]
                top = jnp.maximum(jnp.maximum(q_s, q_h), 1.0)
                p1_to_p2 = ((phase == PH1)
                            & (q_h >= q_s - cs.catchup_tolerance * top))
                in_p2 = phase == PH2
                s_ahead = (q_s >= cs.eta) & (q_s - q_h >= c["tau"])
                h_ahead = (q_h >= cs.eta) & (q_h - q_s >= c["tau"])
                calm = in_p2 & ~(s_ahead | h_ahead)
                new_calm = c["mit_calm"][s] + 1
                retire = (calm & (cs.retire_window > 0)
                          & (new_calm >= cs.retire_window))
                div = in_p2 & (s_ahead | h_ahead)
                # adaptive tau on divergence (eps BEFORE the resets)
                _, e_s = est_stats(c, s)
                _, e_h = est_stats(c, h)
                eps = jnp.maximum(e_s, e_h)
                inc = (div & cs.adaptive_tau & jnp.isfinite(eps)
                       & (eps > cs.eps_upper)
                       & (c["tau_adj"] < cs.max_tau_adjustments))
                c = dict(c,
                         tau=jnp.where(inc, c["tau"] + cs.tau_increase,
                                       c["tau"]),
                         tau_adj=c["tau_adj"] + inc.astype(i32))
                # reset_samples([s, h]) on a new iteration
                obs_n2 = c["obs_n"].at[s].set(
                    jnp.where(div, 0, c["obs_n"][s]))
                obs_n2 = obs_n2.at[h].set(jnp.where(div, 0, obs_n2[h]))
                c = dict(c, obs_n=obs_n2)
                start_p1 = div & s_ahead
                start_p2 = (div & ~s_ahead) | p1_to_p2
                if not cs.enable_phase1:
                    start_p2 = start_p2 | start_p1
                    start_p1 = jnp.zeros_like(start_p1)
                # Build only the rewrite that fires (phase 2 reads the
                # post-reset shares).
                branch = jnp.where(start_p1, 1, jnp.where(start_p2, 2, 0))
                new_w, changed = jax.lax.switch(
                    branch, (keep_weights, apply_phase1, apply_phase2),
                    c, s, h)
                bumped = (branch > 0) & changed
                c = dict(
                    c,
                    weights=new_w,
                    epoch=c["epoch"] + bumped.astype(i32),
                    mit_phase=c["mit_phase"].at[s].set(
                        jnp.where(start_p1, i32(PH1),
                                  jnp.where(start_p2, i32(PH2),
                                            c["mit_phase"][s]))),
                    mit_calm=c["mit_calm"].at[s].set(
                        jnp.where(calm, new_calm.astype(i32),
                                  jnp.where(div, i32(0),
                                            c["mit_calm"][s]))),
                    mit_active=c["mit_active"].at[s].set(
                        c["mit_active"][s] & ~retire),
                )
                return c, processed.at[s].set(True), visits + 1

            c, _, visits = jax.lax.while_loop(
                adv_cond, adv_body, (c, jnp.zeros(W, bool), visits))

            # ---- _detect ----------------------------------------------
            helper_busy = (jnp.zeros(W, i32).at[c["mit_helper"]]
                           .add(c["mit_active"].astype(i32))) > 0
            busy = c["mit_active"] | helper_busy
            free = ~busy
            nfree = jnp.sum(free.astype(i32))
            s0 = jnp.argmax(jnp.where(free, phi, -jnp.inf))
            h0 = jnp.argmin(jnp.where(free, phi, jnp.inf))
            _, e_s0 = est_stats(c, s0)
            _, e_h0 = est_stats(c, h0)
            eps0 = jnp.maximum(e_s0, e_h0)
            enabled = (cs.adaptive_tau
                       & (c["tau_adj"] < cs.max_tau_adjustments))
            t_new, t_chg, t_dec = kref.adjust_tau(
                phi[s0], phi[h0], eps0, c["tau"], eta=cs.eta,
                eps_lower=cs.eps_lower, eps_upper=cs.eps_upper,
                tau_increase=cs.tau_increase, enabled=enabled)
            app = (nfree >= 2) & jnp.isfinite(eps0)
            detect_tau = jnp.where(app & t_dec, t_new, c["tau"])
            c = dict(c,
                     tau=jnp.where(app & t_chg, t_new, c["tau"]),
                     tau_adj=c["tau_adj"] + (app & t_chg).astype(i32))
            # the skewed set: free workers >= eta whose gap to the free
            # minimum (excluding themselves) reaches detect_tau
            minf = jnp.where(free, phi, jnp.inf)
            i1 = jnp.argmin(minf)
            m1 = minf[i1]
            m2 = jnp.min(jnp.where(free & (idx != i1), phi, jnp.inf))
            min_excl = jnp.where(idx == i1, m2, m1)
            skewed = free & (phi >= cs.eta) & (phi - min_excl >= detect_tau)
            shares = jax.lax.cond(jnp.any(skewed), predicted_shares,
                                  lambda c: jnp.zeros(W, c["obs"].dtype), c)
            L = tuples_left

            # One iteration per skewed worker, hottest first.
            def asg_cond(st):
                _, _, processed, _ = st
                return jnp.any(skewed & ~processed)

            def asg_body(st):
                c, taken, processed, visits = st
                s = jnp.argmax(jnp.where(skewed & ~processed, phi, -jnp.inf))
                cands = (free & ~taken & (phi[s] - phi >= detect_tau)
                         & (idx != s))
                ncand = jnp.sum(cands.astype(i32))
                # choose_helpers, max_helpers=1: lexicographic min by
                # (f_hat, phi, index) — the host's stable double sort
                f_m = jnp.where(cands, shares, jnp.inf)
                bf = jnp.min(f_m)
                tie = cands & (shares == bf)
                bp = jnp.min(jnp.where(tie, phi, jnp.inf))
                h = jnp.argmax(tie & (phi == bp))
                f_s = shares[s]
                f_h = shares[h]
                lr_max = (f_s - (f_s + f_h) / 2.0) * L
                future = jnp.maximum(L, 0.0) * f_s    # M = 0 (inf rate)
                chi = jnp.minimum(lr_max, future)
                accept = (ncand > 0) & (chi >= -1e-12)
                # all of s's candidates become taken (host assign_helpers)
                taken = taken | cands
                if cs.enable_phase1:
                    apply, ph = apply_phase1, i32(PH1)
                else:
                    apply, ph = apply_phase2, i32(PH2)
                w_new, changed = jax.lax.cond(accept, apply, keep_weights,
                                              c, s, h)
                c = dict(
                    c,
                    weights=w_new,
                    epoch=c["epoch"] + (accept & changed).astype(i32),
                    mit_active=c["mit_active"].at[s].set(
                        c["mit_active"][s] | accept),
                    mit_helper=c["mit_helper"].at[s].set(
                        jnp.where(accept, h.astype(i32),
                                  c["mit_helper"][s])),
                    mit_phase=c["mit_phase"].at[s].set(
                        jnp.where(accept, ph, c["mit_phase"][s])),
                    mit_calm=c["mit_calm"].at[s].set(
                        jnp.where(accept, i32(0), c["mit_calm"][s])),
                    mit_seq=c["mit_seq"].at[s].set(
                        jnp.where(accept, c["seq_next"], c["mit_seq"][s])),
                    seq_next=c["seq_next"] + accept.astype(i32),
                )
                return c, taken, processed.at[s].set(True), visits + 1

            taken0 = busy | skewed      # skewed workers can't help
            c, _, _, visits = jax.lax.while_loop(
                asg_cond, asg_body,
                (c, taken0, jnp.zeros(W, bool), visits))
            return c, arr, visits

        # Owner-attributed arrivals for this window (integer adds:
        # order-independent, exact) + one observation-log entry so the
        # boundary drain can replay the window through the host twin.
        arr0 = (jnp.zeros(W, c["weights"].dtype)
                .at[c["owner"]].add(arrived.astype(c["weights"].dtype)))
        c = dict(c,
                 log_phi=c["log_phi"].at[c["log_n"]].set(phi),
                 log_arr=c["log_arr"].at[c["log_n"]].set(arr0),
                 log_n=c["log_n"] + 1)
        epoch0 = c["epoch"]

        def tick_body(i, st):
            t = t0 + i
            fire = ((i < k) & (t >= cs.initial_delay)
                    & (jnp.remainder(t - cs.initial_delay,
                                     cs.metric_period) == 0))
            return jax.lax.cond(fire, round_fn, lambda st: st, st)

        c, _, visits = jax.lax.fori_loop(0, cs.KMAX, tick_body,
                                         (c, arr0, jnp.int32(0)))

        def rebuild(c):
            cdf, primary, is_split = kref.routing_consts(c["weights"])
            return dict(c, cdf=cdf, primary=primary, is_split=is_split)

        c = jax.lax.cond(c["epoch"] != epoch0, rebuild, lambda c: c, c)
        return c, jnp.zeros_like(arrived), visits

    return ctrl_step


class _ReplayAdapter:
    """Adapter shim for the boundary drain: replays the device-logged
    observations of past windows through the host :class:`ReshapeController`
    so the host twin re-derives (bit-identically) every decision the
    device controller made in-dispatch.  ``key_shares`` is decision-
    neutral for the eligible configuration (SBR phase 2 ignores it; full-
    partition phase 1 uses it only for the unlogged ``moved`` field)."""

    def __init__(self, base):
        self._base = base
        self.num_workers = base.num_workers
        self.traits = base.traits
        self.routing = base.routing
        self._phi = np.zeros(base.num_workers)
        self._arr = np.zeros(base.num_workers)
        self._drained = True
        self._left = 0.0
        self._rate = 0.0

    def set_window(self, phi, arr, left, rate):
        self._phi = np.asarray(phi, dtype=np.float64)
        self._arr = np.asarray(arr, dtype=np.float64).copy()
        self._drained = False
        self._left = float(left)
        self._rate = float(rate)

    def workloads(self):
        return self._phi.copy()

    def arrivals_by_owner(self):
        if self._drained:
            return np.zeros(self.num_workers)
        self._drained = True
        return self._arr

    def key_shares(self, worker):
        return {}

    def state_units(self, worker, mode):
        return 0.0

    def begin_migration(self, skewed, helpers, mode):
        return None

    def tuples_left(self):
        return self._left

    def processing_rate(self):
        return self._rate


class DeviceController:
    """Device-resident twin of one armed :class:`ReshapeController`.

    While active, the engine stops host-stepping the controller: each
    super-tick calls :meth:`super_tick`, which runs every covered metric
    round inside one jitted ``controller_step`` against device-held
    state, rewriting the routing consts in place (no readback beyond a
    one-scalar epoch probe).  At every materialization boundary
    :meth:`drain` replays the device-logged windows through the host
    controller — the bit-exact oracle and arbitration point — then
    compares the host-derived routing consts against the device's and
    lets the host win on any mismatch.  Anything that mutates host keyed
    state (migrations, merges, demotions) deactivates the device
    controller; the host path resumes seamlessly from the drained twin.
    """

    #: observation-log capacity: drain when this many windows accumulate.
    LOG_CAP = 64

    def __init__(self, rt: "DeviceOpRuntime", controller):
        self.rt = rt
        self.host = controller
        self.active = False
        self.reason = None          # why deactivated (None while active)
        self.cstate = None
        self.spec: Optional[CtrlSpec] = None
        self.meta: List[tuple] = []  # (t0, k, tuples_left, rate) per window
        self.epoch_host = 0          # device epoch after the last step
        self.epoch_synced = 0        # device epoch at the last drain
        self._last_tick = controller._tick

    # ---- eligibility --------------------------------------------------
    @staticmethod
    def ineligible_reason(controller, rt) -> Optional[str]:
        """None iff this (controller, runtime) pair may run in-dispatch.

        The device twin replicates exactly the paper's default control
        path: SBR + SCATTERED (rewrites move no state), single helper,
        full-partition phase 1, zero control delay, instant migration.
        Anything else — MARKERS/REPLICATE strategies, SBK/SBP modes,
        multi-helper, finite migration rates — stays on the host path.
        """
        from ..core.controller import ReshapeController
        from ..core.state_migration import MigrationStrategy
        from ..core.types import TransferMode
        if type(controller) is not ReshapeController:
            return "controller subclass"
        cfg = controller.cfg
        if controller.mode is not TransferMode.SBR:
            return f"transfer mode {controller.mode.value}"
        if controller.strategy is not MigrationStrategy.SCATTERED:
            return f"strategy {controller.strategy}"
        if cfg.control_delay_ticks != 0:
            return "control delay"
        if getattr(cfg, "pressure_rounds", False):
            # Eager pressure-triggered rounds fire off the metric grid;
            # the jitted ctrl_step only covers grid-aligned rounds.
            return "pressure rounds"
        if cfg.max_helpers != 1:
            return "multi-helper"
        if not cfg.phase1_full_partition:
            return "partial-key phase 1"
        if cfg.migration_rate != float("inf"):
            return "finite migration rate"
        if cfg.pinned_helpers:
            return "pinned helpers"
        if cfg.adaptive_tau and (cfg.eps_lower is None
                                 or cfg.eps_upper is None):
            return "unbounded adaptive tau"
        if rt.kind == "sink":
            return "sink"
        if rt.W < 2:
            return "single worker"
        if _ctrl_device() is None:
            return "no CPU backend for the controller step"
        return None

    @property
    def routing_dirty(self) -> bool:
        """True while the device consts carry rewrites the host table has
        not seen yet (between an in-dispatch rewrite and the next drain)."""
        return self.epoch_host != self.epoch_synced

    # ---- arming / state build -----------------------------------------
    def arm(self) -> bool:
        # Scattered-arrival masking must be on from the first armed
        # dispatch: an in-dispatch rewrite cannot retroactively flip it.
        # On one-hot tables the mask is the identity, so arming early is
        # bit-neutral.
        self.rt.op.may_scatter = True
        return self._build()

    def _build(self) -> bool:
        """(Re)build the device controller state from the host twin.
        Returns False (deactivating) when the host state is not
        representable on the device — the recorded demotion rules."""
        host = self.host
        cfg = host.cfg
        rt = self.rt
        from ..core.types import MitigationPhase
        for m in host.mitigations.values():
            if (len(m.helpers) != 1
                    or m.phase not in (MitigationPhase.PHASE_ONE,
                                       MitigationPhase.PHASE_TWO)):
                self.deactivate("non-reformable mitigation", drain=False)
                return False
        if host._pending:
            self.deactivate("pending control messages", drain=False)
            return False
        retire = (cfg.retire_after if cfg.retire_after is not None
                  else cfg.sample_window)
        self.spec = CtrlSpec(
            W=rt.W, K=rt.K, window=int(cfg.sample_window),
            R=self.LOG_CAP, KMAX=max(int(rt.engine.batch_ticks), 1),
            eta=float(cfg.eta),
            metric_period=max(1, int(cfg.metric_period)),
            initial_delay=int(cfg.initial_delay_ticks),
            adaptive_tau=bool(cfg.adaptive_tau),
            eps_lower=float(cfg.eps_lower
                            if cfg.eps_lower is not None else -np.inf),
            eps_upper=float(cfg.eps_upper
                            if cfg.eps_upper is not None else np.inf),
            tau_increase=float(cfg.tau_increase),
            max_tau_adjustments=int(cfg.max_tau_adjustments),
            catchup_tolerance=float(cfg.catchup_tolerance),
            retire_window=int(retire),
            enable_phase1=bool(cfg.enable_phase1),
            horizon=float(host.tracker.horizon))
        import jax
        table = rt.routing
        window = int(cfg.sample_window)
        obs = np.zeros((rt.W, window))
        obs_n = np.zeros(rt.W, np.int32)
        obs_pos = np.zeros(rt.W, np.int32)
        for w, est in enumerate(host.tracker._estimators):
            vals = list(est._obs)
            obs[w, :len(vals)] = vals
            obs_n[w] = len(vals)
            obs_pos[w] = len(vals) % window
        mit_active = np.zeros(rt.W, bool)
        mit_helper = np.zeros(rt.W, np.int32)
        mit_phase = np.zeros(rt.W, np.int32)
        mit_calm = np.zeros(rt.W, np.int32)
        mit_seq = np.zeros(rt.W, np.int32)
        for seq, (s, m) in enumerate(host.mitigations.items()):
            mit_active[s] = True
            mit_helper[s] = m.helpers[0]
            mit_phase[s] = int(m.phase.value)
            mit_calm[s] = int(m.calm_rounds)
            mit_seq[s] = seq
        with _x64():
            rt._refresh_consts(force=True)
            # Host arrays go straight to the controller's device: a detour
            # through the data device would round the float64 ones.
            self.cstate = jax.device_put(dict(
                weights=np.array(table.weights, np.float64),
                cdf=rt.consts["cdf"], primary=rt.consts["primary"],
                is_split=rt.consts["is_split"], owner=rt.consts["owner"],
                obs=obs, obs_n=obs_n, obs_pos=obs_pos,
                tau=np.float64(host.tau),
                tau_adj=np.int32(host.tau_adjustments),
                mit_active=mit_active, mit_helper=mit_helper,
                mit_phase=mit_phase, mit_calm=mit_calm, mit_seq=mit_seq,
                seq_next=np.int32(len(host.mitigations)),
                epoch=np.int32(0),
                log_phi=np.zeros((self.LOG_CAP, rt.W), np.float64),
                log_arr=np.zeros((self.LOG_CAP, rt.W), np.float64),
                log_n=np.int32(0)), _ctrl_device())
        self.meta = []
        self.epoch_host = self.epoch_synced = 0
        self._last_tick = host._tick
        self.active = True
        self.reason = None
        return True

    # ---- the per-super-tick in-dispatch step ---------------------------
    @obs.spanned("ctrl.super_tick")
    def super_tick(self, t0: int, k: int) -> None:
        host = self.host
        cfg = host.cfg
        rt = self.rt
        chaos = getattr(rt.engine, "chaos", None)
        if chaos is not None and not self._chaos_dispatch_ok(chaos):
            # Demoted drain-first; the engine's armed-controller branch
            # skipped the boundary sync for this window, so run it here
            # (the per-tick loop below the boundary will host-step).
            rt.sync_stats()
            return
        rt.flush_staged()       # boundary sends land before the rounds
        delay = int(cfg.initial_delay_ticks)
        period = max(1, int(cfg.metric_period))
        fired = [t for t in range(t0, t0 + k)
                 if t >= delay and (t - delay) % period == 0]
        self._last_tick = t0 + k - 1
        if not fired:
            return              # fast path: no metric round this window
        if len(self.meta) >= self.spec.R:
            self.drain()        # observation log full: reconcile first
        if k > self.spec.KMAX:
            self.spec = dataclasses.replace(self.spec, KMAX=int(k))
        left = float(host.adapter.tuples_left())
        rate = float(host.adapter.processing_rate())
        import jax
        step = _step_for("ctrl")
        with _x64():
            arrived = (rt.state["arrived"] if rt.state is not None
                       else np.zeros(rt.K, np.int64))
            cpu = _ctrl_device()
            arrived = jax.device_put(_readback(arrived, "ctrl.arrived"),
                                     cpu)
            # The step on the CPU backend, through the epoch read that
            # waits for it (a wait on the host, not a chip readback).
            with obs.span("ctrl.cpu_step"):
                c, drained, visits = step(
                    self.spec, self.cstate, arrived,
                    jax.device_put(np.asarray(rt.workloads(), np.float64),
                                   cpu),
                    np.int64(t0), np.int64(k),
                    np.float64(left), np.float64(rate))
                self.cstate = c
                dev = _data_device()
                if rt.state is not None:
                    rt.state["arrived"] = jax.device_put(drained, dev)
                rt.consts = jax.device_put(
                    dict(cdf=c["cdf"], primary=c["primary"],
                         is_split=c["is_split"], owner=c["owner"]), dev)
                self.epoch_host = int(np.asarray(c["epoch"]))
                # CPU-backend output, not a chip readback
                rt._n_split = int(np.count_nonzero(np.asarray(
                    c["is_split"])))
            # CPU-backend scalars, not chip readbacks.
            obs.count("ctrl.rounds", len(fired))
            obs.count("ctrl.slot_visits", int(np.asarray(visits)))
        self.meta.append((t0, k, left, rate))
        host.rounds_on_device += len(fired)

    # ---- boundary drain: mirror decisions into the host twin -----------
    @obs.spanned("ctrl.drain")
    def drain(self) -> None:
        if not self.active:
            return
        host = self.host
        rt = self.rt
        table = rt.routing
        meta, self.meta = self.meta, []
        if not meta:
            if self._last_tick > host._tick:
                host._tick = self._last_tick
            return
        n = int(np.asarray(self.cstate["log_n"]))
        assert n == len(meta), "controller observation log out of step"
        log_phi = np.asarray(self.cstate["log_phi"])[:n]
        log_arr = np.asarray(self.cstate["log_arr"])[:n]
        shim = _ReplayAdapter(host.adapter)
        saved_adapter = host.adapter
        saved_listener = table.listener
        table.listener = None   # the device already routed post-rewrite
        host.adapter = shim
        try:
            with obs.span("ctrl.replay"):
                for (t0, k, left, rate), phi, arr in zip(meta, log_phi,
                                                         log_arr):
                    shim.set_window(phi, arr, left, rate)
                    for t in range(t0, t0 + k):
                        host.step(t)
        finally:
            host.adapter = saved_adapter
            table.listener = saved_listener
        if self._last_tick > host._tick:
            host._tick = self._last_tick
        host.sync_readbacks += 1
        # Arbitration: the host twin is the oracle.  Its replayed table
        # must equal the device's decision bit-for-bit; on mismatch the
        # host wins and the device consts are re-uploaded from it.
        table._refresh_derived()
        import jax
        ok = (np.array_equal(np.asarray(self.cstate["weights"]),
                             table.weights)
              and np.array_equal(np.asarray(self.cstate["cdf"]),
                                 table.cdf32)
              and np.array_equal(np.asarray(self.cstate["primary"]),
                                 table._primary)
              and np.array_equal(np.asarray(self.cstate["is_split"]),
                                 table._is_split))
        with _x64():
            if not ok:
                import warnings
                warnings.warn(
                    "device controller: in-dispatch decisions diverged "
                    "from the host twin; host wins", RuntimeWarning,
                    stacklevel=2)
                eng = self.rt.engine
                eng.incidents.record(
                    "ctrl-mismatch", tick=eng.tick, edge=self.rt.op.name,
                    cause="in-dispatch decisions diverged from the "
                          "host twin",
                    action="host wins; device consts re-uploaded")
                self.cstate.update(jax.device_put(dict(
                    weights=np.array(table.weights, np.float64),
                    cdf=np.asarray(table.cdf32, np.float32),
                    primary=np.asarray(table._primary, np.int64),
                    is_split=np.asarray(table._is_split, bool)),
                    _ctrl_device()))
            self.cstate["log_n"] = jax.device_put(np.int32(0),
                                                  _ctrl_device())
            rt.consts = jax.device_put(
                {k: self.cstate[k]
                 for k in ("cdf", "primary", "is_split", "owner")},
                _data_device())
        rt._consts_version = table.version
        rt._n_split = int(np.count_nonzero(table._is_split))
        self.epoch_synced = self.epoch_host

    # ---- retry/backoff against injected dispatch faults ----------------
    def _chaos_dispatch_ok(self, chaos) -> bool:
        """Consume any injected dispatch fault with retry/backoff; on
        exhaustion demote the controller drain-first (host stepping
        resumes, bit-identical) and return False."""
        eng = self.rt.engine
        policy = eng.retry_policy
        for attempt in range(policy.max_attempts + 1):
            try:
                chaos.dispatch_fault(self.rt)
                return True
            except InjectedDispatchFault as exc:
                if attempt < policy.max_attempts:
                    eng.incidents.record(
                        "retry", tick=eng.tick, edge=self.rt.op.name,
                        cause=str(exc),
                        action="retry controller dispatch",
                        attempt=attempt + 1)
                    policy.sleep(attempt + 1)
        self.deactivate("dispatch retries exhausted", drain=True)
        return False

    # ---- lifecycle -----------------------------------------------------
    def deactivate(self, reason: str, drain: bool = True) -> None:
        """Demote to host stepping (drains pending decisions first unless
        the caller knows there are none worth keeping)."""
        if self.active:
            if drain:
                self.drain()
            eng = self.rt.engine
            eng.incidents.record(
                "ctrl-demotion", tick=eng.tick, edge=self.rt.op.name,
                cause=reason, action="host-stepped controller resumes")
        self.active = False
        self.reason = reason

    def retire(self) -> None:
        """END: no metric round remains, so hand the last decisions to the
        host twin and stop before the END merge mutates host state.  Not
        a demotion: nothing falls back to host stepping."""
        if self.active:
            self.drain()
            self.active = False
            self.reason = "end"

    def on_restore(self) -> None:
        """Checkpoint restore: in-flight device decisions die with the
        restored state; re-form from the restored host twin, or demote
        when its mitigation state is not representable in-dispatch."""
        self.meta = []
        self.epoch_host = self.epoch_synced = 0
        self.active = False
        self._build()


# --------------------------------------------------------------------- #
# The per-(edge, operator) runtime                                        #
# --------------------------------------------------------------------- #
class DeviceOpRuntime:
    """Owns one destination operator's device residency.

    Created by the engine when an edge's destination is device-foldable
    and the ``jit`` executor is selected.  The host keeps exact integer
    mirrors (queue lengths, received/processed/emitted totals) updated
    from the O(W) per-dispatch metrics; record data stays on the device
    until :meth:`sync_host`.
    """

    def __init__(self, op, edge, engine, *, use_kernel: bool = False):
        from .operators import (Filter, GroupByAgg, HashJoinBuild,
                                HashJoinProbe, Project, RangeSort, Sink)

        self.op = op
        self.edge = edge
        self.engine = engine
        self.routing = edge.routing
        self.use_kernel = bool(use_kernel)
        self.kind = {Filter: "filter", Project: "project",
                     GroupByAgg: "fold", Sink: "sink",
                     HashJoinProbe: "probe", HashJoinBuild: "rows",
                     RangeSort: "rows"}[type(op)]
        self.W = op.num_workers
        self.K = edge.routing.num_keys
        self.NB = 0                    # upload padding width (static)
        self.B = 0                     # pop-window width (static)
        self.cap = 0                   # ring capacity (static, pow2)
        self.M = 1                     # probe emit fanout bound (static)
        self.rcap = 0                  # rows segment-store capacity (pow2)
        #: rows kind: per-worker row-log length (exact host mirror, the
        #: twin of ``ScopeRows.total_rows()`` across state + scattered).
        self.rows_len = np.zeros(op.num_workers, dtype=np.int64)
        self.state = None              # device pytree (lazily allocated)
        self.consts = None
        self._consts_version = -1
        self._dispatched = False
        self.staged: List[DeviceChunk] = []
        self.staged_live = 0
        # host mirrors (exact integers, updated per dispatch)
        self.lens = np.zeros(self.W, dtype=np.int64)
        self.received = np.zeros(self.W, dtype=np.int64)
        # ---- spill tier (memory tiering; see module docstring) --------- #
        #: entries of ``lens`` / ``rows_len`` currently held in host
        #: spill segments (exact mirrors: resident = total - spilled).
        self.spilled_lens = np.zeros(self.W, dtype=np.int64)
        self.spilled_rows = np.zeros(self.W, dtype=np.int64)
        self.budget_cfg = spill_tier.resolve_budget(
            getattr(engine, "device_budget", None))
        self.spill: Optional[spill_tier.SpillState] = None
        self._b_limit: Optional[int] = None   # chunked-probe B clamp
        self._degraded_once = False           # one-time degraded-emit
        self._regrow_capped_once = False      # one-time regrow-capped
        self._fn = getattr(op, "predicate", None) or getattr(op, "fn", None)
        self._pull = self._pull_counters    # stable identity (ownership)
        self._host_fresh = False   # host copies match device state
        self._reload_pending = False   # host mutated: reload pre-dispatch
        self._n_split = 0  # split keys of the uploaded consts
        #: placement (partition + scatter) executions, for the bench's
        #: placements-per-super-tick provenance row; chain fusion makes
        #: this 0 on every non-head edge of a fused chain.
        self.placements = 0
        #: the routing token under which ALL current ring content was
        #: placed (None = mixed/unknown).  Chain fusion requires it to
        #: equal the chain's token: token equality of the *current*
        #: tables proves nothing about backlog placed under an older
        #: version (e.g. both edges rewritten identically — tokens still
        #: match, but records queued pre-rewrite sit on the old primary's
        #: ring and would be mis-delivered by a pre-placed push).
        self._placed_token = None
        # ---- chain fusion links (set by Engine._wire_device) ----------- #
        self.chain_up: Optional["DeviceOpRuntime"] = None
        self.chain_down: Optional["DeviceOpRuntime"] = None
        self._chain_serial = -1     # engine super-tick serial last chained
        self._chain_disabled = False  # a fused dispatch failed: stay apart
        # ---- in-dispatch control plane (set by arm_controller) --------- #
        self.ctrl: Optional[DeviceController] = None
        self._ctrl_refused: Optional[str] = None

    # ---- small helpers ------------------------------------------------ #
    def _spec(self, any_split: Optional[bool] = None, P: int = 0) -> StepSpec:
        """The step's static half; ``P`` is a map stage's flat pop width
        (:meth:`_flat_width`)."""
        rt = self.routing
        rt._refresh_derived()
        if any_split is None:
            any_split = bool(rt._any_split)
        if self.ctrl is not None and self.ctrl.active:
            # An in-dispatch rewrite may split keys mid-window; trace the
            # split-aware step up front.  On one-hot tables the saturated
            # cdf routes every draw to the primary, so this is bit-neutral
            # while no split exists.  Past ONEHOT_MAX_KEYS that step sorts
            # the whole window: follow the uploaded consts (the device's
            # table, ahead of the host's between drains) instead.
            any_split = self.K <= ONEHOT_MAX_KEYS or bool(self._n_split)
        return StepSpec(kind=self.kind, W=self.W, K=self.K, cap=self.cap,
                        B=self.B, any_split=bool(any_split),
                        may_scatter=bool(self.op.may_scatter),
                        track_stats=bool(self.op.track_key_stats
                                         and self.op.arrived_by_key
                                         is not None),
                        use_kernel=self.use_kernel, fn=self._fn,
                        M=self.M, rcap=self.rcap, P=int(P))

    def _flat_width(self, budget: int, incoming: int) -> int:
        """A map stage's flat pop window for its next dispatch: it takes at
        most ``budget`` a ring, and no more than the resident ring lanes
        plus the ``incoming`` lanes the dispatch pushes first; rounded up
        to a power of two (a few widths, so a few compiles) and never
        wider than the ``[W, B]`` rectangle."""
        resident = int((self.lens - self.spilled_lens).sum())
        n = min(self.W * int(budget), resident + int(incoming))
        return min(_pow2(n), self.W * self.B)

    def _carry_width(self, spec: StepSpec) -> int:
        """Lanes a map or probe stage hands downstream in one dispatch."""
        if self.kind in MAP_KINDS:
            return spec.P
        return self.W * self._emit_bound(self.B)

    def _count_map_pop(self, spec: StepSpec, take: np.ndarray) -> None:
        """Host mirrors of a map stage's flat pop: its window and the lanes
        it took."""
        if self.kind in MAP_KINDS:
            obs.count("device.map_pop_lanes", spec.P)
            obs.count("device.map_pop_live", int(take.sum()))

    def _count_ingest(self, chunk: Optional[DeviceChunk]) -> None:
        """Host mirrors of a dispatch that ingests ``chunk`` (None: it
        only pops): a keyed fold's input lanes and host-known live lanes;
        and, while the uploaded consts split keys, the dispatch, their
        split-key count and the lanes its split-key rank pass covers (the
        whole chunk)."""
        if chunk is None:
            return
        n = int(chunk.keys.shape[0])
        if self.kind == "fold":
            obs.count("device.fold_lanes", n)
            obs.count("device.fold_live", chunk.n_live)
        if self._n_split:
            obs.count("device.split_dispatches")
            obs.count("device.split_keys", self._n_split)
            obs.count("device.rank_lanes", n)

    def backlog_total(self) -> int:
        return int(self.lens.sum()) + self.staged_live

    def workloads(self) -> np.ndarray:
        out = self.lens.astype(np.float64)
        if self.W == 1:
            out = out + float(self.staged_live)
        return out

    def received_totals(self) -> np.ndarray:
        return self.received.astype(np.float64)

    def _live_token(self):
        """The routing token of the *live* (possibly device-rewritten)
        table.  While the in-dispatch controller holds rewrites the host
        table has not seen yet, no host-side token can describe the
        device consts — chain fusion and placement epochs must treat the
        table as unprovable (None) until the next drain reconciles."""
        if (self.ctrl is not None and self.ctrl.active
                and self.ctrl.routing_dirty):
            return None
        return self.routing.routing_token()

    # ---- in-dispatch control plane ------------------------------------ #
    def arm_controller(self, controller) -> bool:
        """Attach a device-resident twin of ``controller`` (idempotent).
        Returns True when armed; refusals are memoized per runtime."""
        if self.ctrl is not None:
            if self.ctrl.host is controller:
                return self.ctrl.active
            self.ctrl.deactivate("controller replaced")
            self.ctrl = None
        if self._ctrl_refused is not None:
            return False
        reason = DeviceController.ineligible_reason(controller, self)
        if reason is not None:
            self._ctrl_refused = reason
            return False
        ctrl = DeviceController(self, controller)
        if not ctrl.arm():
            return False
        self.ctrl = ctrl
        return True

    # ---- retry/backoff against injected dispatch faults ---------------- #
    def _chaos_dispatch_ok(self, chaos) -> bool:
        """Consume any injected dispatch fault with retry/backoff; on
        exhaustion demote this edge drain-first (the per-chunk host path
        replays the tick bit-identically) and return False."""
        policy = self.engine.retry_policy
        for attempt in range(policy.max_attempts + 1):
            try:
                chaos.dispatch_fault(self)
                return True
            except InjectedDispatchFault as exc:
                if attempt < policy.max_attempts:
                    self.engine.incidents.record(
                        "retry", tick=self.engine.tick, edge=self.op.name,
                        cause=str(exc), action="retry device dispatch",
                        attempt=attempt + 1)
                    policy.sleep(attempt + 1)
        self.demote("dispatch retries exhausted")
        return False

    # ---- demotion (host fallback) ------------------------------------- #
    def demote(self, reason: str) -> None:
        """Fall back to the per-chunk host pallas path (rare: 2-D vals,
        an untraceable user fn, or a second in-edge)."""
        from .exchange import Exchange
        if self.ctrl is not None:
            # sync_host below drains via sync_stats; deactivate without a
            # second drain so the swap sees a quiesced control plane.
            self.ctrl.deactivate(f"demoted({reason})", drain=True)
            self.ctrl = None
        self._unlink_chain()
        staged, self.staged, self.staged_live = self.staged, [], 0
        if self.kind == "sink":
            # Staged sink chunks were accounted at stage time; the host
            # re-send below accounts again.  Back the mirror out *before*
            # sync_host materializes it into queue.received_total.
            for ch in staged:
                self.received[0] -= ch.n_live
        if self.state is not None:
            self.sync_host()
        self.op.device = None
        old = self.edge.exchange
        ex = Exchange(self.routing, self.op, "pallas")
        ex.tuples_sent = old.tuples_sent
        ex.sent_per_worker[:] = old.sent_per_worker
        if self.kind == "sink":
            for ch in staged:
                ex.tuples_sent -= ch.n_live
                ex.sent_per_worker[0] -= ch.n_live
        self.edge.exchange = ex
        self.edge.device_plane = f"demoted({reason})"
        self.engine.incidents.record(
            "demotion", tick=self.engine.tick, edge=self.op.name,
            cause=reason, action="per-chunk host pallas path")
        for ch in staged:
            k, v = ch.to_host() if isinstance(ch, DeviceChunk) else ch
            if getattr(k, "size", len(k)):
                ex.send((k, v))

    # ---- staging (DeviceExchange.send lands here) --------------------- #
    def stage(self, chunk: Union[Chunk, DeviceChunk]) -> None:
        if isinstance(chunk, DeviceChunk):
            if chunk.n_live == 0:
                return
            self._append(chunk)
            return
        keys, vals = chunk
        n = int(keys.shape[0])
        if n == 0:
            return
        if getattr(vals, "ndim", 1) != 1:
            self.demote("2-D vals")
            self.edge.exchange.send(chunk)
            return
        if n > self.NB:
            # Grow the padded upload width (a new pow2 width retraces the
            # step once; oversized host chunks are rare — END flushes are
            # bounded by W * K — so growth beats splitting).
            self.NB = _pow2(n)
        self._append(self._upload(keys, vals))

    def _append(self, chunk: DeviceChunk) -> None:
        if not self.staged:
            # Pin the routing constants of the table version this chunk
            # was *sent* under.  A rewrite between stage and dispatch
            # fires the edge listener, whose sync routes the staged
            # backlog with exactly these constants (the staleness fix:
            # one chunk must never route with mixed old/new tables).
            self._refresh_consts()
        self.staged.append(chunk)
        self.staged_live += chunk.n_live
        self._host_fresh = False
        if self.kind == "sink":
            # Single-worker sink: the histogram is known without a
            # dispatch, and staged chunks may cross a super-tick boundary
            # — account at send time exactly like the host plane.
            self.edge.exchange.account(
                np.array([chunk.n_live], dtype=np.int64))
            self.received[0] += chunk.n_live

    def _upload(self, keys: np.ndarray, vals: np.ndarray) -> DeviceChunk:
        jnp = _jnp()
        n = int(keys.shape[0])
        pk = np.zeros(self.NB, np.int64)
        pv = np.zeros(self.NB, np.int64)
        m = np.zeros(self.NB, bool)
        pk[:n] = keys
        pv[:n] = _val_bits(vals)
        m[:n] = True
        with _x64():
            return DeviceChunk(jnp.asarray(pk, jnp.int64),
                               jnp.asarray(pv, jnp.int64),
                               jnp.asarray(m, bool), n)

    # ---- device state lifecycle --------------------------------------- #
    def _alloc_state(self) -> None:
        jnp = _jnp()
        with _x64():
            st = dict(count=jnp.zeros(self.K, jnp.int64),
                      arrived=jnp.zeros(self.K, jnp.int64),
                      totals=jnp.zeros(self.K, jnp.int64))
            if self.kind != "sink":
                st.update(rk=jnp.zeros((self.W, self.cap), jnp.int64),
                          rv=jnp.zeros((self.W, self.cap), jnp.int64),
                          head=jnp.zeros(self.W, jnp.int64),
                          tail=jnp.zeros(self.W, jnp.int64))
            if self.kind == "fold":
                for name in ("counts", "scat_counts"):
                    st[name] = jnp.zeros((self.W, self.K), jnp.int64)
                for name in ("sums", "scat_sums"):
                    st[name] = jnp.zeros((self.W, self.K), jnp.float64)
                for name in ("present", "scat_present"):
                    st[name] = jnp.zeros((self.W, self.K), bool)
            if self.kind == "probe":
                st["mcounts"] = jnp.zeros((self.W, self.K), jnp.int64)
            if self.kind == "rows":
                st.update(bk=jnp.zeros((self.W, self.rcap), jnp.int64),
                          bv=jnp.zeros((self.W, self.rcap), jnp.int64),
                          bo=jnp.zeros((self.W, self.rcap), bool),
                          rlen=jnp.zeros(self.W, jnp.int64))
            if self.kind == "sink":
                st["counts"] = jnp.zeros(self.K, jnp.int64)
                st["sums"] = jnp.zeros(self.K, jnp.float64)
        self.state = st
        self._load_host_state()

    @obs.spanned("device.reload")
    def _load_host_state(self) -> None:
        """Host -> device: (re)load keyed state, rings and mirrors from
        the operator's host structures (initial wiring, post-migration
        staleness, checkpoint restore)."""
        jnp = _jnp()
        op = self.op
        self._reload_pending = False
        self._host_fresh = False
        # Host structures hold the FULL content (``sync_host`` folds the
        # spill tier back in before any host mutation): everything the
        # reload uploads is resident again, so the spill tier restarts
        # empty and the spilled mirrors zero out.
        self.spilled_lens[:] = 0
        self.spilled_rows[:] = 0
        if self.spill is not None:
            self.spill.clear()
        # Host-loaded queue content has unknown placement provenance
        # (restores may install backlog placed under any table history):
        # chain fusion stays off until these rings drain.
        self._placed_token = None
        with _x64():
            if self.kind != "sink":
                rk = np.zeros((self.W, self.cap), np.int64)
                rv = np.zeros((self.W, self.cap), np.int64)
                for w, worker in enumerate(op.workers):
                    k, v = worker.queue.snapshot()
                    if v.ndim != 1:
                        raise ValueError("device plane requires 1-D vals")
                    ln = int(k.size)
                    rk[w, :ln] = k
                    rv[w, :ln] = _val_bits(v)
                    self.lens[w] = ln
                    self.received[w] = worker.queue.received_total
                self.state.update(
                    rk=jnp.asarray(rk, jnp.int64),
                    rv=jnp.asarray(rv, jnp.int64),
                    head=jnp.zeros(self.W, jnp.int64),
                    tail=jnp.asarray(self.lens.copy(), jnp.int64))
            if self.kind == "fold":
                own = [w.state.export_dense() for w in op.workers]
                scat = [w.scattered.export_dense() for w in op.workers]
                self.state.update(
                    counts=jnp.asarray(
                        np.stack([o[0] for o in own]), jnp.int64),
                    sums=jnp.asarray(
                        np.stack([o[1] for o in own]), jnp.float64),
                    present=jnp.asarray(
                        np.stack([o[2] for o in own]), bool),
                    scat_counts=jnp.asarray(
                        np.stack([s[0] for s in scat]), jnp.int64),
                    scat_sums=jnp.asarray(
                        np.stack([s[1] for s in scat]), jnp.float64),
                    scat_present=jnp.asarray(
                        np.stack([s[2] for s in scat]), bool))
            if self.kind == "probe":
                # Dense match table: owned + scattered build rows SUMMED
                # per (worker, key) — a split build key may hold rows in
                # both (the host plane's fixed probe semantics).  M (the
                # max fanout) is static: a change retraces the step.
                mc = np.stack([np.asarray(w.state.counts)
                               + np.asarray(w.scattered.counts)
                               for w in op.workers])
                self.state["mcounts"] = jnp.asarray(mc, jnp.int64)
                self.M = max(int(mc.max(initial=1)), 1)
            if self.kind == "rows":
                need = max(int(w.state.total_rows()
                               + w.scattered.total_rows())
                           for w in op.workers)
                if need + self.B > self.rcap:
                    self.rcap = _pow2(2 * max(need + self.B, 1))
                bk = np.zeros((self.W, self.rcap), np.int64)
                bv = np.zeros((self.W, self.rcap), np.int64)
                bo = np.zeros((self.W, self.rcap), bool)
                for w, worker in enumerate(op.workers):
                    ok_k, ok_v = worker.state.export_rows()
                    sc_k, sc_v = worker.scattered.export_rows()
                    n1, n2 = int(ok_k.size), int(sc_k.size)
                    bk[w, :n1] = ok_k
                    bv[w, :n1] = _val_bits(ok_v)
                    bo[w, :n1] = True
                    bk[w, n1:n1 + n2] = sc_k
                    bv[w, n1:n1 + n2] = _val_bits(sc_v)
                    self.rows_len[w] = n1 + n2
                self.state.update(
                    bk=jnp.asarray(bk, jnp.int64),
                    bv=jnp.asarray(bv, jnp.int64),
                    bo=jnp.asarray(bo, bool),
                    rlen=jnp.asarray(self.rows_len.copy(), jnp.int64))
            if self.kind == "sink":
                self.state.update(
                    counts=jnp.asarray(op.counts.copy(), jnp.int64),
                    sums=jnp.asarray(op.sums.copy(), jnp.float64))
                # The received mirror is stage-accounted and already
                # correct on every path into here (mid-run staging, or
                # ``on_restore`` which read the restored queue) — do NOT
                # overwrite it from the scratch host queue, whose count
                # lags the chunks staged before first allocation.
                k, v = op.workers[0].queue.snapshot()
                if k.size:           # restored backlog: re-stage, already
                    self.staged = [self._restage(k, v)]     # accounted
                    self.staged_live = int(k.size)

    def _restage(self, keys: np.ndarray, vals: np.ndarray) -> DeviceChunk:
        if keys.shape[0] > self.NB:
            self.NB = _pow2(int(keys.shape[0]))
        return self._upload(keys, vals)

    def _ensure_ready(self, incoming: int = 0) -> None:
        """Grow static shapes (cap/B) and allocate device state.

        ``incoming`` bounds records that will arrive *inside* the next
        dispatch without ever being staged — a fused chain delivers the
        upstream stage's survivors straight into these rings, at most
        its per-ring pop budget per ring (pre-placed: ring ``w`` only
        receives from upstream ring ``w``) — so the capacity check must
        cover them or the in-step scatter would wrap onto live entries.
        """
        # wireable() guarantees service_rate <= MAX_SERVICE_RATE for
        # ring-backed kinds, so B always covers the engine's budgets.
        budget_cap = self.engine.batch_ticks * self.op.service_rate
        if self._b_limit is not None:
            # Degraded (chunked) probe emission: the automatic widening
            # must not blow the emit buffer the chunk driver just sized.
            budget_cap = min(budget_cap, self._b_limit)
        if self.kind != "sink" and budget_cap > self.B:
            self.B = int(budget_cap)
        # Capacity covers the RESIDENT share only — spilled entries live
        # in host segments and re-enter through the budget-covering
        # refill, never all at once.
        need = (int((self.lens - self.spilled_lens).max(initial=0))
                + self.staged_live + int(incoming))
        if self.state is None:
            self.cap = max(self.cap, _pow2(2 * max(need, 1)))
            self._alloc_state()
        elif need > self.cap and self.kind != "sink":
            self.cap = self._capped_growth(_pow2(2 * need), "ring")
            self._regrow_rings()
        if self.kind == "rows" and self.state is not None:
            rres = int((self.rows_len - self.spilled_rows).max(initial=0))
            if rres + self.B > self.rcap:
                # The row log only grows (appends, never pops): double it
                # so the next dispatch's worst-case append (<= B rows)
                # fits.
                self.rcap = self._capped_growth(
                    _pow2(2 * (rres + self.B)), "row store")
                self._regrow_rowstore()

    def _capped_growth(self, new_cap: int, what: str) -> int:
        """Satellite of the spill tier: growth past the budget-implied
        allocation cap means watermark eviction could not keep this edge
        bounded (a burst larger than the budget itself).  Grow anyway —
        correctness over the budget — but surface it once."""
        cfg = self.budget_cfg
        if cfg is not None:
            limit = _pow2(2 * (cfg.per_worker(self.W) + max(self.B, 1)))
            if new_cap > limit and not self._regrow_capped_once:
                self._regrow_capped_once = True
                self.engine.incidents.record(
                    "regrow-capped", tick=self.engine.tick,
                    edge=self.op.name,
                    cause=f"{what} regrowth to {new_cap} cells exceeds "
                          f"the device-budget cap {limit}",
                    action="grow past the budget (burst exceeds it); "
                           "spill resumes bounding the steady state")
        return new_cap

    def _regrow_rings(self) -> None:
        """Re-layout the rings at a larger capacity (content preserved)."""
        jnp = _jnp()
        rk_np = _readback(self.state["rk"], "regrow")
        rv_np = _readback(self.state["rv"], "regrow")
        head = _readback(self.state["head"], "regrow")
        old_cap = rk_np.shape[1]
        new_k = np.zeros((self.W, self.cap), np.int64)
        new_v = np.zeros((self.W, self.cap), np.int64)
        resident = self.lens - self.spilled_lens
        for w in range(self.W):
            ln = int(resident[w])
            idx = ring_span(head[w], ln, old_cap)
            new_k[w, :ln] = rk_np[w, idx]
            new_v[w, :ln] = rv_np[w, idx]
        with _x64():
            self.state.update(rk=jnp.asarray(new_k, jnp.int64),
                              rv=jnp.asarray(new_v, jnp.int64),
                              head=jnp.zeros(self.W, jnp.int64),
                              tail=jnp.asarray(resident.copy(),
                                               jnp.int64))

    def _regrow_rowstore(self) -> None:
        """Re-layout the flat row log at a larger capacity (append-only:
        no ring wrap, so regrowth is a prefix copy per column)."""
        jnp = _jnp()
        bk = _readback(self.state["bk"], "regrow")
        bv = _readback(self.state["bv"], "regrow")
        bo = _readback(self.state["bo"], "regrow")
        old = bk.shape[1]
        new_k = np.zeros((self.W, self.rcap), np.int64)
        new_v = np.zeros((self.W, self.rcap), np.int64)
        new_o = np.zeros((self.W, self.rcap), bool)
        new_k[:, :old] = bk
        new_v[:, :old] = bv
        new_o[:, :old] = bo
        with _x64():
            self.state.update(bk=jnp.asarray(new_k, jnp.int64),
                              bv=jnp.asarray(new_v, jnp.int64),
                              bo=jnp.asarray(new_o, bool))

    # ---- spill tier (memory tiering; see module docstring) ------------- #
    def set_budget(self, budget) -> None:
        """(Re)configure this edge's device budget mid-run (the chaos
        ``mem-pressure`` fault shrinks it; its undo restores).  Setting
        ``None`` disables eviction but keeps any spilled spans reachable
        (refill keeps draining them)."""
        self.budget_cfg = spill_tier.resolve_budget(budget)

    def _device_put(self, a):
        import jax
        with _x64():
            return jax.device_put(a)

    def _spill_corrupt_incident(self, exc) -> None:
        self.engine.incidents.record(
            "spill-corrupt", tick=self.engine.tick, edge=self.op.name,
            cause=str(exc),
            action="recover from the last valid checkpoint cut")

    def _spill_refill(self, budget: int) -> None:
        """Re-upload logically-next spilled ring spans until the pop
        window is covered by resident records: per worker, refill stops
        when ``resident >= budget`` or the spill store drains, so the
        dispatch's ``take = min(budget, resident)`` equals the host
        plane's ``min(budget, total)`` exactly and consumes exactly the
        logically-first records.  Prefetched (pre-uploaded) segments make
        the common refill a device-to-device append."""
        sp = self.spill
        if (sp is None or self.state is None or self._reload_pending
                or self.kind == "sink" or not sp.any()):
            return
        jnp = _jnp()
        budget = int(budget)
        with _x64():
            for w in range(self.W):
                if not sp.rings[w]:
                    continue
                res = int(self.lens[w] - self.spilled_lens[w])
                while sp.rings[w] and res < budget:
                    try:
                        seg, dev = sp.pop_ring_front(w)
                    except spill_tier.SpillCorruptError as exc:
                        self._spill_corrupt_incident(exc)
                        raise
                    if res + seg.n > self.cap:
                        self.cap = _pow2(2 * (res + seg.n + budget))
                        self._regrow_rings()
                    k, v = (seg.arrays if dev is None else dev)[:2]
                    tail = int(_readback(self.state["tail"], "spill")[w])
                    idx = (tail + jnp.arange(seg.n, dtype=jnp.int64)
                           ) % self.cap
                    self.state["rk"] = self.state["rk"].at[w, idx].set(
                        jnp.asarray(k, jnp.int64))
                    self.state["rv"] = self.state["rv"].at[w, idx].set(
                        jnp.asarray(v, jnp.int64))
                    self.state["tail"] = self.state["tail"].at[w].add(
                        np.int64(seg.n))
                    self.spilled_lens[w] -= seg.n
                    res += seg.n
                sp.prefetch(w, self._device_put)

    def _spill_admit(self, budget: int) -> None:
        """Watermark check before a dispatch: evict cold resident spans
        (behind the pop window) to the host spill tier and raise the
        structured ``mem-pressure`` signal on a high-watermark crossing
        (hysteresis: re-arms under the low watermark)."""
        cfg = self.budget_cfg
        if (cfg is None or self.kind == "sink" or self.state is None
                or self._reload_pending):
            return
        L = cfg.per_worker(self.W)
        high = max(int(L * cfg.high_wm), 1)
        low = max(int(L * cfg.low_wm), 1)
        budget = int(budget)
        res = self.lens - self.spilled_lens
        over = [w for w in range(self.W)
                if int(res[w]) > max(high, budget)]
        rows_over = []
        rres = None
        if self.kind == "rows":
            rres = self.rows_len - self.spilled_rows
            rows_over = [w for w in range(self.W) if int(rres[w]) > high]
        if (over or rows_over) and self.spill is None:
            self.spill = spill_tier.SpillState(cfg, self.W)
        if over:
            self._spill_evict_rings(over, keep=max(low, budget))
        if rows_over:
            self._spill_evict_rows(rows_over, keep=low)
        sp = self.spill
        if sp is None:
            return
        pressured = set(over) | set(rows_over)
        for w in range(self.W):
            if w in pressured:
                if not sp.pressure_active[w]:
                    sp.pressure_active[w] = True
                    self.engine.incidents.record(
                        "mem-pressure", tick=self.engine.tick,
                        edge=self.op.name,
                        cause=f"worker {w}: resident device state crossed "
                              f"the high watermark ({high} of {L} "
                              f"cells/worker)",
                        action="spill cold spans to host; notify the "
                               "attached controller")
                    self._notify_pressure(w)
            elif (int(res[w]) <= low
                  and (rres is None or int(rres[w]) <= low)):
                sp.pressure_active[w] = False

    def _spill_evict_rings(self, ws: List[int], keep: int) -> None:
        """Move the newest resident ring records (cold: the next pops
        cannot reach them) of each listed worker into checksummed host
        segments, prepending at the spill front (they are logically just
        before any already-spilled span)."""
        jnp = _jnp()
        rk = _readback(self.state["rk"], "spill")
        rv = _readback(self.state["rv"], "spill")
        head = _readback(self.state["head"], "spill")
        delta = np.zeros(self.W, np.int64)
        for w in ws:
            res = int(self.lens[w] - self.spilled_lens[w])
            m = res - int(keep)
            if m <= 0:
                continue
            idx = (int(head[w]) + res - m + np.arange(m)) % self.cap
            seg = spill_tier.SpillSegment(
                (rk[w, idx].copy(), rv[w, idx].copy()), m)
            self.spill.prepend_ring(w, seg)
            self.spilled_lens[w] += m
            delta[w] = m
        if delta.any():
            with _x64():
                self.state["tail"] = (self.state["tail"]
                                      - jnp.asarray(delta, jnp.int64))
            for w in ws:
                self.spill.prefetch(w, self._device_put)

    def _spill_evict_rows(self, ws: List[int], keep: int) -> None:
        """Spill the oldest rows (a per-worker prefix) of the device row
        store: row logs are append-only and only read back at
        ``sync_host``, so the prefix is the coldest span by construction
        and never needs a mid-run re-upload."""
        jnp = _jnp()
        bk = _readback(self.state["bk"], "spill").copy()
        bv = _readback(self.state["bv"], "spill").copy()
        bo = _readback(self.state["bo"], "spill").copy()
        rlen = _readback(self.state["rlen"], "spill").copy()
        for w in ws:
            rres = int(self.rows_len[w] - self.spilled_rows[w])
            m = rres - int(keep)
            if m <= 0:
                continue
            seg = spill_tier.SpillSegment(
                (bk[w, :m].copy(), bv[w, :m].copy(), bo[w, :m].copy()), m)
            self.spill.append_rows(w, seg)
            left = rres - m
            bk[w, :left] = bk[w, m:rres]
            bv[w, :left] = bv[w, m:rres]
            bo[w, :left] = bo[w, m:rres]
            bk[w, left:rres] = 0
            bv[w, left:rres] = 0
            bo[w, left:rres] = False
            rlen[w] = left
            self.spilled_rows[w] += m
        with _x64():
            self.state.update(bk=jnp.asarray(bk, jnp.int64),
                              bv=jnp.asarray(bv, jnp.int64),
                              bo=jnp.asarray(bo, bool),
                              rlen=jnp.asarray(rlen, jnp.int64))

    def _spill_demote_fresh(self, pushed: np.ndarray) -> None:
        """Fresh pushes landed behind spilled spans: move them to the
        spill tier's logical END so the per-worker order stays
        ``[resident][spilled]`` (the pops of this dispatch never reached
        them — refill guaranteed ``resident >= budget`` up front)."""
        ws = [w for w in range(self.W)
              if int(pushed[w]) > 0 and self.spill.rings[w]]
        if not ws:
            return
        jnp = _jnp()
        rk = _readback(self.state["rk"], "spill")
        rv = _readback(self.state["rv"], "spill")
        head = _readback(self.state["head"], "spill")
        delta = np.zeros(self.W, np.int64)
        for w in ws:
            m = int(pushed[w])
            res = int(self.lens[w] - self.spilled_lens[w])
            idx = (int(head[w]) + res - m + np.arange(m)) % self.cap
            seg = spill_tier.SpillSegment(
                (rk[w, idx].copy(), rv[w, idx].copy()), m)
            self.spill.append_ring(w, seg)
            self.spilled_lens[w] += m
            delta[w] = m
        with _x64():
            self.state["tail"] = (self.state["tail"]
                                  - jnp.asarray(delta, jnp.int64))

    def _spill_gate(self, budget) -> bool:
        """Must this edge stay per-edge (unfused) this dispatch?  True
        when spilled spans exist — refill and fresh-push re-tiering run
        only on the per-edge path — or when the projected resident count
        crosses the high watermark, so a chain dispatch never needs to
        evict mid-flight."""
        if self.spill is not None and self.spill.any():
            return True
        cfg = self.budget_cfg
        if cfg is None or self.kind == "sink":
            return False
        L = cfg.per_worker(self.W)
        high = max(int(L * cfg.high_wm), 1)
        res = int((self.lens - self.spilled_lens).max(initial=0))
        if self.kind == "rows":
            res = max(res, int((self.rows_len
                                - self.spilled_rows).max(initial=0)))
        projected = res + self.staged_live + int(budget)
        return projected > max(high, int(budget))

    def _notify_pressure(self, worker: int) -> None:
        """Memory pressure is a mitigation trigger: hand the structured
        signal to the attached host controller (the skew split of the
        fat worker sheds the hot partition's growth)."""
        for att in getattr(self.engine, "controllers", ()):
            if getattr(att, "op", None) is not self.op:
                continue
            note = getattr(att.controller, "note_memory_pressure", None)
            if note is not None:
                note(worker, self.engine.tick)

    # ---- routing constants / split counters --------------------------- #
    def _refresh_consts(self, force: bool = False) -> None:
        jnp = _jnp()
        rt = self.routing
        rt._refresh_derived()
        if self.ctrl is not None and self.ctrl.active and not force:
            # While armed, the device consts are ahead of the host table
            # between drains: never clobber them from the host copy.  A
            # genuine host-side version bump (an out-of-band rewrite the
            # controller did not make) demotes the control plane first.
            if self._consts_version == rt.version:
                return
            self.ctrl.deactivate("out-of-band table rewrite")
        if self.consts is None or self._consts_version != rt.version:
            with _x64():
                self.consts = dict(
                    cdf=jnp.asarray(rt.cdf32, jnp.float32),
                    primary=jnp.asarray(rt._primary, jnp.int64),
                    is_split=jnp.asarray(rt._is_split, bool),
                    owner=jnp.asarray(rt.owner.copy(), jnp.int64))
            self._consts_version = rt.version
            self._n_split = int(np.count_nonzero(rt._is_split))

    def _pull_counters(self) -> np.ndarray:
        return _readback(self.state["count"], "counters")

    def _claim_counters(self) -> None:
        rt = self.routing
        if rt._count_owner is not self._pull:
            rt.sync_counters()          # a previous owner's last word
            jnp = _jnp()
            with _x64():
                self.state["count"] = jnp.asarray(rt._count.copy(),
                                                  jnp.int64)
            rt._count_owner = self._pull

    # ---- the fused super-tick dispatch -------------------------------- #
    def _prep(self, budget: int, incoming: int = 0) -> None:
        """Pre-dispatch lifecycle shared by the per-edge and chain paths:
        widen the pop window, allocate/grow device state, apply deferred
        host reloads, claim counters, flush version-stale staged chunks
        under their pinned constants, then refresh to the live table."""
        if self.kind != "sink" and int(budget) > self.B:
            # A caller outpaced the batch_ticks sizing (manual
            # run_super_tick with a wider window): widen the static pop
            # window so no popped lane can fall outside it (retrace).
            self.B = int(budget)
        self._ensure_ready(incoming)
        if self._reload_pending:
            self._reload_pending = False
            self._load_host_state()
        if self.kind != "sink":
            self._claim_counters()
        self._flush_stale_staged()
        self._refresh_consts()

    def _flush_stale_staged(self) -> None:
        """Bugfix: staged chunks must route under the table they were
        *sent* under.  The rewrite listener fires after the weights
        moved, so the listener-triggered boundary sync used to dispatch
        staged chunks with the freshly-bumped table while the host plane
        had already routed them at send time with the old one — one
        chunk routed with mixed old/new tables.  The constants pinned at
        stage time (:meth:`_append`) are still on the device: ingest
        with them (budget 0), then the caller refreshes to the live
        table."""
        if (not self.staged or self.consts is None
                or self._consts_version == self.routing.version):
            return
        chunks = list(self.staged)
        self._dispatch(_step_for(self.kind),
                       self._spec(any_split=bool(self._n_split)), chunks, 0)
        self.staged, self.staged_live = [], 0

    def tick(self, budget: int) -> List:
        if self.state is None and not self.staged:
            return []                  # nothing ever arrived
        chaos = getattr(self.engine, "chaos", None)
        if chaos is not None and not self._chaos_dispatch_ok(chaos):
            return self.op.tick(budget)    # demoted: host path replays
        if self.kind == "probe" and not self._probe_capacity_ok(budget):
            if self.budget_cfg is not None:
                # Spill-backed degradation instead of the demotion
                # cliff: emit in chunked sub-budget dispatches.
                return self._tick_probe_chunked(budget)
            # A build table (or budget) skewed enough that the padded
            # emit buffer W * B * M would blow the ceiling: the host
            # path handles unbounded fanout natively.
            self.demote("probe fanout")
            return self.op.tick(budget)
        chain = self._chain_for_dispatch(budget)
        if chain is not None:
            return self._dispatch_chain(chain, budget)
        self._host_fresh = False
        chunks: List[DeviceChunk] = []
        try:
            self._spill_refill(budget)
            self._prep(budget)
            self._spill_admit(budget)
            chunks, self.staged, self.staged_live = self.staged, [], 0
            return self._dispatch(_step_for(self.kind), self._spec(),
                                  chunks, budget)
        except _trace_errors() as exc:
            if self._dispatched:
                raise
            # First-ever dispatch could not trace a user fn: fall back to
            # the host plane and replay this tick there.
            import warnings
            warnings.warn(
                f"device plane: first dispatch for {self.op.name!r} "
                f"failed ({type(exc).__name__}: {exc}); demoting the "
                f"edge to the host path", RuntimeWarning, stacklevel=2)
            self.staged = chunks + self.staged
            self.staged_live = sum(c.n_live for c in self.staged)
            self.demote("untraceable fn")
            return self.op.tick(budget)

    def _host_fanout(self) -> int:
        """Max per-(worker, key) build matches, read from host state."""
        mc = max((int((np.asarray(w.state.counts)
                       + np.asarray(w.scattered.counts)).max(initial=0))
                  for w in self.op.workers), default=0)
        return max(mc, 1)

    def _probe_capacity_ok(self, budget: int) -> bool:
        """Would the probe emit buffer stay under ``MAX_EMIT_CELLS``?
        Uses the host-state fanout whenever the device match table is
        absent or stale (install_build / a migration just ran)."""
        B = max(self.B, int(budget),
                self.engine.batch_ticks * self.op.service_rate)
        M = (self.M if self.state is not None and not self._reload_pending
             else self._host_fanout())
        return self.W * B * M <= MAX_EMIT_CELLS

    def _tick_probe_chunked(self, budget: int) -> List:
        """Spill-backed degradation of the probe-fanout cliff: instead of
        demoting the edge, pop and expand in sub-budget chunks whose
        padded emit buffer ``W * b * M`` stays under ``MAX_EMIT_CELLS``.
        Bit-exact vs one full-budget dispatch: sequential prefix pops
        compose to one pop of the summed budget, and splitting a popped
        window into chunks preserves each lane's expansion order (the
        cross-plane contract is integer-based, so f32 accumulation order
        is already out of contract).  Only a single record whose fanout
        alone blows the buffer (``W * M > MAX_EMIT_CELLS``) still
        demotes."""
        M = max(self.M if self.state is not None and not self._reload_pending
                else self._host_fanout(), 1)
        if self.W * M > MAX_EMIT_CELLS:
            self.demote("probe fanout")
            return self.op.tick(budget)
        b_limit = max(MAX_EMIT_CELLS // (self.W * M), 1)
        self._b_limit = b_limit
        if self.B > b_limit:
            self.B = b_limit       # shrink the static window (one retrace)
        if not self._degraded_once:
            self._degraded_once = True
            self.engine.incidents.record(
                "degraded-emit", tick=self.engine.tick, edge=self.op.name,
                cause=f"probe emit buffer W*B*M over MAX_EMIT_CELLS "
                      f"(W={self.W}, M={M})",
                action=f"chunked emission at B<={b_limit} "
                       f"(no demotion)")
        self._host_fresh = False
        left = int(budget)
        chunks: List[DeviceChunk] = []
        try:
            first = True
            while True:
                b = min(left, b_limit)
                self._spill_refill(b)
                self._prep(b)
                self._spill_admit(b)
                if first:
                    chunks, self.staged, self.staged_live = \
                        self.staged, [], 0
                self._dispatch(_step_for(self.kind), self._spec(),
                               chunks, b)
                chunks = []
                first = False
                left -= b
                if left <= 0 or b == 0:
                    break
                if (int((self.lens - self.spilled_lens).sum()) == 0
                        and not self.staged):
                    break          # drained: further pops would take 0
        except _trace_errors() as exc:
            if self._dispatched:
                raise
            import warnings
            warnings.warn(
                f"device plane: first dispatch for {self.op.name!r} "
                f"failed ({type(exc).__name__}: {exc}); demoting the "
                f"edge to the host path", RuntimeWarning, stacklevel=2)
            self.staged = chunks + self.staged
            self.staged_live = sum(c.n_live for c in self.staged)
            self.demote("untraceable fn")
            return self.op.tick(budget)
        return []

    def _emit_bound(self, budget: int) -> int:
        """Most records this stage can hand its chain follower inside one
        dispatch: the pop budget, times the match fanout for a probe."""
        if self.kind == "probe":
            return int(budget) * max(self.M, 1)
        return int(budget)

    # ---- chain fusion (multi-edge shared placement) -------------------- #
    def _preserves_keys(self) -> bool:
        """May this stage's output reuse its input placement?  A Filter
        only masks, so always; a probe repeats its input records without
        re-keying, so always; a Project must declare
        ``preserves_keys=True`` (an arbitrary fn may re-key, which would
        invalidate the shared placement)."""
        if self.kind in ("filter", "probe"):
            return True
        return bool(getattr(self.op, "preserves_keys", False))

    def _unlink_chain(self) -> None:
        if self.chain_up is not None:
            self.chain_up.chain_down = None
            self.chain_up = None
        if self.chain_down is not None:
            self.chain_down.chain_up = None
            self.chain_down = None

    def _placement_current(self, tok) -> bool:
        """Was every record this stage would hand downstream placed under
        the chain's token?  Ring backlog carries its placement epoch
        (:attr:`_placed_token`); empty rings are vacuously current, and
        staged chunks count only if they will be placed under the live
        table (a version-stale backlog flushes under the old one)."""
        if self.staged and self._consts_version != self.routing.version:
            return False
        return (self._placed_token == tok
                or int(self.lens.sum()) == 0)

    def _chain_for_dispatch(self, budget: int):
        """The fused chain ``[self, ...]`` to advance in one dispatch, or
        ``None`` to stay per-edge.  Re-checked every dispatch, so fusion
        falls apart the moment equivalence stops being provable: routing
        tokens must compare equal along the chain (one-hot tables only —
        any rewrite that splits or moves a key voids or changes them),
        every member must still be device-wired and unfinished, every
        non-tail stage key-preserving, and the budget must be the
        scheduler's ``k * service_rate`` so follower budgets are known
        (manual odd-budget ticks stay per-edge)."""
        eng = self.engine
        if (self.kind not in ("filter", "project", "probe")
                or self.chain_down is None or self._chain_disabled
                or not getattr(eng, "device_chain", True)
                or self.op.device is not self or self.op.finished
                or not self._preserves_keys()
                or budget != eng._super_k * self.op.service_rate):
            return None
        if self._spill_gate(budget):
            return None          # spill handling runs per-edge only
        tok = self._live_token()
        if tok is None:
            return None
        members = [self]
        r = self
        while True:
            d = r.chain_down
            if (d is None or d.op.device is not d or d.op.finished
                    or d._live_token() != tok):
                break
            if d.kind == "sink" and d.use_kernel:
                # The per-edge sink step folds through the Pallas
                # partition_scatter_fold kernel; the chain tail would
                # silently swap in the plain scatter-add (different f32
                # accumulation) — keep use_kernel sinks per-edge so the
                # A/B contract of device_use_kernel is unchanged.
                break
            if (d.kind == "probe" and not d._probe_capacity_ok(
                    eng._super_k * d.op.service_rate)):
                break                   # d's own tick will demote it
            if d._spill_gate(eng._super_k * d.op.service_rate):
                break                   # d must evict/refill per-edge
            members.append(d)
            if (d.kind not in ("filter", "project", "probe")
                    or d._chain_disabled or not d._preserves_keys()):
                break                   # d is the chain's tail
            r = d
        if len(members) < 2:
            return None
        # Token equality of the *current* tables is not enough: every
        # record a non-tail stage will hand downstream must also have
        # been *placed* under that same token — backlog queued before a
        # rewrite that moved both tables in lockstep still sits on the
        # old primaries' rings and would be mis-delivered.
        if not all(m._placement_current(tok) for m in members[:-1]):
            return None
        return members

    def _dispatch_chain(self, members: List["DeviceOpRuntime"],
                        budget: int) -> List:
        """Advance the whole fused chain in one jitted dispatch (the
        head's tick slot; the engine skips the followers' own ticks this
        super-tick via ``_chain_serial``).  Per-stage metrics update the
        same exact host mirrors the per-edge dispatches keep."""
        eng = self.engine
        budgets = [eng._super_k * r.op.service_rate for r in members]
        budgets[0] = int(budget)
        for r in members[1:]:
            if r.staged:                # leftovers from an unfused window
                r.tick(0)               # budget 0 never chains: per-edge
        chunks: List[DeviceChunk] = []
        ingested = False
        tok = self._live_token()
        try:
            empty_before = []
            for i, (r, b) in enumerate(zip(members, budgets)):
                r._host_fresh = False
                empty_before.append(int(r.lens.sum()) == 0)
                # Followers receive up to the upstream stage's per-ring
                # *emit bound* inside the dispatch itself (never staged):
                # the pop budget, fanned out by M for a probe stage
                # (whose M is final — its _prep already ran).
                r._prep(b, incoming=members[i - 1]._emit_bound(
                    budgets[i - 1]) if i else 0)
            chunks, self.staged, self.staged_live = self.staged, [], 0
            dc = None
            incoming = 0
            if len(chunks) == 1:
                ch = chunks[0]
                dc = (ch.keys, ch.vals, ch.valid)
                incoming = ch.n_live
                self._count_ingest(ch)
            elif chunks:
                # Rare multi-chunk stage (END flushes): ingest per-edge
                # first (budget 0 pops nothing), then chain pop-only —
                # bit-identical to the per-edge [(c,0)...(c,B)] sequence.
                self._dispatch(_step_for(self.kind), self._spec(), chunks, 0)
                ingested = True
            specs = []
            for r, b in zip(members, budgets):
                # a follower's incoming lanes: what its predecessor hands on
                specs.append(r._spec(P=r._flat_width(b, incoming)
                                     if r.kind in MAP_KINDS else 0))
                incoming = r._carry_width(specs[-1])
            specs = tuple(specs)
            consts_t = tuple(r.consts for r in members)
            states_t = tuple(r.state for r in members)
            step = _step_for("chain")
            with _x64(), obs.span("device.dispatch"):
                states_t, out, metrics = step(
                    specs, consts_t, states_t, dc,
                    tuple(np.int64(b) for b in budgets))
        except _trace_errors() as exc:
            if all(r._dispatched for r in members):
                raise
            # First fused dispatch failed (typically an untraceable user
            # fn in some stage): permanently un-fuse this head and replay
            # per-edge — the per-edge first-dispatch fallback demotes the
            # offending stage on its own tick, mirrors intact.
            import warnings
            warnings.warn(
                f"device plane: fused chain dispatch at {self.op.name!r} "
                f"failed ({type(exc).__name__}: {exc}); falling back to "
                f"per-edge dispatch", RuntimeWarning, stacklevel=2)
            self.engine.incidents.record(
                "chain-fallback", tick=self.engine.tick,
                edge=self.op.name, cause=f"{type(exc).__name__}: {exc}",
                action="per-edge dispatch")
            if not ingested:
                self.staged = chunks + self.staged
                self.staged_live = sum(c.n_live for c in self.staged)
            self._chain_disabled = True
            return self.tick(budget)
        for r, st in zip(members, states_t):
            r.state = st
            r._dispatched = True
        for r, was_empty in zip(members, empty_before):
            # Everything delivered inside this dispatch was placed under
            # the chain's token (fusibility already proved any surviving
            # backlog shares it).
            if was_empty or r._placed_token == tok:
                r._placed_token = tok
            else:
                r._placed_token = None
        handed = None      # (lanes, live) the previous stage handed on
        for r, spec, (hist, take, emitted) in zip(members, specs, metrics):
            if r.kind == "fold" and handed is not None:
                obs.count("device.fold_lanes", handed[0])
                obs.count("device.fold_live", handed[1])
            handed = None
            hist = _readback(hist, "hist")
            r.edge.exchange.account(hist)
            r.received += hist
            if take is None:            # sink tail: no rings, direct fold
                r.op.workers[0].stats.processed_total += int(hist.sum())
            else:
                take = _readback(take, "take")
                r._count_map_pop(spec, take)
                r._count_ring(hist)
                r.lens += hist - take
                if r.kind == "rows":    # every popped row was appended
                    r.rows_len += take
                for w, worker in enumerate(r.op.workers):
                    worker.stats.processed_total += int(take[w])
            if emitted is not None:
                em = _readback(emitted, "emitted")
                for w, worker in enumerate(r.op.workers):
                    worker.stats.emitted_total += int(em[w])
                handed = (r._carry_width(spec), int(em.sum()))
        for r in members[1:]:
            r._chain_serial = eng._super_serial
        if dc is not None:
            self.placements += 1        # the chain's single placement
        if out is not None:             # map tail: emit downstream
            n_live = int(em.sum())      # the tail stage's emitted counts
            tail = members[-1]
            if n_live and tail.op.out_edge is not None:
                tail.op.out_edge.send(DeviceChunk(*out, n_live))
        return []

    def _count_ring(self, pushed: np.ndarray) -> None:
        """Ring occupancy of one ring-holding dispatch, from the host
        mirrors (call before they take the dispatch's pops): the slots
        swept, ``W * cap``, and the records resident once this dispatch's
        ``pushed`` records landed."""
        obs.count("device.ring_slots", self.W * self.cap)
        obs.count("device.ring_live",
                  int((self.lens - self.spilled_lens).sum() + pushed.sum()))

    def flush_staged(self) -> None:
        """Route staged chunks into the rings without popping (budget 0).

        A blocking upstream's END flush (engine phase 3) can stage a
        chunk *after* this operator's tick in the same super-tick; the
        host plane would already have routed it into the queues, so
        every boundary read (controller metrics, checkpoint cuts) first
        flushes to keep queue lengths, received totals and key-arrival
        stats bit-identical.  The sink keeps its staged chunks (they
        materialize as queue content instead)."""
        if self.staged and self.kind != "sink" and self.op.device is self:
            self.tick(0)

    def _dispatch(self, step, spec: StepSpec, chunks, budget) -> List:
        if chunks and self.kind != "sink":
            # Placement-epoch tracking: the ingested chunks are placed
            # under the *current* table iff the uploaded consts are
            # current (a version-stale flush places under the old,
            # now-unrecoverable table: None).  Content layered over
            # differently-placed backlog poisons the epoch until the
            # rings drain.
            tok = (self._live_token()
                   if self._consts_version == self.routing.version
                   else None)
            if int(self.lens.sum()) == 0:
                self._placed_token = tok
            elif self._placed_token != tok:
                self._placed_token = None
        with _x64():
            if self.kind == "sink":
                for ch in chunks:      # received accounted at stage time
                    with obs.span("device.dispatch"):
                        self.state, _ = step(spec, self.consts, self.state,
                                             (ch.keys, ch.vals, ch.valid))
                    # The host-plane pop happens in this same tick slot.
                    self.op.workers[0].stats.processed_total += ch.n_live
                self._dispatched = True
                return []
            seq = ([(c, 0) for c in chunks[:-1]]
                   + [(chunks[-1], budget)]) if chunks else [(None, budget)]
            outs: List[DeviceChunk] = []
            pushed = np.zeros(self.W, dtype=np.int64)
            for ch, b in seq:
                dc = (None if ch is None
                      else (ch.keys, ch.vals, ch.valid))
                self._count_ingest(ch)
                if self.kind in MAP_KINDS:
                    spec = dataclasses.replace(spec, P=self._flat_width(
                        b, 0 if ch is None else ch.n_live))
                with obs.span("device.dispatch"):
                    res = step(spec, self.consts, self.state, dc,
                               np.int64(b))
                if ch is not None:
                    self.placements += 1
                if self.kind in ("fold", "rows"):
                    self.state, (hist, take) = res
                    emitted = None
                else:
                    self.state, out, (hist, take, emitted) = res
                self._dispatched = True
                hist = _readback(hist, "hist")
                take = _readback(take, "take")
                self._count_map_pop(spec, take)
                self.edge.exchange.account(hist)
                self.received += hist
                self._count_ring(hist)
                self.lens += hist - take
                pushed += hist
                if self.kind == "rows":   # every popped row was appended
                    self.rows_len += take
                for w, worker in enumerate(self.op.workers):
                    worker.stats.processed_total += int(take[w])
                if emitted is not None:
                    em = _readback(emitted, "emitted")
                    n_live = int(em.sum())
                    for w, worker in enumerate(self.op.workers):
                        worker.stats.emitted_total += int(em[w])
                    if n_live:
                        outs.append(DeviceChunk(*out, n_live))
            if (self.spill is not None and pushed.any()
                    and any(self.spill.rings)):
                # Ordering invariant: fresh pushes behind spilled spans
                # re-tier to the spill tail (see _spill_demote_fresh).
                self._spill_demote_fresh(pushed)
        # Emission happens here (inside the op's tick slot) so the
        # downstream edge sees outputs in exactly the host plane's order.
        if outs and self.op.out_edge is not None:
            for oc in outs:
                self.op.out_edge.send(oc)
        return []

    # ---- boundary materialization ------------------------------------- #
    def sync_stats(self) -> None:
        """Drain the device per-key arrival accumulators into the host
        arrays the controller adapter reads (metric-round boundary).

        With an armed in-dispatch controller the boundary first mirrors
        its device decisions into the host twin (:meth:`DeviceController.
        drain`) so everything downstream — the adapter's arrival drain,
        checkpoint cuts, rewrites — sees a reconciled control plane."""
        if self.ctrl is not None and self.ctrl.active:
            self.ctrl.drain()
        self.flush_staged()
        if self.state is None or self.op.arrived_by_key is None:
            return
        a = _readback(self.state["arrived"], "stats")
        t = None
        pending = a.any()
        if not pending and self.ctrl is not None:
            # The in-dispatch controller drains ``arrived`` itself (the
            # owner-aggregated copy feeds its estimators), but the
            # cumulative per-key totals still need to reach the host.
            t = _readback(self.state["totals"], "stats")
            pending = bool(t.any())
        if pending:
            jnp = _jnp()
            if t is None:
                t = _readback(self.state["totals"], "stats")
            self.op.arrived_by_key += a
            self.op.key_arrivals_total += t
            with _x64():
                self.state.update(arrived=jnp.zeros(self.K, jnp.int64),
                                  totals=jnp.zeros(self.K, jnp.int64))

    def sync_sink_counts(self) -> None:
        """Sink-snapshot boundary: materialize the result columns only."""
        if self.state is not None:
            self.op.counts[:] = _readback(self.state["counts"], "sink")
            self.op.sums[:] = _readback(self.state["sums"], "sink")

    def sync_host(self) -> None:
        """Full device -> host materialization (checkpoint cut, END,
        routing rewrite, backend swap).  Device state stays authoritative
        afterwards; call :meth:`mark_state_stale` if the host copies are
        then mutated (migrations, restores).  Idempotent between
        dispatches: repeated boundary reads (e.g. per-candidate
        ``state_units`` probes in one metric round) pay one transfer."""
        self.flush_staged()
        if self.state is None or self._host_fresh:
            return
        if self._reload_pending:
            # The host was mutated after the last sync and no dispatch
            # has run since: the host copies are *ahead* of the device —
            # materializing now would clobber them with stale state.
            return
        with obs.span("device.sync_host"):
            self._materialize()

    def _materialize(self) -> None:
        """The body of :meth:`sync_host`: device state into the host
        structures."""
        op = self.op
        if self.kind != "sink":
            rk = _readback(self.state["rk"], "sync_host")
            rv = _val_floats(_readback(self.state["rv"], "sync_host"))
            head = _readback(self.state["head"], "sync_host")
            for w, worker in enumerate(op.workers):
                res = int(self.lens[w] - self.spilled_lens[w])
                idx = ring_span(head[w], res, self.cap)
                k_w, v_w = rk[w, idx].copy(), rv[w, idx].copy()
                if self.spilled_lens[w]:
                    # Logical order is [resident][spilled]: the host
                    # queue gets resident records first, then the CRC-
                    # verified cold spans in deque order.
                    try:
                        segs = self.spill.drain_ring(w)
                    except spill_tier.SpillCorruptError as exc:
                        self._spill_corrupt_incident(exc)
                        raise
                    k_w = np.concatenate([k_w] + [s.arrays[0] for s in segs])
                    v_w = np.concatenate(
                        [v_w] + [_val_floats(s.arrays[1]) for s in segs])
                worker.queue.restore((k_w, v_w), int(self.received[w]))
        if self.kind == "fold":
            cnt, sm, pres, scnt, ssm, spres = (
                _readback(self.state[name], "sync_host")
                for name in ("counts", "sums", "present", "scat_counts",
                             "scat_sums", "scat_present"))
            for w, worker in enumerate(op.workers):
                worker.state.load_dense(cnt[w], sm[w], pres[w])
                worker.scattered.load_dense(scnt[w], ssm[w], spres[w])
        if self.kind == "rows":
            # Regroup the arrival-order row log by key into the host
            # ScopeRows pair (owned flag -> state vs scattered); the
            # stable grouping inside ``extend_segments`` preserves each
            # scope's arrival order, so scope arrays are bit-identical
            # to the host plane's per-chunk segment appends.
            bk = _readback(self.state["bk"], "sync_host")
            bv = _val_floats(_readback(self.state["bv"], "sync_host"))
            bo = _readback(self.state["bo"], "sync_host")
            for w, worker in enumerate(op.workers):
                n = int(self.rows_len[w] - self.spilled_rows[w])
                k_w, v_w, o_w = bk[w, :n], bv[w, :n], bo[w, :n]
                if self.spilled_rows[w]:
                    # Spilled row segments are the *oldest* rows (a
                    # prefix per worker): re-materialize them ahead of
                    # the resident suffix so arrival order is exact.
                    try:
                        segs = self.spill.drain_rows(w)
                    except spill_tier.SpillCorruptError as exc:
                        self._spill_corrupt_incident(exc)
                        raise
                    k_w = np.concatenate([s.arrays[0] for s in segs]
                                         + [k_w])
                    v_w = np.concatenate([_val_floats(s.arrays[1])
                                          for s in segs] + [v_w])
                    o_w = np.concatenate([s.arrays[2] for s in segs]
                                         + [o_w])
                worker.state.clear()
                worker.scattered.clear()
                worker.state.extend_segments(k_w[o_w], v_w[o_w])
                worker.scattered.extend_segments(k_w[~o_w], v_w[~o_w])
        if self.kind == "sink":
            self.sync_sink_counts()
            parts = [ch.to_host() for ch in self.staged]
            if parts:
                k = np.concatenate([p[0] for p in parts])
                v = np.concatenate([p[1] for p in parts])
            else:
                k = np.zeros(0, np.int64)
                v = np.zeros(0, np.float64)
            op.workers[0].queue.restore((k, v), int(self.received[0]))
        self.sync_stats()
        self.routing.sync_counters()
        if _sanitize.enabled():
            self._sanitize_check()
        self._host_fresh = True

    def _sanitize_check(self) -> None:
        """Boundary sanitizers (``REPRO_SANITIZE=1``): cross-check the
        exact host mirrors against materialized device truth and guard
        fold sums against NaN/inf.  Violations are structured incidents
        (``sanitize-mirror`` / ``sanitize-nan``) plus a hard failure."""
        if self.state is None:
            return
        problems = []
        if self.kind != "sink":
            dev = (_readback(self.state["tail"], "sanitize")
                   - _readback(self.state["head"], "sanitize"))
            resident = self.lens - self.spilled_lens
            if not np.array_equal(dev, resident):
                problems.append((
                    "sanitize-mirror",
                    f"queue-length mirror {resident.tolist()} (total "
                    f"{self.lens.tolist()} - spilled "
                    f"{self.spilled_lens.tolist()}) != device "
                    f"tail-head {dev.tolist()}"))
        if self.kind == "rows":
            rlen = _readback(self.state["rlen"], "sanitize")
            rres = self.rows_len - self.spilled_rows
            if not np.array_equal(rlen, rres):
                problems.append((
                    "sanitize-mirror",
                    f"rows_len mirror {rres.tolist()} (total "
                    f"{self.rows_len.tolist()} - spilled "
                    f"{self.spilled_rows.tolist()}) != device "
                    f"rlen {rlen.tolist()}"))
        # Spill cross-check: host-side segment totals must equal the
        # spilled-count mirrors exactly (resident + spilled == totals).
        for w in range(self.W):
            host_ring = self.spill.ring_len(w) if self.spill else 0
            host_rows = self.spill.rows_len(w) if self.spill else 0
            if (host_ring != int(self.spilled_lens[w])
                    or host_rows != int(self.spilled_rows[w])):
                problems.append((
                    "sanitize-spill",
                    f"worker {w}: spill segments hold {host_ring} ring / "
                    f"{host_rows} row records but mirrors say "
                    f"{int(self.spilled_lens[w])} / "
                    f"{int(self.spilled_rows[w])}"))
        for name in ("sums", "scat_sums"):
            if name in self.state:
                if not np.isfinite(
                        _readback(self.state[name], "sanitize")).all():
                    problems.append((
                        "sanitize-nan",
                        f"non-finite values in fold state {name!r}"))
        for kind, cause in problems:
            self.engine.incidents.record(
                kind, tick=self.engine.tick, edge=self.op.name,
                cause=cause, action="fail (REPRO_SANITIZE=1)")
        if problems:
            raise _sanitize.SanitizeError(
                f"device-plane sanitizer tripped at a sync_host "
                f"boundary on {self.op.name!r}: "
                + "; ".join(c for _, c in problems))

    def mark_state_stale(self) -> None:
        """The host copies were mutated (migration / merge / restore):
        reload the device state from them before the next dispatch.

        The reload itself is deferred (``_reload_pending``) so a rewrite
        migrating m keys — m ``migrate_state`` calls, each guarded by a
        sync/stale pair — costs one download and one upload, not m."""
        if self.ctrl is not None and self.ctrl.active:
            # Host keyed state moved under the device controller (a
            # migration or merge it cannot replicate): the recorded
            # demotion rule is to reconcile and step on the host.
            self.ctrl.deactivate("host state mutated")
        if self.state is None:
            return
        self.routing.sync_counters()
        self.routing._count_owner = None
        self._host_fresh = False
        self._reload_pending = True
        self._consts_version = -1

    def on_restore(self) -> None:
        """Checkpoint restore rewrote every host structure: drop the
        device state and re-upload from the restored host truth.

        The reload is eager — a restored backlog must be poppable on the
        very next tick even if no new chunk ever arrives (sources may
        already be exhausted), so waiting for the next ``stage`` would
        stall END propagation forever.
        """
        self.state = None
        self.consts = None
        self._consts_version = -1
        self._chain_serial = -1        # never "already ticked" post-restore
        self.staged, self.staged_live = [], 0
        # Restored host structures hold the *full* content; any spill
        # segments predate the restore and must not be re-applied.
        self.spilled_lens[:] = 0
        self.spilled_rows[:] = 0
        if self.spill is not None:
            self.spill.clear()
        for w, worker in enumerate(self.op.workers):
            self.lens[w] = len(worker.queue)
            self.received[w] = worker.queue.received_total
        if self.kind == "sink":
            self.lens[:] = 0
        if not self.op.finished:
            self._ensure_ready()    # re-upload rings/state/backlog now
        if self.ctrl is not None:
            self.ctrl.on_restore()  # re-form from restored host (or demote)
