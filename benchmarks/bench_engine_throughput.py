"""Engine data-plane throughput: tuples/sec through a Filter -> GroupBy
pipeline under the fused exchange + batched tick scheduler.

Sweeps worker counts and chunk sizes (the per-tick service rate) over a
zipf-skewed key stream and reports tuples/sec for:

  reference   the pre-refactor tuple-at-a-time plane (dict state,
              per-worker mask scatter) — the baseline
  columnar    the PR-1 columnar plane: fused exchange, per-tick scheduler
              (``batch_ticks=1``) — isolates the batched scheduler's gain
  numpy       the full fused host plane: numpy partition backend + batched
              tick scheduler (``batch_ticks=BATCH`` super-chunk passes)
  pallas      the device-resident exchange plane
              (:mod:`repro.dataflow.device`) at its auto-selected
              executor: the fused jitted super-tick step on TPU, the
              bit-identical host twin off TPU — so off-TPU rows measure
              the plane architecture (same canonical routing rule, fused
              super-tick structure), not XLA:CPU's serial scatter lowering
  pallas_jit  the jitted device step *forced* off-TPU (short stream: every
              super-tick really dispatches the donated-buffer XLA step,
              interpret-style) — tracks the true device-plane code path's
              off-TPU cost so its trajectory is visible PR over PR

Every row's ``speedup_vs_reference`` is computed against a reference
baseline timed at the *same* stream length (the pallas_jit rows run a
shorter stream, so they get their own same-``n`` baseline).

The ``chain_*`` rows (PR 4) document multi-edge chain fusion: same-key
Filter→GroupBy (``fg``) and Filter→Project→GroupBy (``fpg``) pipelines
under the forced-jit device plane, fused vs ``device_chain=False``, with
a ``placements_per_supertick`` column measured over the emitting phase —
the fused rows pay exactly one partition+scatter per super-tick for the
whole chain (2→1 and 3→1 drops), with sink counts asserted identical
across every variant and the host-fused numpy baseline.

The ``join_*`` / ``sort_*`` rows (PR 5) document the row-state operator
set on the device plane: Filter→HashJoinProbe→Sink (W1 shape, 2-rows-
per-key build side) and RangeSort→Sink (W3 shape).  ``*_pallas`` is the
device plane at its auto executor (jit on TPU, host twin off TPU — the
acceptance rows: ≥5x the per-chunk path at chunk=64, ~numpy at ≥512);
``*_pallas_chunk`` is the per-chunk pallas path those edges previously
demoted to (the operator subclassed so ``device.wireable``'s exact-type
check keeps its edge per-chunk — the pre-PR-5 plane); ``*_jit`` is the
forced-jit off-TPU trajectory row.  Each shape carries an honest
same-``n`` reference baseline and sink counts asserted identical.
``join_jit`` vs ``join_jit_unfused`` carries the probe chain fusion
placement drop (Filter→Probe: 2→1 ``placements_per_supertick``).

Acceptance bar for the device-resident plane (PR 3): ``pallas`` >= 100x
the PR-2 pallas rows (which re-entered the Pallas interpreter per chunk:
2,650 tuples/s at chunk=64) and within ~2x of ``numpy`` at chunk >= 512.
The table is persisted to ``BENCH_engine_throughput.json`` at the repo
root with provenance fields (git SHA, jax backend, UTC timestamp) so the
perf trajectory is comparable across PRs.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.dataflow.engine import Engine, Source
from repro.dataflow.operators import (Filter, GroupByAgg, HashJoinProbe,
                                      RangeSort, Sink)

from . import common
from .common import emit

NUM_KEYS = 64
ZIPF_A = 1.4
BATCH = 8          # batched-scheduler window (and the sink snapshot cadence)
PALLAS_JIT_N = 20_000   # forced-jit off-TPU: keep the stream short

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
JSON_PATH = os.path.join(REPO_ROOT, "BENCH_engine_throughput.json")


def _all_pass(k, v):
    """Module-level predicate: a stable identity keys the device plane's
    jit trace cache, so repeated engine builds never retrace."""
    return v >= 0


def _scale_val(k, v):
    """Key-preserving Project map (chain-fusible; stable identity)."""
    return k, v * 2.0


def _stream(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    keys = np.minimum(rng.zipf(ZIPF_A, n) - 1, NUM_KEYS - 1).astype(np.int64)
    vals = rng.uniform(0.0, 10.0, n)
    return keys, vals


def _build(n_tuples, num_workers, chunk, *, reference=False, backend=None,
           batch_ticks=1, device_executor=None):
    keys, vals = _stream(n_tuples)
    eng = Engine(partition_backend=backend, reference=reference,
                 batch_ticks=batch_ticks, device_executor=device_executor)
    src = eng.add_source(Source("zipf", keys, vals, num_workers * chunk))
    filt = eng.add_op(Filter("filter", num_workers, num_workers * chunk,
                             predicate=_all_pass))
    if reference:
        from repro.dataflow.reference import RefGroupByAgg as Grp
    else:
        Grp = GroupByAgg
    grp = eng.add_op(Grp("groupby", num_workers, chunk))
    # Snapshot every BATCH ticks for every mode, so the result cadence —
    # which bounds tick fusion — is identical across rows.
    sink = eng.add_op(Sink("sink", NUM_KEYS, snapshot_every=BATCH))
    eng.connect(src, filt, NUM_KEYS)
    eng.connect(filt, grp, NUM_KEYS)
    eng.connect(grp, sink, NUM_KEYS)
    return eng, sink


def _build_chain(n_tuples, num_workers, chunk, *, with_project=True,
                 backend=None, batch_ticks=BATCH, device_executor=None,
                 device_chain=None):
    """Filter -> [Project ->] GroupBy -> Sink over one key space: every
    edge is routing-equivalent, so the device plane fuses the whole run
    into one placement + one dispatch per super-tick."""
    from repro.dataflow.operators import Project
    keys, vals = _stream(n_tuples)
    eng = Engine(partition_backend=backend, batch_ticks=batch_ticks,
                 device_executor=device_executor, device_chain=device_chain)
    src = eng.add_source(Source("zipf", keys, vals, num_workers * chunk))
    prev = src
    ops = [Filter("filter", num_workers, num_workers * chunk,
                  predicate=_all_pass)]
    if with_project:
        ops.append(Project("project", num_workers, num_workers * chunk,
                           fn=_scale_val, preserves_keys=True))
    ops.append(GroupByAgg("groupby", num_workers, chunk))
    ops.append(Sink("sink", NUM_KEYS, snapshot_every=BATCH))
    for op in ops:
        eng.add_op(op)
        eng.connect(prev, op, NUM_KEYS)
        prev = op
    return eng, ops[-1]


def _run_chain(n_tuples, num_workers, chunk, *, reps=3, **kw):
    """Timed chain run + the placements-per-emitting-super-tick metric."""
    best, sink = _time_build(_build_chain, n_tuples, num_workers, chunk,
                             reps=reps, **kw)
    per_super = _placements_per_supertick(_build_chain, n_tuples,
                                          num_workers, chunk, **kw)
    return best, sink, per_super


def _run_one(n_tuples, num_workers, chunk, *, reps=3, **kw):
    """Best-of-``reps`` tuples/sec (this box is noisy; max is the least
    contended run) plus the last run's sink for the correctness check."""
    return _time_build(_build, n_tuples, num_workers, chunk, reps=reps,
                       **kw)


def _build_monitored(n_tuples, num_workers, chunk, *, backend=None,
                     batch_ticks=BATCH, device_executor=None,
                     device_controller=None):
    """Source -> GroupByAgg (monitored at a per-tick metric cadence) ->
    Sink; the SCATTERED-eligible shape the in-dispatch controller arms
    on.  ``snapshot_every=0`` so the metric grid is the only span cut."""
    from repro.core import ReshapeConfig
    keys, vals = _stream(n_tuples)
    eng = Engine(partition_backend=backend, batch_ticks=batch_ticks,
                 device_executor=device_executor,
                 device_controller=device_controller)
    src = eng.add_source(Source("zipf", keys, vals, num_workers * chunk))
    grp = eng.add_op(GroupByAgg("groupby", num_workers, chunk))
    sink = eng.add_op(Sink("sink", NUM_KEYS, snapshot_every=0))
    eng.connect(src, grp, NUM_KEYS)
    eng.connect(grp, sink, NUM_KEYS)
    eng.attach_controller(grp, ReshapeConfig(metric_period=1))
    return eng, sink


def _run_monitored(n_tuples, num_workers, chunk, *, reps=3, **kw):
    best = 0.0
    for _ in range(reps):
        eng, sink = _build_monitored(n_tuples, num_workers, chunk, **kw)
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        best = max(best, n_tuples / max(dt, 1e-9))
    span = round(eng.tick / max(eng.super_ticks, 1), 2)
    return best, sink, span


def _monitored_rows():
    """Monitored-workflow rows (PR 6): per-tick metric cadence under the
    forced-jit device plane.  Host-stepped (``ctrl_jit``) every metric
    round is a super-tick boundary, so ``ticks_per_supertick`` collapses
    to ~1; armed (``ctrl_jit_armed``) the rounds run inside the fused
    dispatch and spans run to the full BATCH horizon — with sink counts
    (and controller decisions) bit-identical across all three rows."""
    shapes = common.smoke([(16, 512, 40_000)], [(4, 64, 1_500)])
    rows = []
    for num_workers, chunk, n in shapes:
        variants = [
            ("ctrl_numpy", dict(backend="numpy")),
            ("ctrl_jit", dict(backend="pallas", device_executor="jit")),
            ("ctrl_jit_armed", dict(backend="pallas",
                                    device_executor="jit",
                                    device_controller=True)),
        ]
        oracle = None
        for mode, opts in variants:
            tps, sink, span = _run_monitored(n, num_workers, chunk, **opts)
            if oracle is None:
                oracle = sink.counts.copy()
            else:
                assert np.array_equal(sink.counts, oracle), mode
            rows.append(dict(mode=mode, n_tuples=n, workers=num_workers,
                             chunk=chunk, tuples_per_sec=round(tps),
                             ticks_per_supertick=span))
    return rows


class _PerChunkProbe(HashJoinProbe):
    """Deliberate subclass: ``device.wireable`` is exact-type (a subclass
    may override ``process``), so this keeps the probe edge on the
    per-chunk pallas backend — the pre-PR-5 plane shape the ``join_*``
    device rows are measured against."""


class _PerChunkSort(RangeSort):
    """Same trick for the sort edge (pre-PR-5 per-chunk pallas path)."""


def _build_join(n_tuples, num_workers, chunk, *, reference=False,
                backend=None, batch_ticks=BATCH, device_executor=None,
                device_chain=None, per_chunk=False):
    """Source -> Filter -> HashJoinProbe -> Sink over one key space (the
    W1 shape; filter -> probe is the fusible probe chain).  Build side:
    2 rows per key, so every probe tuple fans out x2."""
    keys, vals = _stream(n_tuples)
    eng = Engine(partition_backend=backend, reference=reference,
                 batch_ticks=batch_ticks, device_executor=device_executor,
                 device_chain=device_chain)
    src = eng.add_source(Source("zipf", keys, vals, num_workers * chunk))
    filt = eng.add_op(Filter("filter", num_workers, num_workers * chunk,
                             predicate=_all_pass))
    if reference:
        from repro.dataflow.reference import RefHashJoinProbe as Probe
    else:
        Probe = _PerChunkProbe if per_chunk else HashJoinProbe
    join = eng.add_op(Probe("join", num_workers, chunk))
    sink = eng.add_op(Sink("sink", NUM_KEYS, snapshot_every=BATCH))
    eng.connect(src, filt, NUM_KEYS)
    je = eng.connect(filt, join, NUM_KEYS)
    eng.connect(join, sink, NUM_KEYS)
    bk = np.repeat(np.arange(NUM_KEYS, dtype=np.int64), 2)
    join.install_build(je.routing, bk, np.ones(bk.size))
    return eng, sink


def _build_sort(n_tuples, num_workers, chunk, *, reference=False,
                backend=None, batch_ticks=BATCH, device_executor=None,
                device_chain=None, per_chunk=False):
    """Source -> RangeSort -> Sink (the W3 shape; keys are range ids)."""
    keys, vals = _stream(n_tuples)
    eng = Engine(partition_backend=backend, reference=reference,
                 batch_ticks=batch_ticks, device_executor=device_executor,
                 device_chain=device_chain)
    src = eng.add_source(Source("zipf", keys, vals, num_workers * chunk))
    if reference:
        from repro.dataflow.reference import RefRangeSort as Sort
    else:
        Sort = _PerChunkSort if per_chunk else RangeSort
    sort = eng.add_op(Sort("sort", num_workers, chunk))
    sink = eng.add_op(Sink("sink", NUM_KEYS, snapshot_every=BATCH))
    eng.connect(src, sort, NUM_KEYS)
    eng.connect(sort, sink, NUM_KEYS)
    return eng, sink


def _time_build(build, n_tuples, num_workers, chunk, *, reps=3, **kw):
    best = 0.0
    for _ in range(reps):
        eng, sink = build(n_tuples, num_workers, chunk, **kw)
        t0 = time.perf_counter()
        eng.run()
        dt = time.perf_counter() - t0
        best = max(best, n_tuples / max(dt, 1e-9))
    return best, sink


def _placements_per_supertick(build, n_tuples, num_workers, chunk, **kw):
    """Placements per emitting super-tick (drain windows excluded), the
    chain-fusion provenance metric — 2 -> 1 on a fused Filter -> Probe."""
    meter, _ = build(n_tuples, num_workers, chunk, **kw)
    while not all(s.finished for s in meter.sources):
        meter.run_super_tick(meter._fusible_ticks(BATCH))
    placed = sum(getattr(e.exchange, "placements", 0) for e in meter.edges)
    per_super = placed / max(meter.super_ticks, 1)
    meter.run()
    return round(per_super, 2)


def _rowstate_rows():
    """``join_*`` / ``sort_*`` rows (PR 5): HashJoinProbe and RangeSort
    as first-class device-plane edges.  ``*_pallas`` is the device plane
    at its auto executor (fused jit step on TPU, bit-identical host twin
    off TPU — the acceptance rows); ``*_pallas_chunk`` is the per-chunk
    pallas path these edges previously demoted to (the operator
    subclassed so ``wireable`` keeps its edge per-chunk — the pre-PR-5
    plane); ``*_jit`` forces the jitted step off-TPU (trajectory rows,
    like ``pallas_jit``).  All with honest same-``n`` reference
    baselines; ``join_jit`` vs ``join_jit_unfused`` documents the probe
    chain fusion placement drop (Filter -> Probe: 2 -> 1)."""
    shapes = common.smoke([(16, 64, 4_000), (16, 512, 20_000)],
                          [(4, 64, 1_500)])
    rows = []
    for num_workers, chunk, n in shapes:
        for name, build in (("join", _build_join), ("sort", _build_sort)):
            ref_tps, ref_sink = _time_build(build, n, num_workers, chunk,
                                            reference=True)
            variants = [
                (f"{name}_reference", dict()),
                (f"{name}_numpy", dict(backend="numpy")),
                # the device plane at its auto executor (jit on TPU, the
                # bit-identical host twin off TPU) — the acceptance rows:
                # >= 5x the per-chunk path at chunk=64, ~numpy at >= 512
                (f"{name}_pallas", dict(backend="pallas")),
                (f"{name}_pallas_chunk",
                 dict(backend="pallas", device_executor="jit",
                      per_chunk=True)),
                # forced-jit trajectory rows (the true device code path's
                # off-TPU cost, like the pallas_jit rows above)
                (f"{name}_jit",
                 dict(backend="pallas", device_executor="jit")),
            ]
            if name == "join":
                variants.append((f"{name}_jit_unfused",
                                 dict(backend="pallas",
                                      device_executor="jit",
                                      device_chain=False)))
            for mode, opts in variants:
                if mode.endswith("_reference"):
                    tps, sink = ref_tps, ref_sink
                else:
                    tps, sink = _time_build(build, n, num_workers, chunk,
                                            **opts)
                assert np.array_equal(sink.counts, ref_sink.counts), mode
                row = dict(mode=mode, n_tuples=n, workers=num_workers,
                           chunk=chunk, tuples_per_sec=round(tps),
                           speedup_vs_reference=round(tps / ref_tps, 2))
                if mode.startswith("join_jit"):
                    row["placements_per_supertick"] = \
                        _placements_per_supertick(_build_join, n,
                                                  num_workers, chunk,
                                                  **opts)
                rows.append(row)
    return rows


def _plane_of(mode: str) -> str:
    """Which data plane a mode's rows actually measured — stamped into
    the perf JSON so a 'pallas' row on a CPU box (host twin) is never
    mistaken for the jitted device step when diffing across PRs."""
    if mode.startswith(("join_", "sort_")):
        if mode.endswith("_reference"):
            return "reference"
        if mode.endswith("_numpy"):
            return "host-fused"
        if mode.endswith("_pallas_chunk"):
            return "pallas-per-chunk"
        if mode.endswith("_pallas"):
            return _plane_of("pallas")  # auto executor: jit / host twin
        return "device-jit"             # *_jit, *_jit_unfused
    if mode.startswith(("chain_", "ctrl_")) and mode.endswith("_numpy"):
        return "host-fused"
    if mode.startswith(("chain_", "ctrl_")):
        return "device-jit"
    if mode == "pallas_jit":
        return "device-jit"
    if mode == "pallas":
        try:
            from repro.dataflow.device import resolve_executor
            return ("device-jit" if resolve_executor(None) == "jit"
                    else "host-twin")
        except Exception:
            return "unavailable"
    return {"reference": "reference", "columnar": "host-columnar",
            "numpy": "host-fused"}.get(mode, mode)


def _chain_rows(n: int, num_workers: int = 16, chunk: int = 512):
    """Fused-chain provenance rows (PR 4): same-key chains under the
    forced-jit device plane, fused vs per-edge, plus the host-fused
    baseline.  ``placements_per_supertick`` documents the placement-work
    drop — the Filter→GroupBy chain pays 2 partition+scatter dispatches
    per emitting super-tick per-edge and exactly 1 fused (the second
    edge's placement is eliminated); Filter→Project→GroupBy drops 3→1.
    Sink counts are asserted identical across every variant."""
    variants = [
        # (mode, with_project, engine kwargs)
        ("chain_fg_numpy", False, dict(backend="numpy")),
        ("chain_fg_jit", False, dict(backend="pallas",
                                     device_executor="jit")),
        ("chain_fg_jit_unfused", False, dict(backend="pallas",
                                             device_executor="jit",
                                             device_chain=False)),
        ("chain_fpg_numpy", True, dict(backend="numpy")),
        ("chain_fpg_jit", True, dict(backend="pallas",
                                     device_executor="jit")),
        ("chain_fpg_jit_unfused", True, dict(backend="pallas",
                                             device_executor="jit",
                                             device_chain=False)),
    ]
    rows = []
    oracle = {}
    for mode, with_project, opts in variants:
        tps, sink, per_super = _run_chain(n, num_workers, chunk,
                                          with_project=with_project, **opts)
        if with_project in oracle:
            assert np.array_equal(sink.counts, oracle[with_project]), mode
        else:
            oracle[with_project] = sink.counts.copy()
        rows.append(dict(mode=mode, n_tuples=n, workers=num_workers,
                         chunk=chunk, tuples_per_sec=round(tps),
                         placements_per_supertick=per_super))
    return rows


def run(n_tuples: int = 200_000, include_pallas: bool = True) -> None:
    n_tuples = common.smoke(n_tuples, 2_000)
    jit_n = common.smoke(PALLAS_JIT_N, 0)    # skip forced-jit rows in smoke
    prov = common.provenance()
    rows = []
    for num_workers, chunk in [(w, c) for w in common.smoke((4, 16), (4,))
                               for c in common.smoke((64, 512, 2048), (64,))]:
        baselines = {}          # stream length -> (tps, sink)

        def base(n):
            if n not in baselines:
                baselines[n] = _run_one(n, num_workers, chunk,
                                        reference=True)
            return baselines[n]

        base_tps = base(n_tuples)[0]
        rows.append(dict(mode="reference", n_tuples=n_tuples,
                         workers=num_workers, chunk=chunk,
                         tuples_per_sec=round(base_tps),
                         speedup_vs_reference=1.0))
        variants = [
            ("columnar", dict(backend="numpy", batch_ticks=1)),
            ("numpy", dict(backend="numpy", batch_ticks=BATCH)),
        ]
        if include_pallas:
            variants.append(("pallas", dict(backend="pallas",
                                            batch_ticks=BATCH)))
            if jit_n:
                variants.append(("pallas_jit", dict(
                    backend="pallas", batch_ticks=BATCH,
                    device_executor="jit", n=min(n_tuples, jit_n))))
        for mode, opts in variants:
            n = opts.pop("n", n_tuples)
            tps, sink = _run_one(n, num_workers, chunk, **opts)
            ref_tps, ref_sink = base(n)   # honest same-n baseline
            assert np.array_equal(sink.counts, ref_sink.counts), mode
            rows.append(dict(
                mode=mode, n_tuples=n, workers=num_workers, chunk=chunk,
                tuples_per_sec=round(tps),
                speedup_vs_reference=round(tps / ref_tps, 2)))
    if include_pallas:
        rows += _chain_rows(common.smoke(40_000, 2_000))
        rows += _rowstate_rows()
        rows += _monitored_rows()
    emit("engine_throughput", rows,
         ["mode", "workers", "chunk", "tuples_per_sec",
          "speedup_vs_reference", "placements_per_supertick",
          "ticks_per_supertick"],
         size=dict(n_tuples=n_tuples), prov=prov)
    # Perf trajectory for future PRs to diff against (provenance-stamped).
    # Smoke mode validates the JSON contract against a side path so the
    # repo-root trajectory is never clobbered by tiny-n runs.
    json_path = JSON_PATH if not common.SMOKE else os.path.join(
        common.RESULTS_DIR, "BENCH_engine_throughput.smoke.json")
    os.makedirs(os.path.dirname(json_path), exist_ok=True)
    with open(json_path, "w") as f:
        json.dump([dict({k: r[k] for k in
                         ("mode", "n_tuples", "workers", "chunk",
                          "tuples_per_sec", "placements_per_supertick",
                          "ticks_per_supertick")
                         if k in r},
                        plane=_plane_of(r["mode"]), **prov)
                   for r in rows], f, indent=1)
        f.write("\n")


if __name__ == "__main__":
    run()
