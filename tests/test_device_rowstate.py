"""Row-state operators on the device-resident exchange plane.

PR 5 contract: ``HashJoinBuild`` / ``HashJoinProbe`` / ``RangeSort`` run
as first-class device-jit edges — keyed row state in a device segment
store mirroring :class:`~repro.dataflow.state.ScopeRows`, the probe as a
capacity-bounded expand stage chaining like a map — and every run is
**bit-identical** to the numpy host plane and the tuple-at-a-time
reference oracle: ``Sink.series``, ``sent_per_worker``, routing
counters, worker mirrors, per-scope row arrays at materialization
boundaries, controller event streams, and checkpoint cuts.  The
satellite bugfixes (probe owned+scattered sum, mid-run
``sorted_output`` under an active split, ScatterPlan-routed
``install_build``) are pinned here too.
"""
import numpy as np
import pytest

pytest.importorskip("jax")

from _propcheck import given, settings, st

from repro.core import ReshapeConfig
from repro.dataflow import checkpoint as ckpt
from repro.dataflow.engine import Engine, Source
from repro.dataflow.operators import (Filter, HashJoinBuild, HashJoinProbe,
                                      RangeSort, Sink)

NK = 16


def _series_equal(a, b):
    return (len(a) == len(b)
            and all(t1 == t2 and np.array_equal(c1, c2)
                    for (t1, c1), (t2, c2) in zip(a, b)))


def _all_pass(k, v):
    return v >= 0


def _stream(n, seed=0, hot=0.5, nk=NK):
    rng = np.random.default_rng(seed)
    keys = np.minimum(rng.zipf(1.3, n) - 1, nk - 1).astype(np.int64)
    keys[rng.random(n) < hot] = 0
    return keys, rng.uniform(0.0, 10.0, n)


def _build_table(nk=NK):
    """Multi-row build side: key k holds 1 + (k % 3) rows, so the probe
    fanout is per-key variable (M = 3) — the expand step is exercised."""
    bk = np.repeat(np.arange(nk, dtype=np.int64),
                   1 + (np.arange(nk) % 3))
    return bk, np.ones(bk.size, dtype=np.float64)


def _join_pipeline(backend=None, *, n=5000, num_workers=4, chunk=8,
                   batch_ticks=4, controller=False, seed=1, hot=0.5,
                   reference=False, **engine_kw):
    """Source -> Filter -> HashJoinProbe -> Sink over one key space (the
    W1 shape; filter -> probe is the canonical fusible probe chain)."""
    keys, vals = _stream(n, seed, hot)
    eng = Engine(partition_backend=backend, batch_ticks=batch_ticks,
                 reference=reference, **engine_kw)
    if reference:
        from repro.dataflow.reference import REFERENCE_OPS
        probe_cls = REFERENCE_OPS[HashJoinProbe]
    else:
        probe_cls = HashJoinProbe
    src = eng.add_source(Source("src", keys, vals, num_workers * chunk))
    filt = eng.add_op(Filter("filter", num_workers, num_workers * chunk,
                             predicate=_all_pass))
    join = eng.add_op(probe_cls("join", num_workers, chunk))
    sink = eng.add_op(Sink("sink", NK, snapshot_every=batch_ticks))
    eng.connect(src, filt, NK)
    je = eng.connect(filt, join, NK)
    eng.connect(join, sink, NK)
    join.install_build(je.routing, *_build_table())
    ctrl = None
    if controller:
        ctrl = eng.attach_controller(join, ReshapeConfig(metric_period=4))
    return eng, sink, join, ctrl


def _sort_pipeline(backend=None, *, n=5000, num_workers=4, chunk=8,
                   batch_ticks=4, controller=False, seed=2, hot=0.5,
                   reference=False, **engine_kw):
    """Source -> RangeSort -> Sink (the W3 shape)."""
    keys, vals = _stream(n, seed, hot)
    eng = Engine(partition_backend=backend, batch_ticks=batch_ticks,
                 reference=reference, **engine_kw)
    if reference:
        from repro.dataflow.reference import REFERENCE_OPS
        sort_cls = REFERENCE_OPS[RangeSort]
    else:
        sort_cls = RangeSort
    src = eng.add_source(Source("src", keys, vals, num_workers * chunk))
    sort = eng.add_op(sort_cls("sort", num_workers, chunk))
    sink = eng.add_op(Sink("sink", NK, snapshot_every=batch_ticks))
    eng.connect(src, sort, NK)
    eng.connect(sort, sink, NK)
    ctrl = None
    if controller:
        ctrl = eng.attach_controller(sort, ReshapeConfig(metric_period=4))
    return eng, sink, sort, ctrl


def _build_pipeline(backend=None, *, n=3000, num_workers=4, chunk=8,
                    batch_ticks=4, seed=3, **engine_kw):
    """Source -> HashJoinBuild (blocking terminal: device row state)."""
    keys, vals = _stream(n, seed)
    eng = Engine(partition_backend=backend, batch_ticks=batch_ticks,
                 **engine_kw)
    src = eng.add_source(Source("src", keys, vals, num_workers * chunk))
    bld = eng.add_op(HashJoinBuild("build", num_workers, chunk))
    eng.connect(src, bld, NK)
    return eng, None, bld, None


def _assert_runs_identical(a, b):
    assert a[0].tick == b[0].tick
    if a[1] is not None:
        assert _series_equal(a[1].series, b[1].series)
        np.testing.assert_array_equal(a[1].counts, b[1].counts)
    for ea, eb in zip(a[0].edges, b[0].edges):
        np.testing.assert_array_equal(ea.sent_per_worker, eb.sent_per_worker)
        eb.routing.sync_counters()
        np.testing.assert_array_equal(ea.routing._count, eb.routing._count)
    if a[3] is not None:
        assert ([e.kind for e in a[3].events]
                == [e.kind for e in b[3].events])
    for oa, ob in zip(a[0].ops, b[0].ops):
        for wa, wb in zip(oa.workers, ob.workers):
            assert wa.stats.processed_total == wb.stats.processed_total
            assert wa.stats.emitted_total == wb.stats.emitted_total


def _assert_row_state_identical(op_a, op_b):
    """Per-worker ScopeRows equality: scope sets + exact scope arrays."""
    op_b._device_sync()
    for wa, wb in zip(op_a.workers, op_b.workers):
        for ta, tb in ((wa.state, wb.state), (wa.scattered, wb.scattered)):
            assert set(ta.keys()) == set(tb.keys())
            for k in ta.keys():
                np.testing.assert_array_equal(ta.scope_array(int(k)),
                                              tb.scope_array(int(k)))


class TestRowStateEquivalence:
    def test_join_pipeline_bit_identical(self):
        """Filter -> Probe -> Sink with a variable-fanout build table:
        series / counts / mirrors identical to numpy, probe edge wired
        jit (no silent demotion)."""
        a = _join_pipeline("numpy")
        a[0].run()
        b = _join_pipeline("pallas", device_executor="jit")
        b[0].run()
        assert all(e.device_plane == "jit" for e in b[0].edges)
        _assert_runs_identical(a, b)

    def test_sort_pipeline_bit_identical(self):
        a = _sort_pipeline("numpy")
        a[0].run()
        b = _sort_pipeline("pallas", device_executor="jit")
        b[0].run()
        assert all(e.device_plane == "jit" for e in b[0].edges)
        _assert_runs_identical(a, b)
        _assert_row_state_identical(a[2], b[2])
        np.testing.assert_array_equal(a[2].sorted_output(),
                                      b[2].sorted_output())

    def test_build_row_state_identical(self):
        """Device HashJoinBuild: the flat segment store materializes into
        the exact ScopeRows the host plane holds (scope arrays
        bit-identical, arrival order preserved)."""
        a = _build_pipeline("numpy")
        a[0].run()
        b = _build_pipeline("pallas", device_executor="jit")
        b[0].run()
        assert all(e.device_plane == "jit" for e in b[0].edges)
        _assert_runs_identical(a, b)
        _assert_row_state_identical(a[2], b[2])

    def test_values_travel_as_bit_patterns(self):
        """A TPU keeps float64 as a pair of float32 and rounds it on
        upload, so the device plane carries values as their int64 bit
        patterns: rings and row store hold int64, and values that need
        all 53 significand bits, -0.0 and a subnormal read back with the
        same bits."""
        rng = np.random.default_rng(5)
        vals = np.concatenate([rng.random(1_000) / 3.0,
                               [-0.0, 5e-324, np.pi * 1e300]])
        keys = rng.integers(0, NK, vals.size)
        eng = Engine(partition_backend="pallas", batch_ticks=4,
                     device_executor="jit")
        src = eng.add_source(Source("src", keys, vals, 32))
        bld = eng.add_op(HashJoinBuild("build", 4, 8))
        eng.connect(src, bld, NK)
        eng.run_super_tick(4)
        assert bld.device.state["rv"].dtype == np.int64
        assert bld.device.state["bv"].dtype == np.int64
        eng.run()
        assert all(e.device_plane == "jit" for e in eng.edges)
        bld._device_sync()
        got = np.concatenate([t.scope_array(int(k))
                              for w in bld.workers
                              for t in (w.state, w.scattered)
                              for k in t.keys()])
        np.testing.assert_array_equal(np.sort(got.view(np.int64)),
                                      np.sort(vals.view(np.int64)))

    def test_join_controller_rewrites_and_migrations(self):
        """Reshape on the device probe (the W1 shape): detections,
        phase-1/2 rewrites, REPLICATE migrations of the build state and
        the event stream replay identically."""
        kw = dict(num_workers=6, controller=True, n=8000, seed=1)
        a = _join_pipeline("numpy", **kw)
        a[0].run()
        b = _join_pipeline("pallas", device_executor="jit", **kw)
        b[0].run()
        _assert_runs_identical(a, b)
        assert any(e.kind == "phase2" for e in b[3].events)

    def test_sort_controller_rewrites_and_scattered_merge(self):
        """Reshape on the device sort: SBR splits scatter rows to helper
        workers on-device; END merge and the run replay identically."""
        kw = dict(num_workers=6, controller=True, n=8000, seed=4)
        a = _sort_pipeline("numpy", **kw)
        a[0].run()
        b = _sort_pipeline("pallas", device_executor="jit", **kw)
        b[0].run()
        _assert_runs_identical(a, b)
        assert any(e.kind == "phase2" for e in b[3].events)
        _assert_row_state_identical(a[2], b[2])
        for w in b[2].workers:
            assert not len(w.scattered)          # merged at END

    @pytest.mark.parametrize("use_kernel", [False, True])
    def test_split_tables_with_kernel_partition_core(self, use_kernel):
        """Manual SBR splits on probe + sort edges; with
        ``device_use_kernel=True`` the rows ingest runs the fused Pallas
        ``partition_scatter_fold`` kernel — runs stay bit-identical."""
        def scenario(build, ei, backend, **kw):
            t = build(backend, controller=False, n=3000, **kw)
            for _ in range(4):
                t[0].run_super_tick(t[0]._fusible_ticks(4))
            t[0].edges[ei].routing.split_key(0, [0, 1], [0.5, 0.5])
            t[0].run()
            return t

        for build, ei in ((_join_pipeline, 1), (_sort_pipeline, 0)):
            a = scenario(build, ei, "numpy")
            b = scenario(build, ei, "pallas", device_executor="jit",
                         device_use_kernel=use_kernel)
            assert all(e.device_plane == "jit" for e in b[0].edges)
            _assert_runs_identical(a, b)

    def test_w3_full_device_plane_matches_reference_oracle(self):
        """The W3 workflow end-to-end: every edge device-jit, series and
        the globally sorted output bit-identical to the reference
        oracle."""
        from repro.dataflow import build_w3
        kw = dict(strategy="reshape", n_tuples=3000, num_workers=8,
                  service_rate=6, batch_ticks=4, snapshot_every=2)
        r = build_w3(reference=True, **kw)
        r.run()
        b = build_w3(partition_backend="pallas", device_executor="jit",
                     **kw)
        b.run()
        assert [e.device_plane for e in b.engine.edges] == ["jit", "jit"]
        assert _series_equal(r.sink.series, b.sink.series)
        np.testing.assert_array_equal(r.monitored[0].sorted_output(),
                                      b.monitored[0].sorted_output())
        np.testing.assert_allclose(b.monitored[0].sorted_output(),
                                   np.sort(b.meta["prices"]))


class TestProbeChainFusion:
    def test_filter_probe_placements_2_to_1(self):
        """The acceptance shape: a token-equal Filter -> Probe chain pays
        one placement per emitting super-tick fused (the probe edge's
        partition+scatter is eliminated), two per-edge."""
        fused = _join_pipeline("pallas", device_executor="jit")
        fused[0].run()
        apart = _join_pipeline("pallas", device_executor="jit",
                               device_chain=False)
        apart[0].run()
        _assert_runs_identical(fused, apart)
        f_head = fused[0].edges[0].exchange.placements
        assert f_head > 0
        assert fused[0].edges[1].exchange.placements == 0   # eliminated
        assert apart[0].edges[0].exchange.placements == f_head
        assert apart[0].edges[1].exchange.placements > 0

    def test_rewrite_breaks_probe_chain_and_stays_identical(self):
        """A mitigation splitting the probe edge voids its token: the
        chain falls back per-edge mid-run, bit-identical throughout."""
        kw = dict(num_workers=6, controller=True, n=8000, seed=1)
        b = _join_pipeline("pallas", device_executor="jit", **kw)
        b[0].run()
        a = _join_pipeline("numpy", **kw)
        a[0].run()
        _assert_runs_identical(a, b)
        # fusion engaged (probe edge paid fewer placements than the
        # host plane's one-per-send) and broke during the mitigation
        probe_edge = b[0].edges[1]
        assert 0 < probe_edge.exchange.placements \
            < a[0].edges[1].exchange.placements

    def test_probe_head_chains_into_groupby_tail(self):
        """A probe can also HEAD a chain: Probe -> GroupBy over one key
        space (the W2-ish join -> aggregate shape) advances in one fused
        dispatch — the expand output feeds the fold tail pre-placed —
        with keyed state and series bit-identical to numpy."""
        def build(backend=None, **kw):
            from repro.dataflow.operators import GroupByAgg
            keys, vals = _stream(5000, seed=0, hot=0.4)
            eng = Engine(partition_backend=backend, batch_ticks=4, **kw)
            src = eng.add_source(Source("s", keys, vals, 32))
            join = eng.add_op(HashJoinProbe("j", 4, 8))
            grp = eng.add_op(GroupByAgg("g", 4, 32))
            sink = eng.add_op(Sink("k", NK, snapshot_every=4))
            e = eng.connect(src, join, NK)
            eng.connect(join, grp, NK)
            eng.connect(grp, sink, NK)
            join.install_build(e.routing, *_build_table())
            return eng, sink, grp, None

        a = build("numpy")
        a[0].run()
        b = build("pallas", device_executor="jit")
        b[0].run()
        _assert_runs_identical(a, b)
        b[2]._device_sync()
        for wa, wb in zip(a[2].workers, b[2].workers):
            np.testing.assert_array_equal(wa.state.counts, wb.state.counts)
            np.testing.assert_allclose(wa.state.sums, wb.state.sums)
        assert b[0].edges[1].exchange.placements == 0   # fused behind probe

    def test_probe_fanout_ceiling_demotes(self):
        """A build table whose max fanout would blow MAX_EMIT_CELLS
        demotes the probe edge to the host path — and stays correct."""
        from repro.dataflow import device as dev
        keys = np.zeros(200, dtype=np.int64)
        eng = Engine(partition_backend="pallas", device_executor="jit",
                     batch_ticks=2)
        src = eng.add_source(Source("s", keys, np.ones(200), 100))
        join = eng.add_op(HashJoinProbe("j", 2, 4096))
        sink = eng.add_op(Sink("k", 8))
        e = eng.connect(src, join, 8)
        eng.connect(join, sink, 8)
        # fanout so large that W * B * M > MAX_EMIT_CELLS (B = 2 * 4096)
        m = dev.MAX_EMIT_CELLS // (2 * 2 * 4096) + 1
        join.install_build(e.routing, np.zeros(m, np.int64), np.ones(m))
        eng.run()
        assert e.device_plane.startswith("demoted")
        assert int(sink.counts[0]) == 200 * m


class TestRowStateSatelliteFixes:
    def test_probe_sums_owned_and_scattered_matches(self):
        """Regression: a split build key with rows in BOTH the owned
        table and `scattered` must match against the SUM of both row
        sets (np.where used to drop one side) — on the columnar, the
        reference and the device plane alike."""
        def build(backend=None, reference=False, **kw):
            eng = Engine(partition_backend=backend, reference=reference,
                         batch_ticks=2, **kw)
            keys = np.tile(np.arange(8, dtype=np.int64), 40)
            src = eng.add_source(Source("s", keys, np.ones(keys.size), 16))
            if reference:
                from repro.dataflow.reference import REFERENCE_OPS
                probe_cls = REFERENCE_OPS[HashJoinProbe]
            else:
                probe_cls = HashJoinProbe
            join = eng.add_op(probe_cls("j", 2, 8))
            sink = eng.add_op(Sink("k", 8, snapshot_every=2))
            e = eng.connect(src, join, 8)
            eng.connect(join, sink, 8)
            join.install_build(e.routing, np.arange(8), np.ones(8))
            # the SBR aftermath: 3 extra rows of key 0 parked scattered
            # on key 0's owner
            w0 = int(e.routing.owner[0])
            if reference:
                join.workers[w0].scattered.setdefault(0, []).extend(
                    [2.0, 2.0, 2.0])
            else:
                join.workers[w0].scattered.extend_segments(
                    np.zeros(3, np.int64), np.full(3, 2.0))
            return eng, sink

        runs = [build(), build(reference=True),
                build("pallas", device_executor="jit")]
        for eng, _ in runs:
            eng.run()
        # key 0: 1 owned + 3 scattered = 4 matches per probe tuple
        for _, sink in runs:
            assert int(sink.counts[0]) == 40 * 4
            assert int(sink.counts[1]) == 40
        np.testing.assert_array_equal(runs[0][1].counts, runs[1][1].counts)
        np.testing.assert_array_equal(runs[0][1].counts, runs[2][1].counts)

    def test_sorted_output_mid_run_under_active_split(self):
        """Regression: ``sorted_output`` queried mid-run while an SBR
        split parks rows in scattered buffers must include them (it used
        to silently drop every un-merged buffer) — and the device plane
        must materialize first and agree bit-for-bit."""
        def scenario(backend, reference=False, **kw):
            t = _sort_pipeline(backend, controller=False, n=3000,
                               reference=reference, **kw)
            for _ in range(4):
                t[0].run_super_tick(t[0]._fusible_ticks(4))
            t[0].edges[0].routing.split_key(0, [0, 1], [0.5, 0.5])
            for _ in range(4):
                t[0].run_super_tick(t[0]._fusible_ticks(4))
            return t, t[2].sorted_output()

        (a, sa) = scenario("numpy")
        (r, sr) = scenario(None, reference=True)
        (b, sb) = scenario("pallas", device_executor="jit")
        assert any(len(w.scattered) for w in a[2].workers)  # split active
        # completeness: every processed record is visible mid-run
        assert sa.size == sum(w.stats.processed_total for w in a[2].workers)
        np.testing.assert_array_equal(sa, sb)
        np.testing.assert_array_equal(sa, sr)

    def test_install_build_mid_run_keeps_device_backlog(self):
        """Regression (review finding): a mid-run install_build mutates
        host keyed state, so it must materialize the device copy FIRST —
        without the sync, the post-install reload rebuilds rings from a
        stale host snapshot and silently drops device-resident backlog."""
        def scenario(backend, **kw):
            t = _join_pipeline(backend, controller=False, n=3000, **kw)
            for _ in range(3):
                t[0].run_super_tick(t[0]._fusible_ticks(4))
            assert t[2].backlog_total() > 0       # live probe backlog
            # analyst adds late build rows for key 1 mid-run
            t[2].install_build(t[0].edges[1].routing,
                               np.ones(2, np.int64), np.full(2, 5.0))
            t[0].run()
            return t

        a = scenario("numpy")
        b = scenario("pallas", device_executor="jit")
        _assert_runs_identical(a, b)

    def test_install_build_scatterplan_grouping(self):
        """The ScatterPlan-routed install partitions the build table
        exactly as the old per-unique-worker loop: per-worker scope sets
        and row arrays unchanged, including single-worker identity."""
        from repro.core.partitioner import RoutingTable
        for num_workers in (1, 5):
            rt = RoutingTable(NK, num_workers)
            probe = HashJoinProbe("j", num_workers, 8)
            bk, bv = _build_table()
            rng = np.random.default_rng(0)
            perm = rng.permutation(bk.size)
            probe.install_build(rt, bk[perm], bv[perm])
            for w, worker in enumerate(probe.workers):
                want = np.nonzero(rt.owner == w)[0]
                got = np.array(sorted(worker.state.keys()))
                want_present = np.array(
                    [k for k in want if (bk[perm] == k).any()])
                np.testing.assert_array_equal(got, want_present)
                for k in got:
                    np.testing.assert_array_equal(
                        worker.state.scope_array(int(k)),
                        bv[perm][bk[perm] == k])


class TestRowStateCheckpoint:
    @pytest.mark.parametrize("build", [_join_pipeline, _sort_pipeline],
                             ids=["join", "sort"])
    def test_fail_recover_mid_run_replays_bit_identical(self, build):
        """Snapshot mid-run under a controller, progress, fail, restore,
        finish: identical to a never-failed numpy run (rings, row
        state, match tables re-uploaded from the restored host truth)."""
        kw = dict(num_workers=6, controller=True, n=6000)
        b = build("pallas", device_executor="jit", **kw)
        for _ in range(6):
            b[0].run_super_tick(b[0]._fusible_ticks(4))
        snap = ckpt.snapshot(b[0])
        tick_at_snap = b[0].tick
        for _ in range(3):
            b[0].run_super_tick(b[0]._fusible_ticks(4))
        ckpt.restore(b[0], snap)
        assert b[0].tick == tick_at_snap
        b[0].run()
        a = build("numpy", **kw)
        a[0].run()
        _assert_runs_identical(a, b)

    def test_sort_restore_with_exhausted_sources_drains(self):
        """Eager re-upload regression, row-state edition: a restored
        sort backlog with exhausted sources must drain to END."""
        kw = dict(num_workers=6, controller=True, n=6000)
        b = _sort_pipeline("pallas", device_executor="jit", **kw)
        while not all(s.finished for s in b[0].sources):
            b[0].run_super_tick(b[0]._fusible_ticks(4))
        assert b[2].backlog_total() > 0
        snap = ckpt.snapshot(b[0])
        for _ in range(3):
            b[0].run_super_tick(b[0]._fusible_ticks(4))
        ckpt.restore(b[0], snap)
        ticks = b[0].run(max_ticks=20_000)
        assert b[0].done() and ticks < 20_000
        a = _sort_pipeline("numpy", **kw)
        a[0].run()
        _assert_runs_identical(a, b)
        _assert_row_state_identical(a[2], b[2])

    def test_snapshot_cut_rowstate_matches_host_plane(self):
        """A checkpoint cut through device join+sort edges materializes
        the exact queues / row state / counters the host plane holds."""
        a = _sort_pipeline("numpy", num_workers=6, n=5000)
        b = _sort_pipeline("pallas", device_executor="jit",
                           num_workers=6, n=5000)
        for _ in range(5):
            a[0].run_super_tick(a[0]._fusible_ticks(4))
            b[0].run_super_tick(b[0]._fusible_ticks(4))
        sa, sb = ckpt.snapshot(a[0]), ckpt.snapshot(b[0])
        for oa, ob in zip(sa["ops"], sb["ops"]):
            for wa, wb in zip(oa["workers"], ob["workers"]):
                np.testing.assert_array_equal(wa["queue"][0], wb["queue"][0])
                np.testing.assert_allclose(wa["queue"][1], wb["queue"][1])
                assert wa["received"] == wb["received"]
                assert wa["processed"] == wb["processed"]
        _assert_row_state_identical(a[2], b[2])


class TestDeviceRowStateProperty:
    """Satellite: property test — device join/sort plane == reference
    oracle ``Sink.series`` across random streams, skew levels, manual
    rewrites and checkpoint cuts (fixed shapes keep the jit trace cache
    warm across examples)."""

    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 2**16), hot=st.floats(0.0, 0.8),
           split=st.integers(0, 2), cut=st.integers(0, 3))
    def test_device_plane_matches_reference_oracle(self, seed, hot,
                                                   split, cut):
        build = _join_pipeline if seed % 2 else _sort_pipeline
        ei = 1 if seed % 2 else 0

        def scenario(backend, reference=False, ckpt_cut=False, **kw):
            t = build(backend, n=900, num_workers=3, chunk=8,
                      batch_ticks=4, seed=seed, hot=hot,
                      reference=reference, **kw)
            for _ in range(2):
                t[0].run_super_tick(t[0]._fusible_ticks(4))
            if split == 1:
                t[0].edges[ei].routing.split_key(0, [0, 1], [0.5, 0.5])
            elif split == 2:
                t[0].edges[ei].routing.move_key(0, 2)
            if ckpt_cut:
                snap = ckpt.snapshot(t[0])
                for _ in range(cut):
                    t[0].run_super_tick(t[0]._fusible_ticks(4))
                ckpt.restore(t[0], snap)
            t[0].run()
            return t

        r = scenario(None, reference=True)
        b = scenario("pallas", device_executor="jit", ckpt_cut=True)
        assert _series_equal(r[1].series, b[1].series)
        np.testing.assert_array_equal(r[1].counts, b[1].counts)
        for ea, eb in zip(r[0].edges, b[0].edges):
            np.testing.assert_array_equal(ea.sent_per_worker,
                                          eb.sent_per_worker)
