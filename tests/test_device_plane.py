"""Device-resident exchange plane tests (the jit executor, forced off-TPU).

The contract under test: with ``Engine(partition_backend="pallas",
device_executor="jit")`` every eligible edge runs the fused jitted
super-tick step of :mod:`repro.dataflow.device` — chunks, ring queues,
routing constants, split counters and keyed folds device-resident, one
dispatch per edge, boundary-only materialization — and the run is
**bit-identical** to the numpy host plane: ``Sink.series`` (tick grid +
integer counts), ``sent_per_worker``, per-key routing counters, GroupBy
keyed counts, queue contents at checkpoint cuts, and controller event
streams.  Off-TPU the *default* executor is the host twin (same
canonical rule through the fused numpy exchange); that default is pinned
here too.
"""
import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import ReshapeConfig
from repro.dataflow import checkpoint as ckpt
from repro.dataflow.engine import Engine, Source
from repro.dataflow.exchange import DeviceExchange
from repro.dataflow.operators import Filter, GroupByAgg, Project, Sink


def _series_equal(a, b):
    return (len(a) == len(b)
            and all(t1 == t2 and np.array_equal(c1, c2)
                    for (t1, c1), (t2, c2) in zip(a, b)))


def _all_pass(k, v):
    return v >= 0


def _half_pass(k, v):
    return v >= 5.0


def _from_nine_tenths(k, v):
    return v >= 0.9


def _tiny_or_huge(k, v):
    return ((v > 0.0) & (v < 1e-37)) | (v > 1e38)


def _proj(k, v):
    return k, v * 2.0


def _zipf_stream(n, num_keys, seed=0, hot_frac=0.0):
    rng = np.random.default_rng(seed)
    keys = np.minimum(rng.zipf(1.3, n) - 1, num_keys - 1).astype(np.int64)
    if hot_frac:
        keys[rng.random(n) < hot_frac] = 0
    return keys, rng.uniform(0.0, 10.0, n)


def _pipeline(backend=None, *, n=5000, num_keys=24, num_workers=4, chunk=8,
              batch_ticks=4, predicate=_all_pass, project=None,
              controller=False, hot_frac=0.0, seed=0, pool=None,
              **engine_kw):
    keys, vals = _zipf_stream(n, num_keys, seed, hot_frac)
    if pool is not None:        # values drawn from a fixed set instead
        vals = np.random.default_rng(seed + 1).choice(pool, n)
    eng = Engine(partition_backend=backend, batch_ticks=batch_ticks,
                 **engine_kw)
    src = eng.add_source(Source("src", keys, vals, num_workers * chunk))
    filt = eng.add_op(Filter("filter", num_workers, num_workers * chunk,
                             predicate=predicate))
    ops = [filt]
    if project is not None:
        ops.append(eng.add_op(Project("proj", num_workers,
                                      num_workers * chunk, fn=project)))
    grp = eng.add_op(GroupByAgg("groupby", num_workers, chunk))
    ops.append(grp)
    sink = eng.add_op(Sink("sink", num_keys, snapshot_every=batch_ticks))
    prev = src
    for op in ops:
        eng.connect(prev, op, num_keys)
        prev = op
    eng.connect(prev, sink, num_keys)
    ctrl = None
    if controller:
        ctrl = eng.attach_controller(grp, ReshapeConfig(metric_period=4))
    return eng, sink, grp, ctrl


def _assert_runs_identical(a, b, *, sync=True):
    a_eng, a_sink = a[0], a[1]
    b_eng, b_sink = b[0], b[1]
    assert a_eng.tick == b_eng.tick
    assert _series_equal(a_sink.series, b_sink.series)
    np.testing.assert_array_equal(a_sink.counts, b_sink.counts)
    for ea, eb in zip(a_eng.edges, b_eng.edges):
        np.testing.assert_array_equal(ea.sent_per_worker, eb.sent_per_worker)
        if sync:
            eb.routing.sync_counters()
        np.testing.assert_array_equal(ea.routing._count, eb.routing._count)


class TestJitPlaneEquivalence:
    def test_fold_pipeline_bit_identical(self):
        """Filter -> GroupBy -> Sink, skewed stream, batched scheduler:
        series / counts / histograms / counters identical to numpy."""
        a = _pipeline("numpy")
        a[0].run()
        b = _pipeline("pallas", device_executor="jit")
        b[0].run()
        assert all(e.device_plane == "jit" for e in b[0].edges)
        assert all(isinstance(e.exchange, DeviceExchange)
                   for e in b[0].edges)
        _assert_runs_identical(a, b)

    @pytest.mark.parametrize("block_cells", [1 << 21, 7 * 24],
                             ids=["one-block", "padded-blocks"])
    def test_split_counters_without_sort_match_sorted(self, block_cells,
                                                      monkeypatch):
        """The one-hot prefix-sum split counters equal the stable-sort
        ones lane for lane, also over blocks whose last one is padded."""
        import jax

        from repro.dataflow import device as dev
        K, n = 24, 1000
        rng = np.random.default_rng(3)
        keys = rng.integers(0, K, n)
        valid = rng.random(n) < 0.8
        is_split = np.zeros(K, bool)
        is_split[[0, 3, 7, 11]] = True
        count = rng.integers(0, 50, K)
        spec = dev.StepSpec(kind="fold", W=4, K=K, cap=256, B=8,
                            any_split=True, may_scatter=False,
                            track_stats=False, use_kernel=False)
        def run():
            f = jax.jit(lambda s, c, k, v: dev._split_counters(
                spec, {"is_split": s}, c, k, v))
            return [np.asarray(x) for x in f(is_split, count, keys, valid)]

        with jax.enable_x64(True):
            monkeypatch.setattr(dev, "ONEHOT_BLOCK_CELLS", block_cells)
            got = run()
            monkeypatch.setattr(dev, "ONEHOT_MAX_KEYS", 0)
            want = run()                            # the sort path
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    def test_groupby_state_identical(self):
        a = _pipeline("numpy", predicate=_half_pass)
        a[0].run()
        b = _pipeline("pallas", device_executor="jit", predicate=_half_pass)
        b[0].run()
        _assert_runs_identical(a, b)
        b[2]._device_sync()
        for wa, wb in zip(a[2].workers, b[2].workers):
            assert (dict(wa.state.items()).keys()
                    == dict(wb.state.items()).keys())
            for k in wa.state.keys():
                assert wa.state[k][0] == wb.state[k][0]
                assert wa.state[k][1] == pytest.approx(wb.state[k][1])

    @pytest.mark.parametrize("predicate,pool", [
        (_from_nine_tenths, [0.9, np.nextafter(0.9, 1.0),
                             np.nextafter(0.9, 0.0), 0.9 * (1 + 2.0 ** -50),
                             0.9 * (1 - 2.0 ** -50), 0.5]),
        (_tiny_or_huge, [1e-39, 1e-300, np.finfo(np.float64).tiny,
                         -1e-300, 0.0, -0.0, 3.5e38, 1e200, -1e200, 1.0]),
    ], ids=["at-threshold", "beyond-float32"])
    def test_filter_decisions_on_edge_values(self, predicate, pool):
        """The plane carries values as bit patterns, so a predicate sees
        every binary64 bit: values an ulp from its threshold and outside
        float32's exponent range are kept exactly as on the numpy plane.
        Not covered, because no XLA backend gives it: subnormal values,
        which XLA reads as zero, and a TPU, where the predicate computes
        on float64 emulated as a float32 pair (see the device module)."""
        a = _pipeline("numpy", predicate=predicate, pool=pool)
        a[0].run()
        b = _pipeline("pallas", device_executor="jit", predicate=predicate,
                      pool=pool)
        b[0].run()
        assert all(e.device_plane == "jit" for e in b[0].edges)
        assert 0 < int(a[1].counts.sum()) < 5000
        _assert_runs_identical(a, b)

    def test_project_stage_passthrough(self):
        a = _pipeline("numpy", project=_proj)
        a[0].run()
        b = _pipeline("pallas", device_executor="jit", project=_proj)
        b[0].run()
        assert all(e.device_plane == "jit" for e in b[0].edges)
        _assert_runs_identical(a, b)

    @pytest.mark.parametrize("backend,wired", [("cpu", True),
                                               ("tpu", False)])
    def test_project_wired_only_where_float64_is_ieee(self, backend, wired,
                                                      monkeypatch):
        """A Project computes new values, which a TPU (float64 as a
        float32 pair) cannot turn back into carried bit patterns: there
        it stays on the host plane; Filter, which only reads, does not."""
        import jax

        from repro.dataflow import device as dev
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        proj = Project("p", 4, 32, fn=_proj)
        assert dev.wireable(proj, 24) is wired
        assert dev.wireable(Filter("f", 4, 32, predicate=_all_pass), 24)

    def test_controller_rewrites_and_migrations(self):
        """A Reshape controller on the device GroupBy: detections, the
        two-phase rewrites, scattered folds and migrations replay
        identically (event stream + counters + per-key counts)."""
        a = _pipeline("numpy", num_workers=6, controller=True, hot_frac=0.5,
                      seed=1, n=8000)
        a[0].run()
        b = _pipeline("pallas", device_executor="jit", num_workers=6,
                      controller=True, hot_frac=0.5, seed=1, n=8000)
        b[0].run()
        _assert_runs_identical(a, b)
        assert [e.kind for e in a[3].events] == [e.kind for e in b[3].events]
        assert any(e.kind == "phase2" for e in b[3].events)  # rewrites ran
        b[2]._device_sync()
        for wa, wb in zip(a[2].workers, b[2].workers):
            np.testing.assert_array_equal(wa.state.counts, wb.state.counts)
            assert not len(wb.scattered)        # merged at END

    def test_forced_device_controller_leg(self, monkeypatch):
        """REPRO_DEVICE_CONTROLLER=1 arms the monitored GroupBy's
        in-dispatch controller, and on the same window schedule the run
        — event stream included — stays bit-identical to the host-
        stepped numpy plane (the forced off-TPU leg of the tentpole)."""
        kw = dict(num_workers=6, controller=True, hot_frac=0.5, seed=1,
                  n=8000)
        a = _pipeline("numpy", **kw)
        while not a[0].done():
            a[0].run_super_tick(4)
        monkeypatch.setenv("REPRO_DEVICE_CONTROLLER", "1")
        b = _pipeline("pallas", device_executor="jit", **kw)
        assert b[0].device_controller
        dev = b[2].device
        assert dev is not None and dev.ctrl is not None and dev.ctrl.active
        while not b[0].done():
            b[0].run_super_tick(4)
        _assert_runs_identical(a, b)
        ev = lambda c: [(e.tick, e.kind, e.skewed, tuple(e.helpers),
                         tuple(sorted(e.detail.items()))) for e in c.events]
        assert ev(a[3]) == ev(b[3])
        assert any(e.kind == "phase2" for e in b[3].events)

    def test_w1_full_device_plane_matches_numpy(self):
        """W1 under reshape: since the row-state operator set landed,
        *every* edge — filter, the monitored HashJoinProbe, sink — runs
        device-jit, and the run stays bit-identical to numpy through
        detections, phase-1/2 rewrites and migrations."""
        from repro.dataflow import build_w1
        kw = dict(strategy="reshape", scale=0.005, num_workers=6,
                  service_rate=4, batch_ticks=4, snapshot_every=2)
        a = build_w1(**kw)
        a.run()
        b = build_w1(partition_backend="pallas", device_executor="jit", **kw)
        b.run()
        planes = [e.device_plane for e in b.engine.edges]
        assert planes == ["jit", "jit", "jit"]   # join edge included now
        assert a.engine.tick == b.engine.tick
        assert _series_equal(a.sink.series, b.sink.series)
        for ea, eb in zip(a.engine.edges, b.engine.edges):
            np.testing.assert_array_equal(ea.sent_per_worker,
                                          eb.sent_per_worker)

    def test_use_kernel_partition_core(self):
        """device_use_kernel=True routes the partition core through the
        fused Pallas kernels inside the jitted step (interpret off-TPU)."""
        a = _pipeline("numpy", n=600, num_keys=12, batch_ticks=2)
        for e in a[0].edges[:2]:
            e.routing.split_key(0, [0, 1], [0.5, 0.5])
        a[0].run()
        b = _pipeline("pallas", device_executor="jit",
                      device_use_kernel=True, n=600, num_keys=12,
                      batch_ticks=2)
        for e in b[0].edges[:2]:
            e.routing.split_key(0, [0, 1], [0.5, 0.5])
        b[0].run()
        assert all(e.device_plane == "jit" for e in b[0].edges)  # no silent
        _assert_runs_identical(a, b)                             # demotion

    def test_host_twin_is_the_offtpu_default(self):
        import jax
        if jax.default_backend() == "tpu":  # pragma: no cover - TPU CI
            pytest.skip("host twin is the off-TPU default")
        a = _pipeline("numpy")
        a[0].run()
        h = _pipeline("pallas")             # no executor override
        h[0].run()
        assert all(e.device_plane == "host-twin" for e in h[0].edges)
        _assert_runs_identical(a, h)

    def test_mid_run_backend_swap_materializes_counters(self):
        """A host `route_chunk` on a device-owned table pulls the device
        counters and continues the low-discrepancy sequence bit-exactly
        (the backend-swap handshake)."""
        a = _pipeline("numpy")
        b = _pipeline("pallas", device_executor="jit")
        for e in (a[0].edges[1], b[0].edges[1]):
            e.routing.split_key(0, [0, 1], [0.5, 0.5])
        for _ in range(4):
            a[0].run_super_tick(a[0]._fusible_ticks(4))
            b[0].run_super_tick(b[0]._fusible_ticks(4))
        keys = np.zeros(64, dtype=np.int64)
        np.testing.assert_array_equal(b[0].edges[1].routing.route_chunk(keys),
                                      a[0].edges[1].routing.route_chunk(keys))
        np.testing.assert_array_equal(b[0].edges[1].routing._count,
                                      a[0].edges[1].routing._count)
        a[0].run()
        b[0].run()
        _assert_runs_identical(a, b)


def _proj_keep(k, v):
    return k, v + 1.0


def _rekey(k, v):
    return (k + 1) % 24, v


_UNSET = object()


def _chain_pipeline(backend=None, *, n=5000, num_keys=24, num_workers=4,
                    chunk=8, batch_ticks=4, project=_proj_keep,
                    preserves_keys=True, controller=False, hot_frac=0.0,
                    seed=0, snapshot_every=_UNSET, **engine_kw):
    """Filter -> Project -> GroupBy -> Sink over one key space: the
    canonical fusible chain (three routing-equivalent edges)."""
    keys, vals = _zipf_stream(n, num_keys, seed, hot_frac)
    eng = Engine(partition_backend=backend, batch_ticks=batch_ticks,
                 **engine_kw)
    src = eng.add_source(Source("src", keys, vals, num_workers * chunk))
    filt = eng.add_op(Filter("filter", num_workers, num_workers * chunk,
                             predicate=_all_pass))
    proj = eng.add_op(Project("proj", num_workers, num_workers * chunk,
                              fn=project, preserves_keys=preserves_keys))
    grp = eng.add_op(GroupByAgg("groupby", num_workers, chunk))
    sink = eng.add_op(Sink("sink", num_keys,
                           snapshot_every=batch_ticks
                           if snapshot_every is _UNSET else snapshot_every))
    prev = src
    for op in (filt, proj, grp, sink):
        eng.connect(prev, op, num_keys)
        prev = op
    ctrl = None
    if controller:
        ctrl = eng.attach_controller(grp, ReshapeConfig(metric_period=4))
    return eng, sink, grp, ctrl


def _mirrors_equal(a_eng, b_eng):
    for oa, ob in zip(a_eng.ops, b_eng.ops):
        np.testing.assert_array_equal(oa.received_totals(),
                                      ob.received_totals())
        for wa, wb in zip(oa.workers, ob.workers):
            assert wa.stats.processed_total == wb.stats.processed_total
            assert wa.stats.emitted_total == wb.stats.emitted_total


class TestChainFusion:
    """Multi-edge fusion: routing-equivalent consecutive device edges
    share one placement and advance as one fused dispatch; fusion falls
    back per-edge the moment equivalence stops being provable."""

    def test_chain_bit_identical_and_placements_drop(self):
        """Filter -> Project -> GroupBy over one key space: 3 placements
        per super-tick collapse to 1 (head edge only), run bit-identical
        to numpy."""
        a = _chain_pipeline("numpy")
        a[0].run()
        b = _chain_pipeline("pallas", device_executor="jit")
        b[0].run()
        _assert_runs_identical(a, b)
        _mirrors_equal(a[0], b[0])
        head, mid, tail = b[0].edges[0], b[0].edges[1], b[0].edges[2]
        assert head.exchange.placements > 0
        assert mid.exchange.placements == 0      # placement reused
        assert tail.exchange.placements == 0
        # host plane paid one placement per edge per super-chunk
        assert a[0].edges[1].exchange.placements > 0
        assert a[0].edges[2].exchange.placements > 0

    def test_filter_groupby_chain_placements_2_to_1(self):
        """The acceptance shape: Filter -> GroupBy same-key chain pays
        2 placement dispatches per emitting super-tick unfused (one per
        edge) and exactly 1 fused (the head edge; the second edge's
        partition+scatter is eliminated)."""
        fused = _pipeline("pallas", device_executor="jit")
        fused[0].run()
        apart = _pipeline("pallas", device_executor="jit",
                          device_chain=False)
        apart[0].run()
        _assert_runs_identical(fused, apart, sync=True)
        f_head = fused[0].edges[0].exchange.placements
        assert f_head > 0
        assert fused[0].edges[1].exchange.placements == 0   # eliminated
        assert apart[0].edges[0].exchange.placements == f_head
        # unfused, the second edge re-partitions every emitted chunk
        assert apart[0].edges[1].exchange.placements == pytest.approx(
            f_head, rel=0.1)

    def test_unfused_flag_is_bit_identical(self):
        a = _chain_pipeline("pallas", device_executor="jit",
                            device_chain=False)
        a[0].run()
        assert all(e.exchange.placements > 0 for e in a[0].edges[:3])
        b = _chain_pipeline("pallas", device_executor="jit")
        b[0].run()
        _assert_runs_identical(a, b)
        _mirrors_equal(a[0], b[0])

    def test_rekeying_project_never_chains(self):
        """A Project without preserves_keys must not reuse the upstream
        placement (its output keys re-route) — and stays correct."""
        a = _chain_pipeline("numpy", project=_rekey, preserves_keys=False)
        a[0].run()
        b = _chain_pipeline("pallas", device_executor="jit",
                            project=_rekey, preserves_keys=False)
        b[0].run()
        _assert_runs_identical(a, b)
        # proj -> groupby edge re-partitions (proj's output is re-keyed)
        assert b[0].edges[2].exchange.placements > 0

    def test_sink_tail_chain(self):
        """A W=1 Filter -> Sink pair is routing-equivalent too: the sink
        tail folds the pre-placed survivors directly (no rings), with
        received/processed mirrors exact."""
        def build(backend, **kw):
            keys, vals = _zipf_stream(3000, 16, seed=7)
            eng = Engine(partition_backend=backend, batch_ticks=4, **kw)
            src = eng.add_source(Source("s", keys, vals, 32))
            filt = eng.add_op(Filter("f", 1, 32, predicate=_half_pass))
            sink = eng.add_op(Sink("k", 16, snapshot_every=4))
            eng.connect(src, filt, 16)
            eng.connect(filt, sink, 16)
            eng.run()
            return eng, sink

        a = build("numpy")
        b = build("pallas", device_executor="jit")
        _assert_runs_identical(a, b)
        _mirrors_equal(a[0], b[0])
        assert b[0].edges[1].exchange.placements == 0

    def test_controller_rewrite_breaks_chain_mid_run(self):
        """A Reshape mitigation splits/moves keys on the groupby edge:
        its routing token changes (or voids), the chain falls back to
        per-edge placement mid-run, and everything stays bit-identical —
        series, counters, event stream, keyed state."""
        kw = dict(num_workers=6, controller=True, hot_frac=0.5, seed=1,
                  n=8000)
        a = _pipeline("numpy", **kw)
        a[0].run()
        b = _pipeline("pallas", device_executor="jit", **kw)
        b[0].run()
        _assert_runs_identical(a, b)
        assert [e.kind for e in a[3].events] == [e.kind for e in b[3].events]
        assert any(e.kind == "phase2" for e in b[3].events)
        # fusion engaged for part of the run (fewer placements than the
        # per-edge host plane), then broke: the groupby edge still paid
        # placements while its table was split
        grp_edge = b[0].edges[1]
        assert 0 < grp_edge.exchange.placements \
            < a[0].edges[1].exchange.placements

    def test_mid_chain_demotion_preserves_mirrors(self):
        """Satellite: demoting the *middle* operator of a fused chain
        (untraceable Project fn on the first dispatch) must fall back
        per-edge with received/processed/emitted mirrors exact and no
        double-counted staged records."""
        def impure(k, v):
            return k, np.asarray(v) * 2.0      # concretizes a tracer

        a = _chain_pipeline("numpy", project=impure)
        a[0].run()
        with pytest.warns(RuntimeWarning):
            b = _chain_pipeline("pallas", device_executor="jit",
                                project=impure)
            b[0].run()
        assert b[0].ops[1].device is None                  # proj demoted
        assert b[0].edges[1].device_plane.startswith("demoted")
        _assert_runs_identical(a, b)
        _mirrors_equal(a[0], b[0])

    def test_lockstep_rewrite_with_head_backlog(self):
        """Regression (review finding): rewriting BOTH chain tables in
        lockstep keeps their tokens equal, but backlog queued in the
        head's rings was *placed* under the old table — a pre-placed
        push would deliver it to the old primary's downstream worker.
        The placement-epoch guard must fall back per-edge until the
        old-placed backlog drains, staying bit-identical to numpy."""
        def scenario(backend, **kw):
            keys, vals = _zipf_stream(8000, 16, seed=11)
            eng = Engine(partition_backend=backend, batch_ticks=4, **kw)
            src = eng.add_source(Source("src", keys, vals, 128))
            filt = eng.add_op(Filter("filter", 4, 8,      # slow: backlog
                                     predicate=_all_pass))
            grp = eng.add_op(GroupByAgg("groupby", 4, 8))
            sink = eng.add_op(Sink("sink", 16, snapshot_every=4))
            eng.connect(src, filt, 16)
            eng.connect(filt, grp, 16)
            eng.connect(grp, sink, 16)
            for _ in range(4):
                eng.run_super_tick(eng._fusible_ticks(4))
            assert filt.backlog_total() > 0
            for e in eng.edges[:2]:
                e.routing.move_key(0, 2)     # lockstep: tokens stay equal
            eng.run()
            return eng, sink, grp

        a = scenario("numpy")
        b = scenario("pallas", device_executor="jit")
        np.testing.assert_array_equal(a[2].received_totals(),
                                      b[2].received_totals())
        _assert_runs_identical(a, b)
        _mirrors_equal(a[0], b[0])
        # fusion paused (per-edge placements paid) while the old-placed
        # backlog drained, instead of staying fused and mis-delivering
        assert b[0].edges[1].exchange.placements > 0

    def test_use_kernel_sink_stays_per_edge(self):
        """Review finding: a use_kernel sink tail must not be chained —
        the per-edge sink folds through the Pallas kernel and the chain
        tail would silently swap in a different accumulation."""
        def build(**kw):
            keys, vals = _zipf_stream(1000, 16, seed=7)
            eng = Engine(partition_backend="pallas",
                         device_executor="jit", **kw)
            src = eng.add_source(Source("s", keys, vals, 32))
            filt = eng.add_op(Filter("f", 1, 32, predicate=_all_pass))
            sink = eng.add_op(Sink("k", 16, snapshot_every=4))
            eng.connect(src, filt, 16)
            eng.connect(filt, sink, 16)
            eng.run()
            return eng, sink

        a_eng, a_sink = build(device_use_kernel=True)
        b_eng, b_sink = build(device_use_kernel=False)
        np.testing.assert_array_equal(a_sink.counts, b_sink.counts)
        # kernel sink dispatches per-edge: the fused chain never forms
        assert a_eng.edges[0].exchange.placements > 0
        assert b_eng.edges[1].exchange.placements == 0   # chained (no kernel)

    def test_staleness_flip_mid_super_tick(self):
        """Regression (derived-state staleness window): a chunk staged on
        a device edge then a table rewrite before its dispatch — the
        chunk must route under the *stage-time* table, as the host plane
        did at send time, never with mixed old/new tables."""
        def scenario(backend, **kw):
            eng, sink, grp, _ = _pipeline(backend, seed=2, **kw)
            for _ in range(4):
                eng.run_super_tick(eng._fusible_ticks(4))
            e = eng.edges[1]
            e.send((np.zeros(40, dtype=np.int64), np.ones(40)))
            e.routing.split_key(0, [0, 1], [0.5, 0.5])   # flip mid-window
            eng.run()
            return eng, sink, grp

        a = scenario("numpy")
        b = scenario("pallas", device_executor="jit")
        np.testing.assert_array_equal(a[2].received_totals(),
                                      b[2].received_totals())
        _assert_runs_identical(a, b)
        _mirrors_equal(a[0], b[0])


class TestDegenerateSnapshotConfigs:
    """Satellite: ``Sink(snapshot_every=0 | None)`` means "periodic
    snapshots off" — previously ``int(None)`` blew up the batched
    scheduler's boundary math and the modulo blew up ``Sink.snapshot``
    on every plane."""

    @pytest.mark.parametrize("every", [0, None])
    def test_device_plane_runs_and_matches_numpy(self, every):
        a = _chain_pipeline("numpy", snapshot_every=every)
        a[0].run()
        b = _chain_pipeline("pallas", device_executor="jit",
                            snapshot_every=every)
        b[0].run()
        assert len(a[1].series) == 1      # only the END snapshot
        _assert_runs_identical(a, b)


class TestJitPlaneDemotion:
    def test_two_dim_vals_demote_to_host_path(self):
        eng = Engine(partition_backend="pallas", device_executor="jit")
        src = eng.add_source(Source("s", np.arange(50) % 8,
                                    np.ones((50, 2)), 10))
        filt = eng.add_op(Filter("f", 2, 10,
                                 predicate=lambda k, v: np.ones(
                                     k.shape[0], bool)))
        sink = eng.add_op(Sink("k", 8))
        eng.connect(src, filt, 8)
        eng.connect(filt, sink, 8)
        eng.run()
        assert all((e.device_plane or "").startswith("demoted")
                   for e in eng.edges)
        assert int(sink.counts.sum()) == 50

    def test_untraceable_predicate_demotes_and_replays(self):
        def impure(k, v):
            return np.asarray(v) >= 0       # concretizes a tracer

        eng = Engine(partition_backend="pallas", device_executor="jit")
        src = eng.add_source(Source("s", np.arange(50) % 8, np.ones(50), 10))
        filt = eng.add_op(Filter("f", 2, 10, predicate=impure))
        sink = eng.add_op(Sink("k", 8))
        eng.connect(src, filt, 8)
        eng.connect(filt, sink, 8)
        eng.run()
        assert eng.edges[0].device_plane.startswith("demoted")
        assert int(sink.counts.sum()) == 50
        np.testing.assert_array_equal(sink.counts,
                                      np.bincount(np.arange(50) % 8,
                                                  minlength=8))

    @pytest.mark.parametrize("fused", [False, True],
                             ids=["per-edge", "chain"])
    def test_non_tracer_first_dispatch_error_propagates(self, fused,
                                                        monkeypatch):
        """Only tracer errors from a user fn demote: a compiler refusal
        (here a Mosaic-style ValueError) at the first dispatch, per-edge
        or fused, must surface instead of becoming a host-path run."""
        from repro.dataflow import device as dev
        target = "chain" if fused else "filter"
        real = dev._step_for

        def refusing(*args):
            raise ValueError("Cannot do int indexing on TPU")

        monkeypatch.setattr(
            dev, "_step_for",
            lambda kind: refusing if kind == target else real(kind))
        eng = _pipeline("pallas", device_executor="jit", device_chain=fused)
        eng = eng[0]
        with pytest.raises(ValueError, match="int indexing"):
            eng.run()
        assert not eng.incidents.query("demotion")
        assert not eng.incidents.query("chain-fallback")
        assert all(e.device_plane == "jit" for e in eng.edges)

    def test_second_upstream_demotes(self):
        eng = Engine(partition_backend="pallas", device_executor="jit")
        s1 = eng.add_source(Source("s1", np.arange(30) % 8, np.ones(30), 10))
        s2 = eng.add_source(Source("s2", np.arange(30) % 8, np.ones(30), 10))
        sink = eng.add_op(Sink("k", 8))
        e1 = eng.connect(s1, sink, 8)
        assert sink.device is not None
        e2 = eng.connect(s2, sink, 8)
        assert sink.device is None          # two upstreams: host fallback
        eng.run()
        assert int(sink.counts.sum()) == 60


class TestDeviceCheckpoint:
    """Satellite: checkpoint snapshot/restore under the pallas backend
    with batch_ticks > 1 — a restore mid-run replays from the last
    boundary with counters, queues and results bit-identical to numpy."""

    def _build(self, backend, **kw):
        return _pipeline(backend, num_workers=6, controller=True,
                         hot_frac=0.4, seed=3, n=6000, **kw)

    def test_restore_mid_super_tick_replays_from_boundary(self):
        b = self._build("pallas", device_executor="jit")
        for _ in range(6):
            b[0].run_super_tick(b[0]._fusible_ticks(4))
        snap = ckpt.snapshot(b[0])
        tick_at_snap = b[0].tick
        counters_at_snap = [e["routing"]["count"].copy()
                            for e in snap["edges"]]
        for _ in range(3):                  # progress past the cut...
            b[0].run_super_tick(b[0]._fusible_ticks(4))
        ckpt.restore(b[0], snap)            # ...fail + recover
        assert b[0].tick == tick_at_snap
        for e, want in zip(b[0].edges, counters_at_snap):
            e.routing.sync_counters()
            np.testing.assert_array_equal(e.routing._count, want)
        b[0].run()

        a = self._build("numpy")            # never-failed oracle
        a[0].run()
        _assert_runs_identical(a, b)

    def test_restore_with_exhausted_sources_still_drains(self):
        """Regression: a restore whose snapshot holds backlog but whose
        sources are already exhausted must eagerly re-upload the restored
        rings — no new arrival will ever come to trigger a lazy reload,
        and END propagation would stall forever."""
        b = self._build("pallas", device_executor="jit")
        while not all(s.finished for s in b[0].sources):
            b[0].run_super_tick(b[0]._fusible_ticks(4))
        assert b[2].backlog_total() > 0     # skewed backlog remains
        snap = ckpt.snapshot(b[0])
        for _ in range(3):
            b[0].run_super_tick(b[0]._fusible_ticks(4))
        ckpt.restore(b[0], snap)
        ticks = b[0].run(max_ticks=20_000)
        assert b[0].done() and ticks < 20_000
        a = self._build("numpy")
        a[0].run()
        _assert_runs_identical(a, b)

    def test_streaming_sink_received_mirror_exact(self):
        """Regression: chunks staged into a device sink before its first
        allocation must survive in the received mirror (the scratch host
        queue's zero count must never clobber stage-time accounting)."""
        def build(backend, **kw):
            keys, vals = _zipf_stream(2000, 16, seed=7)
            eng = Engine(partition_backend=backend, batch_ticks=4, **kw)
            src = eng.add_source(Source("s", keys, vals, 32))
            filt = eng.add_op(Filter("f", 4, 32, predicate=_all_pass))
            sink = eng.add_op(Sink("k", 16, snapshot_every=4))
            eng.connect(src, filt, 16)
            eng.connect(filt, sink, 16)    # sink streams every super-tick
            return eng, sink
        a_eng, a_sink = build("numpy")
        b_eng, b_sink = build("pallas", device_executor="jit")
        for _ in range(3):
            a_eng.run_super_tick(a_eng._fusible_ticks(4))
            b_eng.run_super_tick(b_eng._fusible_ticks(4))
        np.testing.assert_array_equal(a_sink.received_totals(),
                                      b_sink.received_totals())
        sa, sb = ckpt.snapshot(a_eng), ckpt.snapshot(b_eng)
        for oa, ob in zip(sa["ops"], sb["ops"]):
            for wa, wb in zip(oa["workers"], ob["workers"]):
                assert wa["received"] == wb["received"]
        a_eng.run()
        b_eng.run()
        np.testing.assert_array_equal(a_sink.counts, b_sink.counts)

    def test_sink_demote_with_staged_chunks_keeps_accounting(self):
        """Regression: demotion with staged-but-undispatched sink chunks
        must back the stage accounting out *before* materializing the
        mirror, then replay — received_total and tuples_sent stay true."""
        eng = Engine(partition_backend="pallas", device_executor="jit")
        src = eng.add_source(Source("s", np.arange(10, dtype=np.int64) % 8,
                                    np.ones(10), 10))
        sink = eng.add_op(Sink("k", 8))
        edge = eng.connect(src, sink, 8)
        eng.run_super_tick(1)               # 10 tuples through the sink
        edge.send((np.arange(6, dtype=np.int64) % 8, np.ones(6)))  # staged
        # A second upstream wired mid-run demotes the sink while the 6
        # tuples are still staged-but-undispatched.
        s2 = eng.add_source(Source("s2", np.arange(4, dtype=np.int64) % 8,
                                   np.ones(4), 10))
        eng.connect(s2, sink, 8)
        assert sink.device is None          # demoted
        assert edge.tuples_sent == 16
        assert sink.workers[0].queue.received_total == 16
        assert len(sink.workers[0].queue) == 6       # staged -> replayed
        sink.tick()
        assert int(sink.counts.sum()) == 16

    def test_end_flush_staged_chunks_visible_at_boundary(self):
        """Regression: a blocking upstream's END flush stages a chunk
        into a device operator *after* its tick in the same super-tick;
        a checkpoint cut in that window must still capture the records
        (the host plane already holds them in the worker queues)."""
        def build(backend, **kw):
            keys, vals = _zipf_stream(800, 16, seed=9)
            eng = Engine(partition_backend=backend, batch_ticks=4, **kw)
            src = eng.add_source(Source("s", keys, vals, 64))
            grp = eng.add_op(GroupByAgg("g", 4, 16))
            filt = eng.add_op(Filter("f", 4, 4, predicate=_all_pass))
            sink = eng.add_op(Sink("k", 16, snapshot_every=4))
            eng.connect(src, grp, 16)
            eng.connect(grp, filt, 16)     # END flush lands here
            eng.connect(filt, sink, 16)
            return eng, sink, grp, None
        a = build("numpy")
        b = build("pallas", device_executor="jit")
        for eng in (a[0], b[0]):
            while not eng.ops[0].finished:     # run through groupby END
                eng.run_super_tick(eng._fusible_ticks(4))
        assert b[0].ops[1].backlog_total() > 0  # END flush is in flight
        sa, sb = ckpt.snapshot(a[0]), ckpt.snapshot(b[0])
        for oa, ob in zip(sa["ops"], sb["ops"]):
            for wa, wb in zip(oa["workers"], ob["workers"]):
                np.testing.assert_array_equal(wa["queue"][0], wb["queue"][0])
                assert wa["received"] == wb["received"]
        a[0].run()
        b[0].run()
        _assert_runs_identical(a, b)

    def test_chain_restore_with_exhausted_sources_replays_bit_identical(self):
        """Satellite: fail/recover of a *fused chain* at a super-tick
        boundary with sources already exhausted — the restored chain
        must re-upload eagerly (END would stall otherwise) and replay
        bit-identical to the unfused numpy plane."""
        kw = dict(num_workers=6, controller=True, hot_frac=0.4, seed=3,
                  n=6000)
        b = _chain_pipeline("pallas", device_executor="jit", **kw)
        while not all(s.finished for s in b[0].sources):
            b[0].run_super_tick(b[0]._fusible_ticks(4))
        assert b[2].backlog_total() > 0      # skewed backlog remains
        snap = ckpt.snapshot(b[0])
        for _ in range(3):
            b[0].run_super_tick(b[0]._fusible_ticks(4))
        ckpt.restore(b[0], snap)
        ticks = b[0].run(max_ticks=20_000)
        assert b[0].done() and ticks < 20_000
        a = _chain_pipeline("numpy", **kw)
        a[0].run()
        _assert_runs_identical(a, b)
        _mirrors_equal(a[0], b[0])

    def test_chain_snapshot_cut_matches_host_plane(self):
        """A checkpoint cut through a fused chain materializes the exact
        queue contents / totals the host plane holds at the same tick."""
        a = _chain_pipeline("numpy", num_workers=6, seed=3, n=6000)
        b = _chain_pipeline("pallas", device_executor="jit",
                            num_workers=6, seed=3, n=6000)
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

    def test_snapshot_queue_contents_match_host_plane(self):
        """The checkpoint cut itself is bit-identical: device rings
        materialize into the exact queue contents the host plane holds."""
        a = self._build("numpy")
        b = self._build("pallas", device_executor="jit")
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
        for ea, eb in zip(sa["edges"], sb["edges"]):
            np.testing.assert_array_equal(ea["routing"]["count"],
                                          eb["routing"]["count"])
            np.testing.assert_array_equal(ea["sent_per_worker"],
                                          eb["sent_per_worker"])
