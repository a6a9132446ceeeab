"""Device-resident skew controller tests (the in-dispatch control plane).

The contract under test: with ``Engine(device_controller=True)`` (or
``REPRO_DEVICE_CONTROLLER=1``) an eligible attached controller — SBR +
SCATTERED, single helper, zero control delay — runs every metric round
*inside* the fused jitted dispatch: detection, adaptive tau, and the
phase-1/phase-2 split-ratio rewrites all happen on device, and the host
``ReshapeController`` is reconciled at boundaries by replaying the
device-logged observation windows.  Every decision must be
**bit-identical** to the host-stepped controller given the same
super-tick schedule: event stream (detection tick, chosen helpers,
split ratios, tau adjustments), tau trajectory, mitigation states, sink
series and routing counters.
"""
import numpy as np
import pytest

pytest.importorskip("jax")

from _propcheck import given, settings, st
from repro.core import ReshapeConfig
from repro.core.types import MitigationPhase
from repro.dataflow import checkpoint as ckpt
from repro.dataflow.engine import Engine, Source
from repro.dataflow.operators import GroupByAgg, Sink


def _skewed_stream(n, num_keys, seed=0, hot_frac=0.4):
    rng = np.random.default_rng(seed)
    keys = np.minimum(rng.zipf(1.3, n) - 1, num_keys - 1).astype(np.int64)
    if hot_frac:
        keys[rng.random(n) < hot_frac] = 0
    return keys, rng.uniform(0.0, 10.0, n)


def _monitored(backend=None, *, n=3000, num_keys=24, num_workers=4, chunk=8,
               batch_ticks=4, hot_frac=0.4, seed=0, metric_period=1,
               cfg=None, snapshot_every=1, **engine_kw):
    """Source -> GroupByAgg (monitored, SCATTERED-eligible) -> Sink."""
    keys, vals = _skewed_stream(n, num_keys, seed, hot_frac)
    eng = Engine(partition_backend=backend, batch_ticks=batch_ticks,
                 **engine_kw)
    src = eng.add_source(Source("src", keys, vals, num_workers * chunk))
    grp = eng.add_op(GroupByAgg("groupby", num_workers, chunk))
    sink = eng.add_op(Sink("sink", num_keys, snapshot_every=snapshot_every))
    eng.connect(src, grp, num_keys)
    eng.connect(grp, sink, num_keys)
    ctrl = eng.attach_controller(
        grp, cfg or ReshapeConfig(metric_period=metric_period))
    return eng, sink, grp, ctrl


def _drive(eng, k, max_ticks=50_000):
    """Fixed-width window schedule (identical across compared runs)."""
    while not eng.done() and eng.tick < max_ticks:
        eng.run_super_tick(k)
    return eng.tick


def _events(ctrl):
    return [(e.tick, e.kind, e.skewed, tuple(e.helpers),
             tuple(sorted(e.detail.items()))) for e in ctrl.events]


def _decisions(ctrl):
    return dict(
        events=_events(ctrl), tau=ctrl.tau,
        tau_adjustments=ctrl.tau_adjustments,
        iterations=ctrl.iterations_total,
        mitigations={s: (m.phase, tuple(m.helpers), m.calm_rounds,
                         m.iteration)
                     for s, m in ctrl.mitigations.items()})


def _series_equal(a, b):
    return (len(a) == len(b)
            and all(t1 == t2 and np.array_equal(c1, c2)
                    for (t1, c1), (t2, c2) in zip(a, b)))


def _assert_same_decisions(a_ctrl, b_ctrl):
    assert _decisions(a_ctrl) == _decisions(b_ctrl)


class TestBitIdentity:
    @settings(max_examples=12, deadline=None)
    @given(st.integers(min_value=0, max_value=10_000),
           st.floats(min_value=0.0, max_value=0.7),
           st.integers(min_value=0, max_value=1))
    def test_decisions_match_host_controller(self, seed, hot_frac, k_ix):
        """Property: across random streams, skew levels and window widths
        the in-dispatch controller's decisions — detection ticks, chosen
        helpers, split ratios (phase-2 ``moved_share``), tau adjustments
        — are bit-identical to the host ``ReshapeController``, and so is
        the data plane (series, counts, routing counters)."""
        k = (4, 8)[k_ix]
        kw = dict(n=2500, num_workers=4, hot_frac=hot_frac, seed=seed,
                  batch_ticks=k)
        a = _monitored("pallas", device_executor="jit",
                       device_controller=False, **kw)
        _drive(a[0], k)
        b = _monitored("pallas", device_executor="jit",
                       device_controller=True, **kw)
        dev = b[0].controllers[0].op.device
        assert dev is not None and dev.ctrl is not None and dev.ctrl.active
        _drive(b[0], k)
        _assert_same_decisions(a[3], b[3])
        assert a[0].tick == b[0].tick
        assert _series_equal(a[1].series, b[1].series)
        np.testing.assert_array_equal(a[1].counts, b[1].counts)
        for ea, eb in zip(a[0].edges, b[0].edges):
            np.testing.assert_array_equal(ea.sent_per_worker,
                                          eb.sent_per_worker)
            eb.routing.sync_counters()
            np.testing.assert_array_equal(ea.routing._count,
                                          eb.routing._count)
            np.testing.assert_array_equal(ea.routing.weights,
                                          eb.routing.weights)

    @settings(max_examples=6, deadline=None)
    @given(st.integers(min_value=1, max_value=12),
           st.integers(min_value=0, max_value=10_000))
    def test_checkpoint_cut_preserves_decisions(self, cut_windows, seed):
        """Property: an armed run cut by snapshot/restore at a random
        super-tick continues bit-identically to an uninterrupted armed
        run (the device controller drains at the cut and re-forms from
        the restored host twin)."""
        k = 4
        kw = dict(n=2000, num_workers=4, seed=seed, batch_ticks=k,
                  device_executor="jit", device_controller=True)
        a = _monitored("pallas", **kw)
        for _ in range(cut_windows):
            if a[0].done():
                break
            a[0].run_super_tick(k)
        snap = ckpt.snapshot(a[0])
        _drive(a[0], k)
        b = _monitored("pallas", **kw)
        for _ in range(cut_windows):
            if b[0].done():
                break
            b[0].run_super_tick(k)
        ckpt.restore(b[0], snap)
        _drive(b[0], k)
        _assert_same_decisions(a[3], b[3])
        np.testing.assert_array_equal(a[1].counts, b[1].counts)
        assert _series_equal(a[1].series, b[1].series)


class TestLifecycle:
    def test_restore_mid_mitigation_reforms(self):
        """Regression: a checkpoint restore while mitigations are live in
        PHASE_ONE/PHASE_TWO re-forms the device controller from the
        restored host state (stays armed) and continues bit-identically."""
        k = 4
        kw = dict(n=4000, num_workers=6, hot_frac=0.6, seed=1,
                  batch_ticks=k, device_executor="jit",
                  device_controller=True)
        a = _monitored("pallas", **kw)
        for _ in range(8):
            a[0].run_super_tick(k)
        snap = ckpt.snapshot(a[0])
        assert a[3].mitigations, "cut must land mid-mitigation"
        assert all(m.phase in (MitigationPhase.PHASE_ONE,
                               MitigationPhase.PHASE_TWO)
                   for m in a[3].mitigations.values())
        _drive(a[0], k)
        b = _monitored("pallas", **kw)
        for _ in range(8):
            b[0].run_super_tick(k)
        ckpt.restore(b[0], snap)
        dev = b[0].controllers[0].op.device
        assert dev.ctrl is not None and dev.ctrl.active   # re-formed
        _drive(b[0], k)
        _assert_same_decisions(a[3], b[3])
        np.testing.assert_array_equal(a[1].counts, b[1].counts)

    def test_restore_demotes_on_unsupported_state(self):
        """Regression: when the restored host twin carries mitigation
        state the device controller cannot represent (e.g. a MIGRATING
        phase), ``on_restore`` demotes cleanly instead of re-arming."""
        from repro.core.controller import _Mitigation
        from repro.core.types import TransferMode
        b = _monitored("pallas", device_executor="jit",
                       device_controller=True, num_workers=4)
        dev = b[0].controllers[0].op.device
        assert dev.ctrl is not None and dev.ctrl.active
        b[3].mitigations[1] = _Mitigation(
            skewed=1, helpers=[2], mode=TransferMode.SBR,
            phase=MitigationPhase.MIGRATING)
        dev.ctrl.on_restore()
        assert not dev.ctrl.active
        assert dev.ctrl.reason == "non-reformable mitigation"
        del b[3].mitigations[1]
        _drive(b[0], 4)                  # host stepping finishes the run
        a = _monitored("pallas", device_executor="jit",
                       device_controller=False, num_workers=4)
        _drive(a[0], 4)
        np.testing.assert_array_equal(a[1].counts, b[1].counts)

    def test_ineligible_configs_refuse(self):
        """Multi-helper / delayed-control / pinned configs stay host-
        stepped (memoized refusal), and the run still completes."""
        for cfg, why in [
            (ReshapeConfig(max_helpers=2), "multi-helper"),
            (ReshapeConfig(control_delay_ticks=2), "control delay"),
            (ReshapeConfig(pinned_helpers={0: 1}), "pinned helpers"),
        ]:
            b = _monitored("pallas", device_executor="jit",
                           device_controller=True, cfg=cfg, n=600)
            dev = b[0].controllers[0].op.device
            assert dev.ctrl is None
            assert dev._ctrl_refused == why
            _drive(b[0], 4)

    def test_step_runs_on_the_cpu_backend_or_refuses(self, monkeypatch):
        """The controller state lives on the host's CPU backend (a TPU's
        float64 would break the bit-exact twin); without that backend the
        controller stays host-stepped instead of arming."""
        from repro.dataflow import device as devmod
        b = _monitored("pallas", device_executor="jit",
                       device_controller=True, n=600)
        ctrl = b[0].controllers[0].op.device.ctrl
        assert ctrl.active
        assert {d.platform for d in ctrl.cstate["weights"].devices()} == {
            "cpu"}
        monkeypatch.setattr(devmod, "_ctrl_device", lambda: None)
        b = _monitored("pallas", device_executor="jit",
                       device_controller=True, n=600)
        dev = b[0].controllers[0].op.device
        assert dev.ctrl is None
        assert dev._ctrl_refused == "no CPU backend for the controller step"
        _drive(b[0], 4)

    def test_env_var_arms_controller(self, monkeypatch):
        monkeypatch.setenv("REPRO_DEVICE_CONTROLLER", "1")
        b = _monitored("pallas", device_executor="jit", n=600)
        assert b[0].device_controller
        dev = b[0].controllers[0].op.device
        assert dev.ctrl is not None and dev.ctrl.active

    def test_metric_rounds_no_longer_cut_fused_spans(self):
        """The tentpole scheduling claim: with the controller armed,
        ``_fusible_ticks`` ignores the metric grid (spans run to the
        horizon); host-stepped, every metric round is a boundary."""
        host = _monitored("pallas", device_executor="jit",
                          device_controller=False, metric_period=1,
                          batch_ticks=16, n=2000, snapshot_every=0)
        armed = _monitored("pallas", device_executor="jit",
                           device_controller=True, metric_period=1,
                           batch_ticks=16, n=2000, snapshot_every=0)
        host[0].run_super_tick(host[0]._fusible_ticks(16))   # past delay
        assert host[0]._fusible_ticks(16) == 1       # cut at every round
        armed[0].run_super_tick(armed[0]._fusible_ticks(16))
        assert armed[0]._fusible_ticks(16) == 16     # full horizon
        armed[0].run()
        host[0].run()
        assert armed[0].super_ticks < host[0].super_ticks
        np.testing.assert_array_equal(host[1].counts, armed[1].counts)

    def test_metric_messages_accounting(self):
        """Armed: in-dispatch rounds cost no host traffic; only boundary
        drains count (O(W) readbacks).  Host-stepped device plane: each
        super-tick boundary drain is accounted on top of the rounds."""
        host = _monitored("pallas", device_executor="jit",
                          device_controller=False, metric_period=1,
                          batch_ticks=8, n=2000)
        _drive(host[0], 8)
        armed = _monitored("pallas", device_executor="jit",
                           device_controller=True, metric_period=1,
                           batch_ticks=8, n=2000)
        _drive(armed[0], 8)
        assert armed[3].rounds_on_device > 0
        assert armed[3].sync_readbacks >= 1          # END/merge drain
        assert host[3].sync_readbacks > 0            # per-boundary drain
        assert armed[3].metric_messages() < host[3].metric_messages()
