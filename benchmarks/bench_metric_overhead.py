"""§7.9 (Fig. 25): metric-collection overhead. Mitigation disabled; the
overhead model is messages x per-message cost vs total data-plane work
(the paper measures 1-2% wall time; our engine counts control traffic).

Three planes are surfaced.  ``host``: the pure host controller, one O(W)
stats collection per metric round.  ``device-host-ctrl``: the host
controller over the jit device plane — each round still costs O(W), and
every super-tick boundary additionally drains device stats (one O(W)
readback, now honestly counted in ``metric_messages``).
``device-armed``: ``device_controller=True`` runs the rounds inside the
fused dispatch, so only the boundary drain readbacks remain as host
traffic."""
from __future__ import annotations

from repro.core import ReshapeConfig
from repro.dataflow import build_w1
from repro.dataflow.engine import Engine, Source
from repro.dataflow.operators import GroupByAgg, Sink

from . import common
from .common import emit

# Calibration: the paper collects metrics ~1/sec while a worker processes
# ~60k tuples/sec; our tick = 4 tuples/worker, so the equivalent cadence is
# one collection every ~25 ticks, and one message costs ~0.1 tuple-equiv
# (a metric message is ~100B vs a tuple's full operator work).
MSG_COST_TUPLES = 0.1
METRIC_PERIOD = 25


def _row(plane, scale, workers, ctrl, op):
    msgs = ctrl.metric_messages()
    total_tuples = sum(w.stats.processed_total for w in op.workers)
    overhead = msgs * MSG_COST_TUPLES / max(total_tuples, 1)
    return {
        "plane": plane, "scale": scale, "workers": workers,
        "metric_messages": msgs,
        "tuples_processed": total_tuples,
        "modeled_overhead_pct": round(100 * overhead, 2),
        "mitigations": ctrl.iterations_total,
    }


def _device_plane_rows(scale, workers, batch_ticks=8):
    """Same collection cadence on the jit device plane, host-stepped vs
    armed — the armed controller turns W-per-round host traffic into
    boundary-only drain readbacks.  GroupByAgg is the monitored op: the
    in-dispatch controller refuses W1's REPLICATE-migrating probe."""
    import numpy as np
    n = int(200_000 * scale)
    num_keys = 64
    rng = np.random.default_rng(0)
    keys = np.minimum(rng.zipf(1.3, n) - 1, num_keys - 1).astype(np.int64)
    vals = rng.uniform(0.0, 10.0, n)
    rows = []
    for plane, armed in (("device-host-ctrl", False), ("device-armed", True)):
        eng = Engine(partition_backend="pallas", device_executor="jit",
                     batch_ticks=batch_ticks, device_controller=armed)
        src = eng.add_source(Source("src", keys, vals, workers * 4))
        grp = eng.add_op(GroupByAgg("groupby", workers, 4))
        sink = eng.add_op(Sink("sink", num_keys, snapshot_every=0))
        eng.connect(src, grp, num_keys)
        eng.connect(grp, sink, num_keys)
        cfg = ReshapeConfig(eta=float("inf"), adaptive_tau=False,
                            metric_period=METRIC_PERIOD)
        ctrl = eng.attach_controller(grp, cfg)
        eng.run()
        rows.append(_row(plane, scale, workers, ctrl, grp))
    return rows


def run():
    rows = []
    for scale, workers in common.smoke(
            ((0.1, 40), (0.15, 48), (0.2, 56)), ((0.02, 8),)):
        # eta=inf disables mitigation: measure pure collection traffic
        cfg = ReshapeConfig(eta=float("inf"), adaptive_tau=False,
                            metric_period=METRIC_PERIOD)
        wf = build_w1(strategy="reshape", scale=scale, num_workers=workers,
                      service_rate=4, cfg=cfg)
        wf.run()
        rows.append(_row("host", scale, workers, wf.controllers[0],
                         wf.monitored[0]))
        rows += _device_plane_rows(scale, workers)
    emit("metric_overhead", rows, ["plane", "scale", "workers",
                                   "metric_messages", "tuples_processed",
                                   "modeled_overhead_pct", "mitigations"])
    return rows


if __name__ == "__main__":
    run()
