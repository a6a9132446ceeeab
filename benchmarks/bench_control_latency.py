"""§7.5 (Fig. 21): control-message latency degrades load balancing.
Simulated delays {0, 2, 5, 10, 15} ticks; LB ratio of the CA and TX pairs.

A second table, ``control_latency_mitigation``, measures the engine's own
control latency on the batched device plane: detection -> first rebalanced
dispatch, in ticks.  Host-stepped, the controller only sees stats at
super-tick boundaries, so widening ``batch_ticks`` widens the reaction
lag; with ``device_controller=True`` every metric round runs inside the
fused dispatch and the split-ratio rewrite lands on the very next window
while spans stay full width.  The honest comparison keeps k-wide fused
spans on *both* legs (the host leg gets ``metric_period=k``, its natural
boundary cadence — a period-1 host leg would win latency only by cutting
every span to one tick, which the ``host-tick`` tradeoff row documents).
"""
from __future__ import annotations

import numpy as np

from repro.core import ReshapeConfig
from repro.dataflow import build_w1
from repro.dataflow.engine import Engine, Source
from repro.dataflow.metrics import PairLoadSampler
from repro.dataflow.operators import GroupByAgg, Sink

from . import common
from .common import emit

WORKERS = 48
MIT_WORKERS = 8
MIT_TICK_CAP = 100_000


def _grp_pipeline(*, n, batch_ticks, metric_period, num_workers=MIT_WORKERS,
                  num_keys=24, chunk=8, seed=0, hot_frac=0.5, backend=None,
                  **engine_kw):
    """Source -> GroupByAgg (monitored, SCATTERED-eligible) -> Sink.

    W1's monitored HashJoinProbe migrates by REPLICATE, which the
    in-dispatch controller refuses by design, so the mitigation-latency
    pair is measured on the scatter-migrating GroupByAgg workload."""
    rng = np.random.default_rng(seed)
    keys = np.minimum(rng.zipf(1.3, n) - 1, num_keys - 1).astype(np.int64)
    keys[rng.random(n) < hot_frac] = 0
    vals = rng.uniform(0.0, 10.0, n)
    eng = Engine(partition_backend=backend, batch_ticks=batch_ticks,
                 **engine_kw)
    src = eng.add_source(Source("src", keys, vals, num_workers * chunk))
    grp = eng.add_op(GroupByAgg("groupby", num_workers, chunk))
    sink = eng.add_op(Sink("sink", num_keys, snapshot_every=0))
    edge = eng.connect(src, grp, num_keys)
    eng.connect(grp, sink, num_keys)
    eng.attach_controller(grp, ReshapeConfig(metric_period=metric_period))
    return eng, edge, grp, sink


def _detect_oracle(n):
    """Ground-truth detection tick: a tick-exact host run (batch_ticks=1,
    metric_period=1) — the earliest any plane could possibly react."""
    eng, edge, grp, sink = _grp_pipeline(n=n, batch_ticks=1, metric_period=1)
    eng.run(MIT_TICK_CAP)
    detect = next(e.tick for e in eng.controllers[0].controller.events
                  if e.kind == "detect")
    return detect, sink.counts.copy()


def _run_leg(eng, edge, k):
    """Drive full fused windows; return the start tick of the first
    super-tick dispatched under a rewritten routing table (the first
    rebalanced dispatch), total ticks and super-ticks."""
    dev = edge.dst.device
    ctrl = None if dev is None else dev.ctrl
    v0 = edge.routing.version
    first = None
    while not eng.done() and eng.tick < MIT_TICK_CAP:
        if first is None:
            if ctrl is not None and ctrl.active:
                rewritten = ctrl.epoch_host > 0
            else:
                rewritten = edge.routing.version > v0
            if rewritten:
                first = eng.tick
        eng.run_super_tick(eng._fusible_ticks(k))
    return first, eng.tick, eng.super_ticks


def _mitigation_latency_rows():
    n = common.smoke(20_000, 2_500)
    rows = []
    for k in common.smoke((4, 8, 16), (8,)):
        detect, oracle_counts = _detect_oracle(n)
        legs = [
            # the acceptance pair: both keep k-wide fused spans
            ("device", 1, True),
            ("host-boundary", k, False),
            # tradeoff row: the host controller can match per-tick cadence
            # only by cutting every fused span at the metric grid
            ("host-tick", 1, False),
        ]
        for plane, period, armed in legs:
            eng, edge, grp, sink = _grp_pipeline(
                n=n, batch_ticks=k, metric_period=period, backend="pallas",
                device_executor="jit", device_controller=armed)
            if armed:
                dev = edge.dst.device
                assert dev.ctrl is not None and dev.ctrl.active
            first, ticks, super_ticks = _run_leg(eng, edge, k)
            assert np.array_equal(sink.counts, oracle_counts), plane
            rows.append({
                "batch_ticks": k, "plane": plane, "metric_period": period,
                "detect_oracle_tick": detect,
                "first_rebalanced_tick": -1 if first is None else first,
                "latency_ticks": -1 if first is None else first - detect,
                "avg_span": round(ticks / max(super_ticks, 1), 2),
                "ticks": ticks,
            })
    return rows


def run(scale: float = 0.1):
    rows = []
    for delay in (0, 2, 5, 10, 15):
        cfg = ReshapeConfig(control_delay_ticks=delay)
        wf = build_w1(strategy="reshape", scale=scale, num_workers=WORKERS,
                      service_rate=4, cfg=cfg)
        m = wf.meta
        ca = PairLoadSampler(m["ca_worker"], m["az_worker"])
        join = wf.monitored[0]
        eng = wf.engine
        tx_pair = None
        while not eng.done() and eng.tick < 100_000:
            eng.run_tick()
            if tx_pair is None:
                for e in wf.controllers[0].events:
                    if e.kind == "detect" and e.skewed == m["tx_worker"]:
                        tx_pair = PairLoadSampler(m["tx_worker"], e.helpers[0])
            if eng.tick % 5 == 0:
                ca.sample(join.received_totals())
                if tx_pair:
                    tx_pair.sample(join.received_totals())
        rows.append({
            "delay_ticks": delay,
            "lb_ratio_ca": round(ca.average, 3),
            "lb_ratio_tx": round(tx_pair.average, 3) if tx_pair else -1,
            "ticks": eng.tick,
        })
    emit("control_latency", rows, ["delay_ticks", "lb_ratio_ca",
                                   "lb_ratio_tx", "ticks"],
         size=dict(scale=scale, workers=WORKERS))
    mit = _mitigation_latency_rows()
    if mit:
        emit("control_latency_mitigation", mit,
             ["batch_ticks", "plane", "metric_period", "detect_oracle_tick",
              "first_rebalanced_tick", "latency_ticks", "avg_span", "ticks"],
             size=dict(workers=MIT_WORKERS))
    return rows


if __name__ == "__main__":
    run()
