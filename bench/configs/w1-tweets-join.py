"""W1 of the paper (§7.1-7.2): tweets joined with slang per location.

The graph, its data and its plain reference.  The join has one build row
per location, so every tweet yields exactly one result: the reference's
final per-location counts are the stream's key counts and its sums are
the correctly rounded sums of the tweet values.  The visible series is
held to what any pipelined execution of the stream may show (see
``checks.prefix_series_violations``).
"""
from __future__ import annotations

import numpy as np

from bench import checks, data


def make_data(cfg: dict, traffic: dict, seed: int) -> dict:
    keys, vals = data.tweets_stream(cfg["scale"], traffic["order_seed"], seed)
    return dict(keys=keys, vals=vals)


def _keep_all(k, v):
    """Filter predicate (module level: one jit trace-cache identity)."""
    return np.ones(k.shape, dtype=bool)


def build(cfg: dict, d: dict, *, executor=None, use_kernel: bool = False):
    from repro.core import ReshapeConfig
    from repro.dataflow.engine import Engine, Source
    from repro.dataflow.operators import Filter, HashJoinProbe, Sink

    W, K, rate = cfg["num_workers"], cfg["num_locations"], cfg["service_rate"]
    emit = W * rate                     # the join is the bottleneck
    eng = Engine(partition_backend="pallas", batch_ticks=cfg["batch_ticks"],
                 device_executor=executor, device_use_kernel=use_kernel,
                 device_controller=cfg["device_controller"])
    src = eng.add_source(Source("tweets", d["keys"], d["vals"], emit))
    filt = eng.add_op(Filter("filter", W, emit, predicate=_keep_all))
    join = eng.add_op(HashJoinProbe("join", W, rate))
    sink = eng.add_op(Sink("viz", K, snapshot_every=cfg["snapshot_every"]))
    eng.connect(src, filt, K)
    join_edge = eng.connect(filt, join, K)
    eng.connect(join, sink, K)
    join.install_build(join_edge.routing, np.arange(K, dtype=np.int64),
                       np.ones(K, dtype=np.float64))
    pin = cfg["pinned_helper"]
    rcfg = ReshapeConfig(**cfg["reshape"], pinned_helpers={
        pin["skewed_location"] % W: pin["helper_location"] % W})
    eng.attach_controller(join, rcfg)
    return eng, join, sink


def emit_rate(cfg: dict) -> int:
    return cfg["num_workers"] * cfg["service_rate"]


def representative(cfg: dict, d: dict):
    """The §7.2 pair whose visible ratio has to settle, and its actual ratio."""
    rep = cfg["representative"]
    counts = np.bincount(d["keys"], minlength=cfg["num_locations"])
    return rep["key_a"], rep["key_b"], counts[rep["key_a"]] / counts[rep["key_b"]], rep["tol"]


def outputs(eng, last_op, sink) -> dict:
    return dict(series=list(sink.series), counts=sink.counts.copy(),
                sums=sink.sums.copy())


def reference(cfg: dict, d: dict) -> dict:
    K = cfg["num_locations"]
    return dict(counts=checks.ref_counts(d["keys"], K),
                sums=checks.ref_sums(d["keys"], d["vals"], K))


def compare(cfg: dict, d: dict, ref: dict, out: dict) -> dict:
    return dict(
        count_mismatch=checks.count_mismatch(out["counts"], ref["counts"]),
        series_violations=checks.prefix_series_violations(
            out["series"], d["keys"], emit_rate(cfg), cfg["snapshot_every"],
            ref["counts"]),
        sums_rel_err=checks.sums_rel_err(out["sums"], ref["sums"]))
