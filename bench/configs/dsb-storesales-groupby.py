"""DSB store_sales grouped by item (the paper's aggregate workflows, §7.1).

``SELECT ss_item_sk, COUNT(*), SUM(v) FROM store_sales WHERE v < 0.9
GROUP BY ss_item_sk`` over DSB's fact table, whose 18,000 items (SF 1)
follow a Zipf law with s = 2.0; ``scale_factor`` times SF 1's 2,880,404
rows.  The graph, its data and its plain reference.  The group-by blocks:
at its END it hands the sink one row per present item carrying the item's
sum, so the sink shows nothing before END and then every item once (see
``checks.blocking_series_violations``); the per-item counts are read from
the group-by's merged state.
"""
from __future__ import annotations

import numpy as np

from bench import checks


def make_data(cfg: dict, traffic: dict, seed: int) -> dict:
    n = int(round(cfg["scale_factor"] * cfg["rows_per_scale_factor"]))
    rng = np.random.default_rng(seed)
    p = np.arange(1, cfg["num_items"] + 1, dtype=np.float64) ** -cfg["item_zipf"]
    items = rng.choice(cfg["num_items"], size=n, p=p / p.sum())
    return dict(keys=items.astype(np.int64), vals=rng.random(n))


def _keep(k, v):
    """The filter (module level: one jit trace-cache identity per run)."""
    return v < 0.9


def build(cfg: dict, d: dict, *, executor=None, use_kernel: bool = False):
    from repro.core import ReshapeConfig
    from repro.dataflow.engine import Engine, Source
    from repro.dataflow.operators import Filter, GroupByAgg, Sink

    if cfg["filter_below"] != 0.9:
        raise ValueError("the filter is v < 0.9")
    W, K, rate = cfg["num_workers"], cfg["num_items"], cfg["service_rate"]
    eng = Engine(partition_backend="pallas", batch_ticks=cfg["batch_ticks"],
                 device_executor=executor, device_use_kernel=use_kernel,
                 device_controller=cfg["device_controller"])
    src = eng.add_source(Source("sales", d["keys"], d["vals"], W * rate))
    filt = eng.add_op(Filter("filter", W, cfg["filter_rate"], predicate=_keep))
    grp = eng.add_op(GroupByAgg("groupby_item", W, rate))
    sink = eng.add_op(Sink("out", K, snapshot_every=cfg["snapshot_every"]))
    eng.connect(src, filt, K)
    eng.connect(filt, grp, K)
    eng.connect(grp, sink, K)
    eng.attach_controller(grp, ReshapeConfig(**cfg["reshape"]))
    if cfg["device_controller"] and not (
            grp.device is not None and grp.device.ctrl is not None
            and grp.device.ctrl.active):
        raise RuntimeError("the group-by's controller did not arm in-dispatch")
    return eng, grp, sink


def emit_rate(cfg: dict) -> int:
    return cfg["num_workers"] * cfg["service_rate"]


def outputs(eng, last_op, sink) -> dict:
    return dict(series=list(sink.series), rows=sink.counts.copy(),
                sums=sink.sums.copy(),
                counts=sum(w.state.counts for w in last_op.workers))


def reference(cfg: dict, d: dict) -> dict:
    K = cfg["num_items"]
    keep = d["vals"] < cfg["filter_below"]
    counts = checks.ref_counts(d["keys"][keep], K)
    return dict(counts=counts, rows=(counts > 0).astype(np.int64),
                sums=checks.ref_sums(d["keys"][keep], d["vals"][keep], K))


def compare(cfg: dict, d: dict, ref: dict, out: dict) -> dict:
    return dict(
        count_mismatch=(checks.count_mismatch(out["counts"], ref["counts"])
                        + checks.count_mismatch(out["rows"], ref["rows"])),
        series_violations=checks.blocking_series_violations(
            out["series"], ref["rows"]),
        sums_rel_err=checks.sums_rel_err(out["sums"], ref["sums"]))
