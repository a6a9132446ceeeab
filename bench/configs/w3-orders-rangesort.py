"""W3 of the paper (§7.10): a range sort of TPC-H orders on o_totalprice.

The graph, its data and its plain reference.  The reference's sorted
output is ``np.sort`` of the prices, its per-range counts and sums those
of ``searchsorted`` over the equal-width bounds.  The sort blocks, so the
sink may show nothing before the sort's END and then everything (see
``checks.blocking_series_violations``).
"""
from __future__ import annotations

import numpy as np

from bench import checks, data


def make_data(cfg: dict, traffic: dict, seed: int) -> dict:
    sf = cfg["scale_factor"]
    prices = data.tpch_orders(int(round(sf * cfg["orders_per_scale_factor"])),
                              seed, sf)
    lo, hi = cfg["price_range"]
    bounds = data.price_ranges(cfg["num_ranges"], lo, hi)
    return dict(keys=data.range_ids(prices, bounds), vals=prices)


def build(cfg: dict, d: dict, *, executor=None, use_kernel: bool = False):
    from repro.core import ReshapeConfig
    from repro.dataflow.engine import Engine, Source
    from repro.dataflow.operators import RangeSort, Sink

    W, K, rate = cfg["num_workers"], cfg["num_ranges"], cfg["service_rate"]
    eng = Engine(partition_backend="pallas", batch_ticks=cfg["batch_ticks"],
                 device_executor=executor, device_use_kernel=use_kernel,
                 device_controller=cfg["device_controller"])
    src = eng.add_source(Source("orders", d["keys"], d["vals"], W * rate))
    sort = eng.add_op(RangeSort("sort", W, rate))
    sink = eng.add_op(Sink("out", K, snapshot_every=cfg["snapshot_every"]))
    eng.connect(src, sort, K)
    eng.connect(sort, sink, K)
    eng.attach_controller(sort, ReshapeConfig(**cfg["reshape"]))
    if cfg["device_controller"] and not (
            sort.device is not None and sort.device.ctrl is not None
            and sort.device.ctrl.active):
        raise RuntimeError("the sort's controller did not arm in-dispatch")
    return eng, sort, sink


def emit_rate(cfg: dict) -> int:
    return cfg["num_workers"] * cfg["service_rate"]


def outputs(eng, last_op, sink) -> dict:
    return dict(series=list(sink.series), counts=sink.counts.copy(),
                sums=sink.sums.copy(), sorted=last_op.sorted_output())


def reference(cfg: dict, d: dict) -> dict:
    K = cfg["num_ranges"]
    return dict(counts=checks.ref_counts(d["keys"], K),
                sums=checks.ref_sums(d["keys"], d["vals"], K),
                sorted=np.sort(d["vals"]))


def compare(cfg: dict, d: dict, ref: dict, out: dict) -> dict:
    got = out["sorted"]
    sorted_mismatch = (int(np.count_nonzero(got != ref["sorted"]))
                       if got.shape == ref["sorted"].shape
                       else int(ref["sorted"].size))
    return dict(
        count_mismatch=checks.count_mismatch(out["counts"], ref["counts"]),
        series_violations=checks.blocking_series_violations(
            out["series"], ref["counts"]),
        sorted_mismatch=sorted_mismatch,
        sums_rel_err=checks.sums_rel_err(out["sums"], ref["sums"]))
