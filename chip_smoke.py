"""Run the engine's device-resident plane end to end on one TPU chip.

    python chip_smoke.py

One process, one chip.  Each phase builds a workflow through the public
builders or ``Engine`` with ``partition_backend="pallas"`` and the default
device executor, which resolves to the jitted plane on a TPU, and runs it
with the runtime sanitizers armed (``REPRO_SANITIZE=1``) and the engine's
``RuntimeWarning``s raised as errors:

  A   W1, tweets join slang per location -> sink (``build_w1``): scale 1.0
      (the paper's section 7.2 ratios, CA = 26,000 tweets), 48 workers,
      56 locations, Reshape attached, service rate 16 to cut the ticks;
      against the numpy plane and the tuple-at-a-time reference oracle.
  B   W3, range sort of TPC-H orders at SF 1 (``build_w3``, 1.5M orders,
      20 workers, 40 ranges), controller armed in-dispatch, every order in
      the device row store; against the numpy plane.
  B'  W3 at 200k orders with the Pallas partition kernels compiled into
      the step (the engine's ``device_use_kernel`` option, set on the
      built workflow before its first dispatch).
  C   Filter -> GroupByAgg -> Sink over the DSB item column (10**6 rows,
      128 items, Zipf s = 2.0), a fused chain with the GroupBy's
      controller armed in-dispatch.

A phase fails unless every edge ran on the jit plane, no demotion,
fallback, controller-mismatch or sanitizer incident was recorded, and
the integer results -- sink series and counts, per-worker routing
histograms, split counters, controller event streams and final tables,
the sorted output -- equal the numpy plane's bit for bit.  Float sums may
differ by accumulation order and must agree within ``SUM_RTOL``.  A
window schedule of fixed width drives both planes of B, B' and C: an
armed controller does not cut windows at metric rounds, a host-stepped
one does, and only equal schedules give comparable decisions.

The lines before the last report each phase's settings, wall seconds
(compiles included), compile count, super-ticks and peak device bytes:
bring-up facts, not measurements.  The last line is the JSON device
record.  With no TPU the script exits non-zero and prints no record.
"""
from __future__ import annotations

import contextlib
import inspect
import json
import os
import sys
import time
import warnings

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402

from repro.analysis import sanitize  # noqa: E402
from repro.core import ReshapeConfig  # noqa: E402
from repro.dataflow import build_w1, build_w3, datasets, resilience  # noqa: E402
from repro.dataflow.engine import Engine, Source  # noqa: E402
from repro.dataflow.operators import Filter, GroupByAgg, Sink  # noqa: E402

#: relative tolerance for float64 sums (Sink.sums): scatter-add order on
#: the device is unspecified, numpy's bincount sums sequentially.
SUM_RTOL = 1e-9
#: the same for sums folded by the Pallas kernel, which accumulates in
#: float32 by design (``partition_scatter_fold``).
KERNEL_SUM_RTOL = 1e-5

#: incident kinds that mean a device path quietly did not run.
BAD_INCIDENTS = ("demotion", "chain-fallback", "ctrl-demotion",
                 "ctrl-mismatch")


class SmokeFailure(AssertionError):
    """A phase's check failed."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@contextlib.contextmanager
def _strict():
    """The engine's RuntimeWarnings (demotions, arbitrations) are errors."""
    with warnings.catch_warnings():
        warnings.filterwarnings("error", category=RuntimeWarning,
                                module=r"repro\.")
        yield


def _drive(eng: Engine, k: int) -> None:
    """Fixed-width window schedule, identical across compared planes."""
    while not eng.done():
        eng.run_super_tick(k)


def _peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _run_device(eng: Engine, run) -> dict:
    """Run the device plane under the strict checks; bring-up facts."""
    before = sanitize.trace_counts()
    n_global = len(resilience.GLOBAL)
    t0 = time.perf_counter()
    with _strict():
        run()
    wall = time.perf_counter() - t0
    traced = {k: n - before.get(k, 0)
              for k, n in sanitize.trace_counts().items()
              if n > before.get(k, 0)}
    planes = [e.device_plane for e in eng.edges]
    _check(all(p == "jit" for p in planes), f"edge planes {planes}")
    incidents = list(eng.incidents) + resilience.GLOBAL.incidents[n_global:]
    bad = [f"{i.kind}@{i.edge}: {i.cause}" for i in incidents
           if i.kind in BAD_INCIDENTS or i.kind.startswith("sanitize-")]
    _check(not bad, f"incidents {bad}")
    return dict(wall_s=wall, compiles=sum(traced.values()),
                step_kinds=sorted({_step_kind(k) for k in traced}),
                super_ticks=eng.super_ticks, ticks=eng.tick,
                peak_bytes_in_use=_peak_bytes(), traced=traced)


def _step_kind(key) -> str:
    kind, spec, _ = key
    return kind if kind in ("chain", "ctrl") else spec.kind


def _same_series(a: Sink, b: Sink, what: str) -> None:
    _check(len(a.series) == len(b.series)
           and all(ta == tb and np.array_equal(ca, cb)
                   for (ta, ca), (tb, cb) in zip(a.series, b.series)),
           f"{what}: Sink.series differ")
    _check(np.array_equal(a.counts, b.counts), f"{what}: Sink.counts differ")


def _events(ctrl) -> list:
    return [(e.tick, e.kind, e.skewed, tuple(e.helpers),
             tuple(sorted(e.detail.items()))) for e in ctrl.events]


def _same_as_host(host: Engine, dev: Engine, host_ctrls, dev_ctrls,
                  sum_rtol: float = SUM_RTOL) -> dict:
    """The integer contract against the numpy plane, and the float sums."""
    _check(host.tick == dev.tick, f"ticks {host.tick} != {dev.tick}")
    _same_series(host.sink, dev.sink, "numpy plane")
    for ea, eb in zip(host.edges, dev.edges):
        name = ea.dst.name
        _check(np.array_equal(ea.sent_per_worker, eb.sent_per_worker),
               f"{name}: sent_per_worker differ")
        eb.routing.sync_counters()
        _check(np.array_equal(ea.routing._count, eb.routing._count),
               f"{name}: routing counters differ")
        _check(np.array_equal(ea.routing.weights, eb.routing.weights),
               f"{name}: routing tables differ")
    for ca, cb in zip(host_ctrls, dev_ctrls):
        _check(_events(ca) == _events(cb), "controller events differ")
    a, b = host.sink.sums, dev.sink.sums
    err = float(np.max(np.abs(a - b) / np.maximum(np.abs(a), 1e-300),
                       initial=0.0))
    _check(err <= sum_rtol, f"Sink.sums rel err {err} > {sum_rtol}")
    return dict(events=len(_events(dev_ctrls[0])) if dev_ctrls else 0,
                sums_max_rel_err=err)


# --------------------------------------------------------------------- #
# Phases (sizes and executor are arguments; the TPU check is in main)    #
# --------------------------------------------------------------------- #
def phase_w1(*, scale: float = 1.0, num_workers: int = 48,
             service_rate: int = 16, batch_ticks: int = 8,
             snapshot_every: int = 8, executor=None,
             reference: bool = True) -> dict:
    """A: W1 under Reshape; numpy plane and reference oracle agree.
    (``build_w1``'s default service rate of 4 takes ~4,000 ticks, each a
    super-tick of its own under the host-stepped controller.)"""
    kw = dict(strategy="reshape", scale=scale, num_workers=num_workers,
              service_rate=service_rate, batch_ticks=batch_ticks,
              snapshot_every=snapshot_every)
    host = build_w1(partition_backend="numpy", **kw)
    host.run()
    dev = build_w1(partition_backend="pallas", device_executor=executor,
                   **kw)
    rep = _run_device(dev.engine, dev.run)
    rep.update(_same_as_host(host.engine, dev.engine, host.controllers,
                             dev.controllers))
    if reference:
        ref = build_w1(reference=True, **kw)
        ref.run()
        _same_series(ref.sink, dev.sink, "reference oracle")
    rep.update(tuples=int(dev.engine.sources[0].keys.size),
               reference_checked=reference)
    return rep


def phase_w3(*, n_tuples: int = 1_500_000, num_workers: int = 20,
             service_rate: int = 6, batch_ticks: int = 256,
             snapshot_every: int = 256, use_kernel: bool = False,
             executor=None) -> dict:
    """B / B': W3 range sort, controller armed in-dispatch."""
    kw = dict(strategy="reshape", n_tuples=n_tuples,
              num_workers=num_workers, service_rate=service_rate,
              batch_ticks=batch_ticks, snapshot_every=snapshot_every)
    host = build_w3(partition_backend="numpy", **kw)
    _drive(host.engine, batch_ticks)
    dev = build_w3(partition_backend="pallas", device_executor=executor,
                   device_controller=True, **kw)
    if use_kernel:
        # Engine(device_use_kernel=True) for a built workflow: the edge
        # runtimes read the option at every dispatch.
        dev.engine.device_use_kernel = True
        for op in dev.engine.ops:
            if op.device is not None:
                op.device.use_kernel = True
    sort = dev.monitored[0]
    _check(sort.device is not None and sort.device.ctrl is not None
           and sort.device.ctrl.active, "W3 controller not armed")
    rep = _run_device(dev.engine, lambda: _drive(dev.engine, batch_ticks))
    rep.update(_same_as_host(
        host.engine, dev.engine, host.controllers, dev.controllers,
        KERNEL_SUM_RTOL if use_kernel else SUM_RTOL))
    _check(np.array_equal(host.monitored[0].sorted_output(),
                          sort.sorted_output()), "sorted_output differs")
    if use_kernel:
        # The kernels run in the sink fold and in the row store's
        # split-table ingest once the controller has split a range.
        ran = {spec.kind for kind, spec, _ in rep["traced"]
               if kind in ("fold", "sink") and spec.use_kernel
               and (spec.kind == "sink" or spec.any_split)}
        _check(ran == {"rows", "sink"}, f"kernel steps traced: {ran}")
    rep.update(tuples=n_tuples)
    return rep


def _keep(k, v):
    """Filter predicate (module level: a stable jit trace-cache identity)."""
    return v < 0.9


def _chain(backend, *, items, vals, num_workers, service_rate, batch_ticks,
           snapshot_every, executor=None, device_controller=False):
    n_items = datasets.DsbSpec().num_items
    eng = Engine(partition_backend=backend, batch_ticks=batch_ticks,
                 device_executor=executor,
                 device_controller=device_controller)
    rate = num_workers * service_rate
    src = eng.add_source(Source("sales", items, vals, rate))
    filt = eng.add_op(Filter("filter", num_workers, rate, predicate=_keep))
    grp = eng.add_op(GroupByAgg("groupby_item", num_workers, service_rate))
    sink = eng.add_op(Sink("viz", n_items, snapshot_every=snapshot_every))
    eng.connect(src, filt, n_items)
    eng.connect(filt, grp, n_items)
    eng.connect(grp, sink, n_items)
    ctrl = eng.attach_controller(grp, ReshapeConfig())
    return eng, grp, ctrl


def phase_chain(*, n_rows: int = 1_000_000, num_workers: int = 40,
                service_rate: int = 256, batch_ticks: int = 64,
                snapshot_every: int = 64, executor=None) -> dict:
    """C: fused Filter -> GroupByAgg -> Sink, GroupBy controller armed."""
    _, items, _, vals = datasets.dsb_sales(n_rows)
    kw = dict(items=items, vals=vals, num_workers=num_workers,
              service_rate=service_rate, batch_ticks=batch_ticks,
              snapshot_every=snapshot_every)
    host, _, host_ctrl = _chain("numpy", **kw)
    _drive(host, batch_ticks)
    dev, grp, dev_ctrl = _chain("pallas", executor=executor,
                                device_controller=True, **kw)
    _check(grp.device is not None and grp.device.ctrl is not None
           and grp.device.ctrl.active, "GroupBy controller not armed")
    rep = _run_device(dev, lambda: _drive(dev, batch_ticks))
    _check("chain" in rep["step_kinds"], "the chain never fused")
    rep.update(_same_as_host(host, dev, [host_ctrl], [dev_ctrl]))
    rep.update(tuples=n_rows)
    return rep


PHASES = [
    ("A", "W1 join, scale 1.0", phase_w1, dict()),
    ("B", "W3 sort, SF 1 orders", phase_w3, dict()),
    ("B'", "W3 sort, Pallas kernels", phase_w3,
     dict(n_tuples=200_000, use_kernel=True)),
    ("C", "DSB item chain", phase_chain, dict()),
]


def main() -> int:
    dev0 = jax.devices()[0]
    if dev0.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev0.platform!r}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)
    os.environ["REPRO_SANITIZE"] = "1"
    for name, title, fn, kw in PHASES:
        settings = {p.name: p.default
                    for p in inspect.signature(fn).parameters.values()}
        settings.update(kw)
        print(f"phase {name} ({title}) settings: {settings}", flush=True)
        rep = fn(**kw)
        rep.pop("traced")
        print(f"phase {name} ok: {rep}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
