"""One run of one benchmark cell: set-up, the measured window, the check.

A cell names a configuration (``configs/<config>.json`` with its module
``configs/<config>.py``: graph, data, plain reference, comparison) and a
traffic mix (``traffic/<cell>.json``).  A run

1. generates the cell's data from the seed on the host and runs one whole
   execution of the graph on the device plane as warm-up: ring and row
   store capacities grow during an execution, so only a whole one compiles
   every shape the window meets.  All of that is ``setup_s``;
2. runs executions back to back, each a fresh ``Engine`` over the same
   data driven by ``Engine.run()``, as an analyst re-running a workflow.
   ``run_super_tick`` is wrapped to record the wall clock at every
   super-tick boundary; the window closes at the first boundary after
   ``seconds``;
3. finishes the interrupted execution with ``Engine.run()``, untimed (it
   picks every window afresh, so the result is that of an uninterrupted
   run), reads the device's peak memory, frees the program's state, and
   compares every execution of the window with the plain reference.

With ``trace`` the run also records host spans around the controller
entries and the sink snapshot, traces a few seconds of the window with the
JAX profiler, and reports the cell's per-layer metrics (read by
``metrics/<name>.py``) in place of its end-to-end ones.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import trace

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

#: incident kinds that mean a device path quietly did not run.
BAD_INCIDENTS = ("demotion", "chain-fallback", "ctrl-demotion",
                 "ctrl-mismatch")
#: JAX's event for every executable it builds, compiled or loaded from the
#: persistent cache.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: the traced stretches: where in the window the first starts, and the
#: length of the device stretch and of the host stretch after it.
TRACE_AT_SHARE, TRACE_SECONDS, HOST_TRACE_SECONDS = 0.25, 4.0, 2.0

now = time.perf_counter


# --------------------------------------------------------------------- #
# Finding a cell's files by name                                         #
# --------------------------------------------------------------------- #
def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(spec: dict, name: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == name:
            return cell
    raise KeyError(f"no cell {name!r} in BENCHMARK.json")


def _load_module(path: str, name: str):
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def _module_name(kind: str, name: str) -> str:
    return "bench_" + kind + "_" + "".join(
        c if c.isalnum() else "_" for c in name)


def load_config(name: str):
    """The configuration's sizes (JSON) and its graph/reference module."""
    base = os.path.join(BENCH_DIR, "configs", name)
    with open(base + ".json") as f:
        cfg = json.load(f)
    return cfg, _load_module(base + ".py", _module_name("config", name))


def load_traffic(cell_name: str) -> dict:
    with open(os.path.join(BENCH_DIR, "traffic", cell_name + ".json")) as f:
        return json.load(f)


def _applies(metric: dict, cell_name: str) -> bool:
    return cell_name in metric.get("workloads", [cell_name])


def load_readers(spec: dict, cell_name: str) -> Dict[str, Callable]:
    """``read(run) -> value | None`` of each per-layer metric of the cell."""
    readers = {}
    for m in spec["per_layer"]:
        if _applies(m, cell_name):
            path = os.path.join(BENCH_DIR, "metrics", m["name"] + ".py")
            readers[m["name"]] = _load_module(
                path, _module_name("metric", m["name"])).read
    return readers


# --------------------------------------------------------------------- #
# Counters the window reads                                              #
# --------------------------------------------------------------------- #
class _Compiles:
    """Executables JAX builds, counted from its monitoring events."""

    count = 0
    _registered = False

    @classmethod
    def arm(cls) -> None:
        if not cls._registered:
            import jax

            def listen(event, duration, **kw):
                if event == COMPILE_EVENT:
                    cls.count += 1

            jax.monitoring.register_event_duration_secs_listener(listen)
            cls._registered = True


def _traces() -> int:
    from repro.analysis import sanitize
    return sum(sanitize.trace_counts().values())


class _Spans:
    """Host spans around the controller entries and the sink snapshot.

    Installed for traced runs only.  Each entry becomes a named
    ``TraceAnnotation`` in the profiler's trace, and the wall time inside
    the outermost controller entry is summed while ``recording``."""

    def __init__(self):
        self.recording = False
        self.ctrl_s = 0.0
        self._depth = 0

    @contextlib.contextmanager
    def installed(self):
        import jax
        from repro.core.controller import ReshapeController
        from repro.dataflow.device import DeviceController
        from repro.dataflow.operators import Sink

        patches = [(ReshapeController, "step", "ctrl.step", True),
                   (DeviceController, "super_tick", "ctrl.super_tick", True),
                   (DeviceController, "drain", "ctrl.drain", True),
                   (Sink, "snapshot", "sink.snapshot", False)]
        saved = [(cls, attr, cls.__dict__[attr]) for cls, attr, _, _ in patches]
        for cls, attr, span, is_ctrl in patches:
            setattr(cls, attr, self._wrap(getattr(cls, attr), span, is_ctrl,
                                          jax.profiler.TraceAnnotation))
        try:
            yield self
        finally:
            for cls, attr, fn in saved:
                setattr(cls, attr, fn)

    def _wrap(self, fn, span, is_ctrl, annotation):
        spans = self

        def wrapped(*args, **kwargs):
            with annotation(span):
                if not is_ctrl:
                    return fn(*args, **kwargs)
                spans._depth += 1
                t = now()
                try:
                    return fn(*args, **kwargs)
                finally:
                    spans._depth -= 1
                    if spans._depth == 0 and spans.recording:
                        spans.ctrl_s += now() - t
        return wrapped


class _Profiler:
    """Two stretches of the window under the JAX profiler, one after the
    other, the Python tracer off in both.

    The device stretch records the chips' operations with the host tracer
    off, so that the host keeps its untraced pace (the host tracer records
    every thunk of the controller's XLA:CPU step and slows it ~50x): its
    reduction gives the busy time, the idle share and the top operations.
    The host stretch records the harness's spans as well and puts each
    instant the chip is idle down to one of them.  The host spans stop
    adding controller time while either runs, and ``stretch`` (both) is
    left out of the host-clock per-layer metrics."""

    def __init__(self, start_at: float, seconds: float, host_seconds: float,
                 spans: "_Spans"):
        self.start_at = start_at
        self.seconds = (seconds, host_seconds)
        self.spans = spans
        self.dirs: List[str] = []                    # device, host
        self.clock: Optional[tuple] = None   # device stretch, trace clock
        self.stretch: Optional[List[float]] = None   # [on, off] wall
        self.device_wall: Optional[tuple] = None      # device stretch, wall
        self._session = self._on = 0.0
        self._stop_at: Optional[float] = None
        self._marker = None

    def on_boundary(self, wall: float) -> None:
        if not self.dirs:
            if wall >= self.start_at:
                self._start(host=False)
        elif self._stop_at is not None and wall >= self._stop_at:
            device_done = len(self.dirs) == 1
            self.stop()
            if device_done:
                self._start(host=True)

    def _start(self, host: bool) -> None:
        import jax
        self.spans.recording = False
        self._session = now()
        if self.stretch is None:
            self.stretch = [self._session, None]
        self.dirs.append(tempfile.mkdtemp(prefix="bench-trace-"))
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1 if host else 0
        jax.profiler.start_trace(self.dirs[-1], profiler_options=options)
        self._on = now()
        self._stop_at = self._on + self.seconds[host]
        if host:
            self._marker = jax.profiler.TraceAnnotation(trace.MARKER)
            self._marker.__enter__()

    def stop(self) -> None:
        if self._stop_at is None:
            return
        import jax
        off = now()
        if self._marker is not None:
            self._marker.__exit__(None, None, None)
            self._marker = None
        else:
            # the trace's clock counts from the session's start, which
            # start_trace opens
            self.clock = (self._on - self._session, off - self._session)
            self.device_wall = (self._on, off)
        jax.profiler.stop_trace()
        self._stop_at = None
        self.stretch[1] = now()
        self.spans.recording = True

    def reduce(self) -> Optional[dict]:
        """The device stretch's reduction over the run's accelerator chips,
        with the host stretch's idle gaps (None on a CPU run, which gives
        no device numbers)."""
        import jax
        chips = {f"/device:{d.platform.upper()}:{d.id}"
                 for d in jax.local_devices() if d.platform != "cpu"}
        try:
            dev = (trace.reduce_file(self.dirs[0], chips, self.clock)
                   if self.clock is not None else None)
            host = (trace.reduce_file(self.dirs[1], chips)
                    if dev is not None and len(self.dirs) > 1 else None)
        finally:
            for d in self.dirs:
                shutil.rmtree(d, ignore_errors=True)
        if dev is not None:
            dev["idle_gaps"] = host["idle_gaps"] if host is not None else []
        return dev


# --------------------------------------------------------------------- #
# Executions                                                             #
# --------------------------------------------------------------------- #
class _WindowClosed(Exception):
    """Raised at the first super-tick boundary after the window's end."""


@dataclasses.dataclass
class Execution:
    engine: object
    last_op: object
    sink: object
    start: float
    #: (first tick, ticks, wall at start, wall at end) of every super-tick
    steps: List[tuple] = dataclasses.field(default_factory=list)
    processed_in_window: int = 0
    error: Optional[str] = None


def _processed(op) -> int:
    """Tuples the operator's workers have popped and processed."""
    return int(sum(w.stats.processed_total for w in op.workers))


class _Window:
    """Wraps each execution's ``run_super_tick``; closes the window."""

    def __init__(self, deadline: float, annotate, on_boundary=None):
        self.deadline: Optional[float] = deadline
        self.close: Optional[float] = None
        self.annotate = annotate
        self.on_boundary = on_boundary

    def attach(self, ex: Execution) -> None:
        eng, inner, window = ex.engine, ex.engine.run_super_tick, self

        def run_super_tick(k):
            t0, ws = eng.tick, now()
            with window.annotate("engine.super_tick"):
                inner(k)
            we = now()
            ex.steps.append((t0, k, ws, we))
            if window.deadline is None:
                return
            if window.on_boundary is not None:
                window.on_boundary(we)
            if we >= window.deadline:
                window.close = we
                ex.processed_in_window = _processed(ex.last_op)
                raise _WindowClosed

        eng.run_super_tick = run_super_tick


def _incidents_of(eng, global_from: int) -> List[str]:
    from repro.dataflow import resilience
    found = list(eng.incidents) + resilience.GLOBAL.incidents[global_from:]
    return [f"{i.kind}@{i.edge}: {i.cause}" for i in found
            if i.kind in BAD_INCIDENTS]


# --------------------------------------------------------------------- #
# End-to-end metrics                                                     #
# --------------------------------------------------------------------- #
def _step_of(t0s: np.ndarray, ticks: np.ndarray) -> np.ndarray:
    """Index of the super-tick covering each tick (later ticks, such as the
    END snapshot's, fall to the last)."""
    return np.clip(np.searchsorted(t0s, ticks, side="right") - 1,
                   0, t0s.size - 1)


def result_latencies(steps: List[tuple], series, keys: np.ndarray,
                     emit_rate: int, close: float) -> np.ndarray:
    """Seconds from the start of the super-tick that emitted each source
    tuple to the end of the super-tick whose sink snapshot first shows its
    key's count at or above the tuple's ordinal among its key's tuples;
    only tuples that became visible by ``close``."""
    st = np.asarray(steps, dtype=np.float64)
    t0s, wall_start, wall_end = st[:, 0], st[:, 2], st[:, 3]
    snap_ticks = np.array([t for t, _ in series], dtype=np.float64)
    snap_end = wall_end[_step_of(t0s, snap_ticks)]
    counts = np.stack([np.asarray(c) for _, c in series])
    emitted = wall_start[_step_of(t0s, np.arange(keys.size) // emit_rate)]
    out = []
    for k in np.unique(keys):
        idx = np.flatnonzero(keys == k)          # stream order
        first = np.searchsorted(counts[:, k], np.arange(1, idx.size + 1),
                                side="left")
        seen = first < counts.shape[0]
        vis = snap_end[first[seen]]
        lat = vis - emitted[idx[seen]]
        out.append(lat[vis <= close])
    return np.concatenate(out) if out else np.zeros(0)


def time_to_representative(steps: List[tuple], start: float, series,
                           key_a: int, key_b: int, actual: float, tol: float,
                           close: float):
    """(convergence tick, seconds from the execution's start to the end of
    the super-tick holding it, or None when that end is after ``close``)."""
    from bench import data
    tick = data.convergence_tick(series, key_a, key_b, actual, tol)
    if tick is None:
        return None, None
    st = np.asarray(steps, dtype=np.float64)
    end = st[_step_of(st[:, 0], np.array([tick]))[0], 3]
    return tick, (end - start if end <= close else None)


# --------------------------------------------------------------------- #
# A cell prepared for a run                                              #
# --------------------------------------------------------------------- #
class Cell:
    """A cell's configuration, traffic and data for one seed.

    ``control`` swaps in a lower-precision twin of the program: "kernel"
    switches on the engine's float32 Pallas fold (``device_use_kernel``),
    "float32-values" hands the program the values rounded to float32; the
    reference always gets the float64 data.  ``overrides`` =
    {"config": {...}, "traffic": {...}} resizes the cell (the CPU
    rehearsals use it)."""

    def __init__(self, name: str, seed: int, *, spec: Optional[dict] = None,
                 executor: Optional[str] = None,
                 control: Optional[str] = None,
                 overrides: Optional[dict] = None):
        self.spec = load_spec() if spec is None else spec
        self.cfg, self.mod = load_config(find_cell(self.spec, name)["config"])
        traffic = load_traffic(name)
        overrides = overrides or {}
        self.cfg.update(overrides.get("config", {}))
        traffic.update(overrides.get("traffic", {}))
        self.limits = self.cfg["limits"]
        self.max_ticks = int(self.cfg["max_ticks"])
        self.data = self.mod.make_data(self.cfg, traffic, int(seed) % 2**64)
        self.prog = dict(self.data)
        if control == "float32-values":
            self.prog["vals"] = self.data["vals"].astype(
                np.float32).astype(np.float64)
        elif control not in (None, "kernel"):
            raise ValueError(f"unknown control {control!r}")
        self.use_kernel = control == "kernel"
        self.executor = executor
        self._ref = None

    def build(self):
        """(engine, last operator before the sink, sink) of a fresh graph."""
        return self.mod.build(self.cfg, self.prog, executor=self.executor,
                              use_kernel=self.use_kernel)

    def record(self, ex: "Execution", global_from: int) -> dict:
        """What the check and the metrics need of a finished execution."""
        eng = ex.engine
        rec = dict(error=ex.error, steps=ex.steps, start=ex.start,
                   processed=ex.processed_in_window,
                   done=ex.error is None and eng.done(),
                   ticks_to_finish=eng.ticks_to_finish,
                   planes=[e.device_plane for e in eng.edges],
                   incidents=_incidents_of(eng, global_from), out=None)
        if ex.error is None:
            try:
                rec["out"] = self.mod.outputs(eng, ex.last_op, ex.sink)
            except Exception as exc:
                rec["error"] = f"{type(exc).__name__}: {exc}"
        return rec

    def numbers(self, rec: dict) -> Dict[str, float]:
        """Each compared number of one execution."""
        if self._ref is None:
            self._ref = self.mod.reference(self.cfg, self.data)
        numbers = dict(non_jit_edges=sum(p != "jit" for p in rec["planes"]),
                       bad_incidents=len(rec["incidents"]),
                       unfinished=int(not rec["done"]))
        if rec["out"] is not None:
            numbers.update(self.mod.compare(self.cfg, self.data, self._ref,
                                            rec["out"]))
        for k in self.limits:
            numbers.setdefault(k, float("inf"))
        return numbers

    def failed(self, numbers: Dict[str, float]) -> bool:
        return any(numbers[k] > limit for k, limit in self.limits.items())


def readings(cell: Cell) -> Dict[str, float]:
    """The compared numbers of one uninterrupted execution of the timed
    path: a fresh graph run to its end by ``Engine.run()``."""
    from repro.dataflow import resilience
    global_from = len(resilience.GLOBAL)
    eng, last_op, sink = cell.build()
    ex = Execution(eng, last_op, sink, now())
    try:
        eng.run(cell.max_ticks)
    except Exception as exc:
        ex.error = f"{type(exc).__name__}: {exc}"
    rec = cell.record(ex, global_from)
    del ex, eng, last_op, sink
    gc.collect()
    return cell.numbers(rec)


# --------------------------------------------------------------------- #
# The run                                                                #
# --------------------------------------------------------------------- #
def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: Optional[float] = None, spec: Optional[dict] = None,
        **cell_kw) -> dict:
    """One run of a cell; returns the result line as a dict.  ``cell_kw``
    go to :class:`Cell` (``executor`` None is the jit plane on a TPU)."""
    t_start = now() if t_start is None else t_start
    import jax
    from repro.dataflow import resilience
    _Compiles.arm()
    cell = Cell(cell_name, seed, spec=spec, **cell_kw)
    spec = cell.spec

    # ---- set-up: one whole execution compiles every shape -------------- #
    eng, _, _ = cell.build()
    eng.run(cell.max_ticks)
    del eng
    gc.collect()

    spans = _Spans()
    profiler = None
    annotate = (jax.profiler.TraceAnnotation if trace
                else (lambda name: contextlib.nullcontext()))
    global_from = len(resilience.GLOBAL)
    start = now()
    setup_s = start - t_start
    if trace:
        profiler = _Profiler(start + TRACE_AT_SHARE * seconds,
                             min(TRACE_SECONDS, 0.5 * seconds),
                             min(HOST_TRACE_SECONDS, 0.25 * seconds), spans)
    window = _Window(start + seconds, annotate,
                     profiler.on_boundary if profiler else None)
    compiles0, traces0 = _Compiles.count, _traces()
    executions: List[Execution] = []

    # ---- the measured window ------------------------------------------ #
    with (spans.installed() if trace else contextlib.nullcontext()):
        spans.recording = True
        try:
            while window.close is None:
                t = now()
                with annotate("bench.build"):
                    eng, last_op, sink = cell.build()
                ex = Execution(eng, last_op, sink, t)
                executions.append(ex)
                window.attach(ex)
                try:
                    eng.run(cell.max_ticks)
                except _WindowClosed:
                    break
                except Exception as exc:        # the program failed
                    ex.error = f"{type(exc).__name__}: {exc}"
                    window.close = now()
                    break
                ex.processed_in_window = _processed(last_op)
                if not eng.done():
                    window.close = now()        # max_ticks: never finishes
        finally:
            if profiler is not None:
                profiler.stop()
            spans.recording = False
        compiles = _Compiles.count - compiles0
        traces = _traces() - traces0
        close = window.close
        window.deadline = None

        # ---- finish the interrupted execution, untimed ----------------- #
        last = executions[-1]
        if last.error is None and not last.engine.done():
            try:
                last.engine.run(cell.max_ticks)
            except Exception as exc:
                last.error = f"{type(exc).__name__}: {exc}"

    peak = peak_bytes()
    trace_summary = profiler.reduce() if profiler is not None else None

    # ---- outputs, then the program's state is freed; the check --------- #
    records = [cell.record(ex, global_from) for ex in executions]
    del eng, last_op, sink, ex, last, executions
    gc.collect()
    per_exec = [cell.numbers(rec) for rec in records]
    checks = {k: dict(value=max(n[k] for n in per_exec), limit=limit)
              for k, limit in cell.limits.items()}
    failed = sum(cell.failed(n) for n in per_exec)

    # ---- metrics ------------------------------------------------------- #
    cfg, mod, d = cell.cfg, cell.mod, cell.data
    window_wall = close - start
    in_window = [s for r in records for s in r["steps"] if s[3] <= close]
    host_wall = window_wall
    if profiler is not None and profiler.stretch is not None:
        on, off = profiler.stretch
        host_wall -= min(off, close) - on
        in_window = [s for s in in_window if s[3] <= on or s[2] >= off]
    first = records[0]
    conv = None
    notes = dict(executions=len(records), window_s=window_wall,
                 window_traces=traces,
                 errors=[r["error"] for r in records if r["error"]],
                 incidents=[i for r in records for i in r["incidents"]],
                 execution_s=[r["steps"][-1][3] - r["start"] for r in records
                              if r["steps"] and r["steps"][-1][3] < close],
                 cut_s=close - records[-1]["start"])
    if trace_summary is not None:
        on, off = profiler.device_wall
        notes["device_stretch"] = dict(
            op_events=trace_summary["op_events"],
            super_ticks=sum(on <= s[2] and s[3] <= off
                            for r in records for s in r["steps"]))
    e2e = dict(setup_s=setup_s,
               tuples_per_s=sum(r["processed"] for r in records) / window_wall)
    checked = [r for r in records if r["out"] is not None]
    if hasattr(mod, "representative") and checked:
        key_a, key_b, actual, tol = mod.representative(cfg, d)
        times = []
        for r in checked:
            tick, secs = time_to_representative(
                r["steps"], r["start"], r["out"]["series"], key_a, key_b,
                actual, tol, close)
            if r is first:
                conv = tick
            if secs is not None:
                times.append(secs)
        if times:
            e2e["time_to_representative_s"] = float(np.mean(times))
        notes["representative_executions"] = len(times)
    wanted = {m["name"] for m in spec["end_to_end"] if _applies(m, cell_name)}
    if "result_latency_p95_s" in wanted and checked:
        lat = np.concatenate([
            result_latencies(r["steps"], r["out"]["series"], d["keys"],
                             mod.emit_rate(cfg), close) for r in checked])
        if lat.size:
            e2e["result_latency_p95_s"] = float(np.percentile(lat, 95))
        notes["latency_samples"] = int(lat.size)

    run_rec = RunRecord(
        window_wall=window_wall,
        host_wall=host_wall,
        super_ticks=len(in_window), ticks=sum(s[1] for s in in_window),
        compiles=compiles, ctrl_s=spans.ctrl_s if trace else None,
        ticks_to_finish=first["ticks_to_finish"] if first["done"] else None,
        convergence_tick=conv, trace=trace_summary)

    units = {m["name"]: m["unit"]
             for m in spec["end_to_end"] + spec["per_layer"]}
    metrics = {}
    if trace:
        for name, read in load_readers(spec, cell_name).items():
            value = read(run_rec)
            if value is not None:
                metrics[name] = dict(value=value, unit=units[name])
    else:
        for name in wanted:
            if name in e2e:
                metrics[name] = dict(value=e2e[name], unit=units[name])
    device = device_record(peak)
    result = dict(correct=failed == 0, attempted=len(records), failed=failed,
                  metrics=metrics, device=device)
    if trace and trace_summary is not None:
        device.update(busy_s=trace_summary["busy_s"],
                      window_s=trace_summary["window_s"])
        result["breakdown"] = dict(device_ops=trace_summary["device_ops"],
                                   idle_gaps=trace_summary["idle_gaps"])
    result["notes"] = notes
    result["checks"] = checks
    return result


@dataclasses.dataclass
class RunRecord:
    """What the per-layer readers read (``metrics/<name>.py``)."""

    window_wall: float          # seconds of the measured window
    host_wall: float            # the same, less the traced stretch
    super_ticks: int            # super-ticks of host_wall
    ticks: int                  # engine ticks they covered
    compiles: int               # executables JAX built inside the window
    ctrl_s: Optional[float]     # host seconds inside the controller entries
    ticks_to_finish: Optional[int]   # of the window's first execution
    convergence_tick: Optional[int]  # of the window's first execution
    trace: Optional[dict]       # bench.trace reduction of the device stretch


def peak_bytes() -> Optional[int]:
    """Peak device memory of the fullest chip, where the backend says."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def device_record(peak) -> dict:
    import jax
    dev = jax.devices()[0]
    return dict(platform=dev.platform, kind=dev.device_kind,
                count=len(jax.devices()), memory_peak_bytes=peak)


def print_result(result: dict, out=sys.stdout, err=sys.stderr) -> None:
    """The compared numbers as the last lines on stderr, the result as the
    last line on stdout."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
