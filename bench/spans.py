"""The engine's own spans and counters over whole executions of a cell, on
the chip, beside the harness's outside timing.

    python3 bench/spans.py --workload <cell> --seed <n> [--pairs 2]

After one warm-up execution (every shape compiled), the cell's graph runs
whole executions alternately with the program's recorder off and on
(off, on, on, off, ...).  Every execution is timed from outside as a
benchmark run times it (the wall of each super-tick, and the harness's
host spans round the controller entries), and checked against the plain
reference; the recorder's cost is the change of the mean super-tick wall.
From each recorded execution come the shares of its wall spent in the
program's spans (readbacks, state syncs, the controller hop's parts, END),
its readbacks per super-tick and ring fill.

A last execution is traced as a benchmark run traces one: a device
stretch with the host tracer off, then a host stretch with it on.  The
recorder's spans, put on the trace's clock as ``perf_counter - session
start``, explain each idle instant of the device stretch at the host's
true pace (the innermost open span); in the host stretch, where the
same spans also land as ``TraceAnnotation`` events, the mapping's error
is the median distance between the two starts of each super-tick.

One JSON line per execution, then the summary.  The benchmark's own runs
do not run this.
"""
from __future__ import annotations

import argparse
import bisect
import contextlib
import gc
import glob
import json
import os
import shutil
import statistics
import sys
import tempfile
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

#: the controller's entries (outermost ones counted once).
CTRL_ENTRIES = ("ctrl.step", "ctrl.super_tick", "ctrl.drain")
TOP = 10


# --------------------------------------------------------------------- #
# Reductions of recorded spans (``repro.obs.Span``)                      #
# --------------------------------------------------------------------- #
def seconds_in(spans: Sequence, names) -> float:
    """Seconds inside spans named in ``names``; a span nested in another of
    ``names`` is counted once, with its outermost."""
    names = set(names)
    total = 0.0
    for s in spans:
        if s.name not in names:
            continue
        p = s.parent
        while p >= 0 and spans[p].name not in names:
            p = spans[p].parent
        if p < 0:
            total += s.end - s.start
    return total


def readings(spans: Sequence, counters: Dict[str, int], wall: float) -> dict:
    """The program's numbers over one recorded stretch of ``wall`` host
    seconds (shares in percent of it)."""
    ticks = counters.get("engine.super_ticks", 0)
    slots = counters.get("device.ring_slots", 0)

    def share(*names):
        return 100.0 * seconds_in(spans, names) / wall if wall > 0 else None

    steps = [s.end - s.start for s in spans if s.name == "engine.super_tick"]
    return dict(
        readbacks_per_supertick=(counters.get("device.readbacks", 0) / ticks
                                 if ticks else None),
        readbacks_by_site=_by_site(spans, ticks, lambda s: 1),
        ring_fill_pct=(100.0 * counters.get("device.ring_live", 0) / slots
                       if slots else None),
        readback_wait_share=share("device.readback"),
        state_sync_share=share("device.sync_host", "device.reload"),
        ctrl_span_share=share(*CTRL_ENTRIES),
        ctrl_cpu_step_share=share("ctrl.cpu_step"),
        ctrl_replay_share=share("ctrl.replay"),
        end_phase_s=seconds_in(spans, ("engine.end",)),
        supertick_span_ms=(1000.0 * statistics.fmean(steps)
                           if steps else None))


def _by_site(spans: Sequence, per: int, weight) -> Dict[str, float]:
    """Readback spans' ``weight`` summed per ``site``, over ``per``."""
    out: Dict[str, float] = defaultdict(float)
    for s in spans:
        if s.name == "device.readback":
            out[s.args.get("site")] += weight(s)
    return {k: v / per for k, v in sorted(out.items())} if per else {}


def phase_split(spans: Sequence, first_drain: int) -> dict:
    """Mean milliseconds per super-tick inside each span name (outermost
    counted once per name), and in readbacks per site, for the super-ticks
    before ``first_drain`` (the sources still emit) and from it on."""
    tops = [i for i, s in enumerate(spans)
            if s.name == "engine.super_tick" and s.parent < 0]
    out = {}
    for phase, lo, hi in (("source", 0, first_drain),
                          ("drain", first_drain, len(tops))):
        chosen = tops[lo:hi]
        if not chosen:
            continue
        inside = set(chosen)
        per: Dict[str, float] = defaultdict(float)
        mine = []
        for i, s in enumerate(spans):
            root, p = i, s.parent
            while p >= 0:
                root, p = p, spans[p].parent
            if root not in inside:
                continue
            mine.append(s)
            q = s.parent
            while q >= 0 and spans[q].name != s.name:
                q = spans[q].parent
            if q < 0:
                per[s.name] += s.end - s.start
        out[phase] = dict(
            super_ticks=len(chosen),
            ms={k: 1000.0 * v / len(chosen) for k, v in sorted(per.items())},
            readback_ms_by_site=_by_site(
                mine, len(chosen), lambda s: 1000.0 * (s.end - s.start)))
    return out


def on_trace_clock(spans: Sequence, session: float,
                   offset: float = 0.0) -> List[Tuple[float, float, str]]:
    """Recorded spans as (start, end, name) on a profiler trace's clock:
    ``perf_counter - session``, where ``session`` was read right before
    ``start_trace``, plus a measured ``offset``."""
    return [(s.start - session + offset, s.end - session + offset, s.name)
            for s in spans]


def program_idle(devices: Dict[str, List[tuple]], spans: List[tuple],
                 stretch: Tuple[float, float]) -> Optional[dict]:
    """Idle seconds of the chips over ``stretch`` (trace clock), each idle
    instant put down to the innermost program span open then; averaged
    over the chips.  ``spans`` are on the trace's clock already."""
    from bench import trace
    if not devices:
        return None
    lo, hi = stretch
    inside = [(max(s, lo), min(e, hi), n) for s, e, n in spans
              if min(e, hi) > max(s, lo)]
    idle: Dict[str, float] = defaultdict(float)
    idle_s = 0.0
    for intervals in devices.values():
        cover = trace.union((max(s, lo), min(e, hi)) for s, e, _ in intervals
                            if min(e, hi) > max(s, lo))
        gap_list = trace.gaps(cover, lo, hi)
        idle_s += sum(b - a for a, b in gap_list)
        for name, secs in trace.attribute(gap_list, inside).items():
            idle[name] += secs
    n = len(devices)
    idle_s /= n
    gaps = sorted(((k, v / n) for k, v in idle.items()), key=lambda kv: -kv[1])
    return dict(stretch_s=hi - lo, idle_s=idle_s,
                idle_share=100.0 * idle_s / (hi - lo) if hi > lo else None,
                idle_in_readback_share=(
                    100.0 * idle.get("device.readback", 0.0) / n / idle_s
                    if idle_s > 0 else None),
                program_idle_gaps=[[k, v] for k, v in gaps[:TOP]])


def clock_error(spans: List[tuple], events: List[tuple],
                stretch: Tuple[float, float],
                name: Optional[str] = "engine.super_tick") -> Optional[dict]:
    """Median |start difference| between the recorded spans (on the
    trace's clock) that lie within the traced ``stretch`` and the trace's
    events of the same name, each span paired with the nearest event; of
    the spans named ``name``, or of every span (``name`` None).  None where
    either side has none."""
    by_name: Dict[str, List[float]] = defaultdict(list)
    for s, _, n in events:
        by_name[n].append(s)
    for starts in by_name.values():
        starts.sort()
    lo, hi = stretch
    diffs = []
    for s, e, n in spans:
        starts = by_name.get(n)
        if (not starts or (name is not None and n != name)
                or s < lo or e > hi):
            continue
        i = bisect.bisect_left(starts, s)
        near = [starts[j] for j in (i - 1, i) if 0 <= j < len(starts)]
        diffs.append(min(abs(s - t) for t in near))
    if not diffs:
        return None
    return dict(pairs=len(diffs), median_abs_dstart_s=statistics.median(diffs),
                max_abs_dstart_s=max(diffs))


# --------------------------------------------------------------------- #
# The run                                                                #
# --------------------------------------------------------------------- #
class _Stop(Exception):
    """Ends the traced execution once both stretches are taken."""


def _chips():
    import jax
    return {f"/device:{d.platform.upper()}:{d.id}"
            for d in jax.local_devices() if d.platform != "cpu"}


def _read_trace(log_dir: str):
    from bench import trace
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return {}, [], None
    return trace.read_xspace(files[-1], _chips())


def _execution(cell, recorded: bool, on_boundary=None):
    """One whole execution (or a traced part of one), timed from outside."""
    from bench import harness
    from repro import obs
    from repro.dataflow import resilience
    global_from = len(resilience.GLOBAL)
    outside = harness._Spans()
    eng, last_op, sink = cell.build()
    ex = harness.Execution(eng, last_op, sink, harness.now())
    window = harness._Window(float("inf"),
                             lambda name: contextlib.nullcontext(),
                             on_boundary)
    window.attach(ex)
    drain_from = []
    inner = eng.run_super_tick

    def run_super_tick(k):
        inner(k)
        if not drain_from and all(s.finished for s in eng.sources):
            drain_from.append(len(ex.steps))
    eng.run_super_tick = run_super_tick
    rec = None
    stopped = False
    with outside.installed():
        outside.recording = True
        with (obs.recording() if recorded
              else contextlib.nullcontext()) as rec:
            t0 = harness.now()
            try:
                eng.run(cell.max_ticks)
            except _Stop:
                stopped = True
            wall = harness.now() - t0
    out = dict(recorded=recorded, wall_s=wall, super_ticks=len(ex.steps),
               supertick_ms=1000.0 * wall / max(len(ex.steps), 1),
               ctrl_host_share=100.0 * outside.ctrl_s / wall,
               end_phase_s=None)
    if recorded:
        out.update(readings(rec.spans, rec.counters, wall))
        out["phases"] = phase_split(rec.spans, drain_from[0] if drain_from
                                    else len(ex.steps))
        out["spans"] = len(rec.spans)
    if not stopped:
        numbers = cell.numbers(cell.record(ex, global_from))
        out["correct"] = not cell.failed(numbers)
        out["series"] = list(sink.series)
    return out, rec


def _traced(cell, at: float, seconds: float, host_seconds: float) -> dict:
    """Trace a recorded execution as a benchmark run does: the device
    stretch from ``at`` seconds in, host tracer off, then the host
    stretch with its marker; stop the execution after it."""
    import jax

    from bench import harness, trace
    state = dict(dirs=[], session=[], on=[], off=[], marker=None)
    t_start = harness.now()

    def start(host: bool):
        d = tempfile.mkdtemp(prefix="bench-spans-")
        state["dirs"].append(d)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1 if host else 0
        state["session"].append(harness.now())
        jax.profiler.start_trace(d, profiler_options=options)
        state["on"].append(harness.now())
        if host:
            state["marker"] = (jax.profiler.TraceAnnotation(trace.MARKER),
                               harness.now())
            state["marker"][0].__enter__()

    def stop():
        state["off"].append(harness.now())
        if state["marker"] is not None and len(state["dirs"]) == 2:
            state["marker"][0].__exit__(None, None, None)
        jax.profiler.stop_trace()

    def on_boundary(wall):
        n = len(state["dirs"])
        if n == 0 and wall - t_start >= at:
            start(host=False)
        elif n == 1 and len(state["off"]) == 0 and \
                wall - state["on"][0] >= seconds:
            stop()
            start(host=True)
        elif n == 2 and len(state["off"]) == 1 and \
                wall - state["on"][1] >= host_seconds:
            stop()
            raise _Stop

    try:
        run, rec = _execution(cell, True, on_boundary)
        if len(state["off"]) < len(state["dirs"]):
            stop()
        result = dict(traced_super_ticks=run["super_ticks"])
        if len(state["dirs"]) < 2:
            return dict(result, error="the execution ended before both "
                                      "stretches were taken")
        spans = rec.spans
        devices, _, _ = _read_trace(state["dirs"][0])
        _, host, window = _read_trace(state["dirs"][1])
        # the host stretch: the same spans as TraceAnnotation events
        host_stretch = (state["on"][1] - state["session"][1],
                        state["off"][1] - state["session"][1])
        err = clock_error(on_trace_clock(spans, state["session"][1]), host,
                          host_stretch)
        offset = 0.0
        if window is not None:
            marker_wall = state["marker"][1] - state["session"][1]
            result["marker_offset_s"] = window[0] - marker_wall
        result["clock"] = err
        result["clock_all_spans"] = clock_error(
            on_trace_clock(spans, state["session"][1]), host, host_stretch,
            name=None)
        if (err is not None and err["median_abs_dstart_s"] > 1e-3
                and "marker_offset_s" in result):
            offset = result["marker_offset_s"]
            result["clock_with_offset"] = clock_error(
                on_trace_clock(spans, state["session"][1], offset), host,
                host_stretch)
        result["offset_applied_s"] = offset
        dev_stretch = (state["on"][0] - state["session"][0],
                       state["off"][0] - state["session"][0])
        result["device"] = program_idle(
            devices, on_trace_clock(spans, state["session"][0], offset),
            dev_stretch)
        return result
    finally:
        for d in state["dirs"]:
            shutil.rmtree(d, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--pairs", type=int, default=2)
    ap.add_argument("--trace-seconds", type=float, default=4.0)
    ap.add_argument("--host-seconds", type=float, default=2.0)
    ap.add_argument("--executor", choices=("jit",),
                    help="force the jit plane off a TPU (a rehearsal)")
    ap.add_argument("--overrides", type=json.loads, default=None,
                    help="resize the cell (JSON, as harness.Cell takes)")
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    if jax.devices()[0].platform != "tpu" and args.executor is None:
        print("spans: needs a TPU (or --executor jit)", file=sys.stderr)
        return 2
    from bench import harness
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    cell = harness.Cell(args.workload, args.seed, executor=args.executor,
                        overrides=args.overrides)
    eng, _, _ = cell.build()
    eng.run(cell.max_ticks)                     # warm-up: every shape
    del eng
    gc.collect()

    order = [bool(i % 4 in (1, 2)) for i in range(2 * args.pairs)]
    runs = []
    for recorded in order:
        run, _ = _execution(cell, recorded)
        runs.append(run)
        line = {k: v for k, v in run.items() if k != "series"}
        print(json.dumps(line), flush=True)
        gc.collect()
    series_equal = all(
        len(r["series"]) == len(runs[0]["series"])
        and all(ta == tb and (ca == cb).all()
                for (ta, ca), (tb, cb) in zip(r["series"], runs[0]["series"]))
        for r in runs)
    off = [r["supertick_ms"] for r in runs if not r["recorded"]]
    on = [r for r in runs if r["recorded"]]
    traced = _traced(cell, 0.25 * statistics.fmean(r["wall_s"] for r in on),
                     args.trace_seconds, args.host_seconds)
    summary = dict(
        workload=args.workload, seed=args.seed,
        platform=jax.devices()[0].platform,
        correct=all(r["correct"] for r in runs), series_equal=series_equal,
        supertick_ms_off=off,
        supertick_ms_on=[r["supertick_ms"] for r in on],
        recorder_cost_pct=100.0 * (statistics.fmean(
            r["supertick_ms"] for r in on) / statistics.fmean(off) - 1.0),
        ctrl_span_minus_host_share_pp=[
            r["ctrl_span_share"] - r["ctrl_host_share"] for r in on],
        supertick_span_vs_wall_pct=[
            100.0 * (r["supertick_span_ms"] / r["supertick_ms"] - 1.0)
            for r in on],
        traced=traced)
    print(json.dumps(summary), flush=True)
    return 0 if summary["correct"] and series_equal else 1


if __name__ == "__main__":
    sys.exit(main())
