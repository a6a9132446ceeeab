"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The traced stretch is given on the trace's clock (seconds from the start
of the profiler's session), or is the host span ``bench.traced`` that the
harness opens right after starting the profiler and closes before
stopping it.  Within it, for each chip:

* busy: the union of the intervals in which an operation ran on the chip
  (the ``XLA Ops`` line of its plane, or every line where there is none);
* idle share: 1 - busy / stretch;
* device ops: seconds per operation name, and the count of op events;
* idle gaps: each instant the chip is idle is put down to the innermost
  host span open on the harness's thread at that instant (the harness's
  own spans, the engine's jitted dispatches), or to "no host span".

Busy seconds, ops and gaps are averaged over the chips.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float, str]          # (start s, end s, name)

MARKER = "bench.traced"
#: host spans the harness records (see harness._Spans, harness._Window).
HARNESS_SPANS = ("bench.", "engine.", "ctrl.", "sink.")
NO_SPAN = "no host span"
TOP = 10


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, disjoint cover of the given intervals."""
    out: List[List[float]] = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi), n) for s, e, n in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The complement of a disjoint sorted cover within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def timeline(spans: Iterable[Interval]) -> List[Interval]:
    """Disjoint segments naming the innermost open span at each instant.

    Spans of one thread nest; the innermost open span at an instant is the
    open one that started last (a span reaching past its parent is cut at
    the parent's end)."""
    out: List[Interval] = []
    stack: List[list] = []                  # [end, name], innermost last
    t = float("-inf")

    def emit(upto: float) -> None:
        nonlocal t
        if stack and upto > t:
            out.append((t, upto, stack[-1][1]))
        t = max(t, upto)

    for s, e, n in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            emit(stack[-1][0])
            stack.pop()
        emit(s)
        stack.append([min(e, stack[-1][0]) if stack else e, n])
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def attribute(gap_list: Sequence[Tuple[float, float]],
              spans: Sequence[Interval]) -> Dict[str, float]:
    """Seconds of the gaps under each innermost host span."""
    segs = timeline(spans)
    ends = [b for _, b, _ in segs]
    out: Dict[str, float] = defaultdict(float)
    for lo, hi in gap_list:
        covered = 0.0
        i = bisect.bisect_right(ends, lo)
        while i < len(segs) and segs[i][0] < hi:
            a, b, name = segs[i]
            part = min(b, hi) - max(a, lo)
            if part > 0:
                out[name] += part
                covered += part
            i += 1
        if hi - lo - covered > 0:
            out[NO_SPAN] += hi - lo - covered
    return dict(out)


def reduce(devices: Dict[str, List[Interval]], host: List[Interval],
           window: Tuple[float, float]) -> dict:
    """Busy and idle seconds, top device ops and top idle causes of the
    stretch ``window``, averaged over ``devices`` (chip -> op intervals)."""
    lo, hi = window
    n = max(len(devices), 1)
    busy_s, events = 0.0, 0
    ops: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    spans = _clip(host, lo, hi)
    for intervals in devices.values():
        clipped = _clip(intervals, lo, hi)
        events += len(clipped)
        cover = union((s, e) for s, e, _ in clipped)
        busy_s += sum(e - s for s, e in cover)
        for s, e, name in clipped:
            ops[name] += e - s
        for name, secs in attribute(gaps(cover, lo, hi), spans).items():
            idle[name] += secs
    window_s = hi - lo

    def top(d):
        return [[k, v / n] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    busy_s /= n
    return dict(busy_s=busy_s, window_s=window_s,
                idle_share=1.0 - busy_s / window_s if window_s > 0 else None,
                chips=len(devices), op_events=events / n,
                device_ops=top(ops), idle_gaps=top(idle))


# --------------------------------------------------------------------- #
# Reading the profiler's XPlane file                                     #
# --------------------------------------------------------------------- #
def _events(line) -> List[Interval]:
    return [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
             ev.name) for ev in line.events]


def op_names(ops: List[Interval], modules: List[Interval]) -> List[Interval]:
    """Ops named ``<module>:<op>``: the HLO instruction's name (the text
    before `` = ``) under the program running at the op's start."""
    modules = sorted(modules)
    starts = [s for s, _, _ in modules]
    out = []
    for s, e, name in ops:
        op = name.split(" = ", 1)[0].lstrip("%")
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < modules[i][1]:
            op = modules[i][2] + ":" + op
        out.append((s, e, op))
    return out


def read_xspace(path: str, chips):
    """(chip -> op intervals, harness-thread host spans, stretch or None)
    from one ``.xplane.pb`` file; ``chips`` names the device planes of the
    chips the run used (``/device:TPU:0``, ...)."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, List[Interval]] = {}
    host: List[Interval] = []
    window: Optional[Tuple[float, float]] = None
    for plane in pd.planes:
        if plane.name in chips:
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" in lines:
                devices[plane.name] = op_names(
                    _events(lines["XLA Ops"]),
                    _events(lines["XLA Modules"])
                    if "XLA Modules" in lines else [])
            else:
                devices[plane.name] = [iv for ln in lines.values()
                                       for iv in _events(ln)]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                evs = _events(ln)
                if any(n.startswith(HARNESS_SPANS) for _, _, n in evs):
                    host.extend(evs)
                for s, e, n in evs:
                    if n == MARKER:
                        window = (s, e)
    return devices, host, window


def reduce_file(log_dir: str, chips,
                stretch: Optional[Tuple[float, float]] = None) -> Optional[dict]:
    """Reduce the newest trace under a ``jax.profiler.start_trace`` dir
    over ``stretch``, or over the marked one; None where it holds no plane
    of ``chips`` or no stretch."""
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return None
    devices, host, window = read_xspace(files[-1], chips)
    window = stretch or window
    if not devices or window is None:
        return None
    host = [iv for iv in host if iv[2] != MARKER]
    return reduce(devices, host, window)
