"""Spans and counters inside the engine.

Three parts, one process-wide registry:

* :func:`count` adds to a plain integer counter.  Counters are always on;
  a site pays one dict update.
* :func:`span` times a named stretch of host work.  While no recording is
  active it returns one shared no-op context and records nothing.  Inside
  :func:`recording` it appends ``(name, start, end, parent, args)`` to the
  recorder, on ``time.perf_counter``'s clock, and opens a
  ``jax.profiler.TraceAnnotation`` of the same name, so the span also
  lands in any profiler trace taken meanwhile.  :func:`spanned` puts a
  whole function inside one.
* :func:`recording` turns spans on for the duration of a ``with`` block
  and yields the :class:`Recorder`: its spans, and the counters' growth
  over the block once it ends.

Spans stay in memory; the caller reads them when the recording ends.  The
engine is single-threaded, so spans of one recording nest, and a span's
``parent`` is the index of the span open around it (-1 at the top).
"""
from __future__ import annotations

import contextlib
import functools
import time
from typing import Dict, Iterator, List, NamedTuple, Optional

__all__ = ["Span", "Recorder", "count", "counters", "recording", "span",
           "spanned"]

now = time.perf_counter

_COUNTS: Dict[str, int] = {}
_active: Optional["Recorder"] = None


class Span(NamedTuple):
    name: str
    start: float
    end: float
    parent: int             # index of the enclosing span, -1 at the top
    args: dict


class Recorder:
    """What one :func:`recording` collected."""

    def __init__(self):
        self.spans: List[Span] = []
        #: counter growth over the recording (set when it ends).
        self.counters: Dict[str, int] = {}
        self._open: List[list] = []         # [name, start, end, parent, args]
        self._stack: List[int] = []
        self._counts0 = dict(_COUNTS)
        import jax
        self._annotation = jax.profiler.TraceAnnotation

    def _close(self) -> None:
        end = now()
        self.spans = [Span(n, s, end if e is None else e, p, a)
                      for n, s, e, p, a in self._open]
        self._open = []
        self.counters = {k: v - self._counts0.get(k, 0)
                         for k, v in _COUNTS.items()
                         if v != self._counts0.get(k, 0)}


class _Span:
    __slots__ = ("rec", "name", "args", "idx", "ann")

    def __init__(self, rec: Recorder, name: str, args: dict):
        self.rec, self.name, self.args = rec, name, args

    def __enter__(self):
        rec = self.rec
        self.ann = rec._annotation(self.name)
        self.ann.__enter__()
        self.idx = len(rec._open)
        rec._open.append([self.name, now(), None,
                          rec._stack[-1] if rec._stack else -1, self.args])
        rec._stack.append(self.idx)
        return self

    def __exit__(self, *exc):
        rec = self.rec
        rec._open[self.idx][2] = now()
        rec._stack.pop()
        self.ann.__exit__(*exc)
        return False


_NOOP = contextlib.nullcontext()


def span(name: str, **args):
    """A context timing ``name`` while a recording is active; the shared
    no-op otherwise."""
    rec = _active
    if rec is None:
        return _NOOP
    return _Span(rec, name, args)


def spanned(name: str):
    """Decorate a function to run inside ``span(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def count(name: str, n: int = 1) -> None:
    _COUNTS[name] = _COUNTS.get(name, 0) + n


def counters() -> Dict[str, int]:
    """Every counter's total since the process started."""
    return dict(_COUNTS)


@contextlib.contextmanager
def recording() -> Iterator[Recorder]:
    """Record spans, and the counters' growth, over the ``with`` block."""
    global _active
    if _active is not None:
        raise RuntimeError("a recording is already active")
    rec = Recorder()
    _active = rec
    try:
        yield rec
    finally:
        _active = None
        rec._close()
