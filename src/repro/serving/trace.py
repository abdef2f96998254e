"""Host spans and JIT attribution for the serving loop.

``SpanTable.span(name, **attrs)`` opens a ``jax.profiler.TraceAnnotation``
(the span lands in a profiler trace, on the device planes' clock, when
one is being recorded) and, on exit, adds to its table row::

    name -> [count, seconds, self_seconds, jit_seconds, jit_events]

``self_seconds`` is ``seconds`` less the spans opened inside it.  With
no profiler running a span costs one ``TraceAnnotation`` and two clock
reads; it never waits on the device.

JIT attribution: one process-wide ``jax.monitoring`` listener (JAX's
listeners cannot be removed) takes JAX's trace, lower and compile
durations.  Each event is charged to the innermost span open in the
thread that reported it, or to :data:`OUTSIDE` when none is.  Only the
part of an event's interval not already counted is charged, so a jit
traced inside another's trace is not counted twice.
"""
from __future__ import annotations

import bisect
import threading
import time
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

JIT_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

# JIT seconds and events reported while no span was open
OUTSIDE: List = [0.0, 0]

clock = time.perf_counter
_local = threading.local()      # .stack: the thread's open spans
_covered: List[Tuple[float, float]] = []    # merged JIT intervals, sorted
_KEEP = 256                     # intervals kept for the overlap test
_lock = threading.Lock()
_listening = False


def _stack() -> List["Span"]:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


def _uncovered(a: float, b: float) -> float:
    """Seconds of ``[a, b]`` not yet in ``_covered``; adds the interval."""
    i = bisect.bisect_left(_covered, (a, a))
    if i and _covered[i - 1][1] >= a:
        i -= 1
    j, lo, hi, seen = i, a, b, 0.0
    while j < len(_covered) and _covered[j][0] <= b:
        s, e = _covered[j]
        seen += min(e, b) - max(s, a)
        lo, hi = min(lo, s), max(hi, e)
        j += 1
    _covered[i:j] = [(lo, hi)]
    del _covered[:-_KEEP]
    return (b - a) - seen


def _on_event(event: str, duration: float, **kwargs) -> None:
    if event not in JIT_EVENTS:
        return
    end = clock()
    with _lock:
        new = _uncovered(end - duration, end)
        stack = _stack()
        if stack:
            stack[-1].row[3] += new
            stack[-1].row[4] += 1
        else:
            OUTSIDE[0] += new
            OUTSIDE[1] += 1


def _listen() -> None:
    global _listening
    if not _listening:
        import jax
        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _listening = True


class Span:
    """One open span; ``seconds`` is set when it closes."""
    __slots__ = ("row", "_ann", "_t0", "_child", "seconds")

    def __init__(self, row: List, name: str, attrs: Dict):
        self.row = row
        self._ann = TraceAnnotation(name, **attrs)
        self._child = 0.0
        self.seconds: Optional[float] = None

    def __enter__(self) -> "Span":
        self._ann.__enter__()
        _stack().append(self)
        self._t0 = clock()
        return self

    def __exit__(self, *exc) -> None:
        dt = clock() - self._t0
        stack = _stack()
        stack.pop()
        if stack:
            stack[-1]._child += dt
        row = self.row
        row[0] += 1
        row[1] += dt
        row[2] += dt - self._child
        self.seconds = dt
        self._ann.__exit__(*exc)


class SpanTable(dict):
    """name -> [count, seconds, self_seconds, jit_seconds, jit_events]."""

    def __init__(self):
        super().__init__()
        _listen()

    def span(self, name: str, **attrs) -> Span:
        row = self.get(name)
        if row is None:
            row = self[name] = [0, 0.0, 0.0, 0.0, 0]
        return Span(row, name, attrs)
