"""Reduce a profiler trace to device busy time, module time and idle gaps.

``load`` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData`` into
plain lists; ``reduce`` turns those into numbers, so that it can be
checked on a small trace written by hand.

- Device events are the ``XLA Ops`` and ``Async XLA Ops`` lines of each
  ``/device:`` plane (the ``XLA Modules`` line where a plane has
  neither): an asynchronous copy keeps the chip's memory system busy
  while no op of the other line runs.  Busy time is the union of their
  intervals inside the window, averaged over the planes that hold an op
  or a module event: a TPU trace also carries planes that run nothing,
  such as ``/device:CUSTOM:Megascale Trace``, and ``load`` drops them.
- Module time is the summed duration of ``XLA Modules`` events by name,
  with the trailing ``(<id>)`` of a program's name removed.
- An idle gap is a stretch of the window with no device event.  Each is
  labelled by the innermost ``bench.*`` span the host had open at its
  middle, and the innermost other host event there.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]          # name, start_ns, duration_ns

OPS_LINES = ("XLA Ops", "Async XLA Ops")
MODULES_LINE = "XLA Modules"
SYNC_LINE = "XLA Ops"             # ops that run one at a time (they nest)


def runs_ops(lines: Dict[str, List[Event]]) -> bool:
    """Whether a device plane holds an op or a module event."""
    return bool(lines["ops"] or lines["modules"])


def read_planes(planes) -> Tuple[Dict[str, Dict[str, List[Event]]],
                                 List[Event]]:
    """Device planes that run ops, with their ``ops``, ``modules`` and
    ``sync_ops`` (the ``XLA Ops`` line alone), and every host event, of
    ``ProfileData`` planes."""
    device: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {ln.name: [(e.name, e.start_ns, e.duration_ns)
                               for e in ln.events] for ln in plane.lines}
            got = {"ops": [e for n in OPS_LINES for e in lines.get(n, [])],
                   "modules": lines.get(MODULES_LINE, []),
                   "sync_ops": lines.get(SYNC_LINE, [])}
            if runs_ops(got):
                device[plane.name] = got
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns)
                            for e in ln.events)
    return device, host


def load(path: str) -> Dict:
    """Device planes that run ops, and host events, of one
    ``.xplane.pb``."""
    from jax.profiler import ProfileData
    device, host = read_planes(ProfileData.from_file(path).planes)
    return {"device": device, "host": host}


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(intervals, t0: float, t1: float):
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if b > t0 and a < t1]


def module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def window(host: List[Event], span: str) -> Optional[Tuple[float, float]]:
    """Start and end of the host span ``span`` (the longest, if several)."""
    got = [(d, s) for n, s, d in host if n == span]
    if not got:
        return None
    d, s = max(got)
    return s, s + d


def _innermost(host: List[Event], t: float, bench: bool) -> Optional[str]:
    best = None
    for n, s, d in host:
        if s <= t <= s + d and n.startswith("bench.") == bench:
            if best is None or d < best[1]:
                best = (n, d)
    return best[0] if best else None


def reduce(trace: Dict, t0: float, t1: float, top: int = 10) -> Dict:
    """Numbers of the window ``[t0, t1]`` (nanoseconds, trace clock)."""
    planes = {k: v for k, v in trace["device"].items() if runs_ops(v)}
    if not planes:
        raise ValueError("trace holds no device plane that runs ops")
    busy_ns, module_ns, op_ns = [], {}, {}
    union_all: List[Tuple[float, float]] = []
    for lines in planes.values():
        events = lines["ops"] or lines["modules"]
        spans = merge(clip([(s, s + d) for _, s, d in events], t0, t1))
        busy_ns.append(sum(b - a for a, b in spans))
        union_all.extend(spans)
        for n, s, d in lines["modules"]:
            if s < t1 and s + d > t0:
                key = module_name(n)
                module_ns[key] = module_ns.get(key, 0.0) + d
        for n, s, d in lines["ops"]:
            if s < t1 and s + d > t0:
                op_ns[n] = op_ns.get(n, 0.0) + d
    busy = merge(union_all)
    gaps, prev = [], t0
    for a, b in busy + [(t1, t1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    labelled = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        span = _innermost(trace["host"], mid, True) or "none"
        inner = _innermost(trace["host"], mid, False)
        labelled.append([span + (f" > {inner}" if inner else ""),
                         (b - a) / 1e9])
    by_time = lambda d: sorted(([k, v / 1e9] for k, v in d.items()),
                               key=lambda kv: -kv[1])
    return {
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "window_s": (t1 - t0) / 1e9,
        "modules": {k: v / 1e9 for k, v in module_ns.items()},
        "device_ops": by_time(module_ns or op_ns)[:top],
        "idle_gaps": labelled,
    }
