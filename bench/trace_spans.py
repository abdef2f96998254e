"""The program's own spans and scopes in a profiler trace, and its span
table over a window.

``trace_reduce`` labels device idle time with the harness's ``bench.*``
spans and sums device time by module.  This module reads what the
program itself records:

- ``load`` reads an ``.xplane.pb`` into what ``trace_reduce.load``
  returns, plus ``op_scopes``: the ``named_scope`` path of each op (its
  ``tf_op`` stat), keyed by program id and op name.
- ``idle_by_span`` puts every idle second of the window down to the
  innermost ``engine.*`` host span open at that moment (``none`` where
  no such span is open), in one sweep over sorted gaps and spans.
- ``scope_s`` sums the device time of ops inside ``decode_step``
  modules by scope, over the scopes a configuration lists (its file's
  ``scopes``: for ``tinymistral_248m`` ``sail_mm``, the LUT matmuls, and
  ``attn``, the KV write, gather, dequant and attention), and ``other``
  for an op in none of them; an op that holds others (a ``while``)
  counts only its own time.
- ``snapshot``/``delta`` read ``Engine.spans`` and the queue counters
  before and after a window; ``numeric_leaves``/``leaves_delta`` do the
  same for every number ``Engine.stats()`` reports.
"""
from __future__ import annotations

import numbers
import re
from typing import Dict, List, Optional, Sequence, Tuple

from bench.trace_reduce import Event, clip, merge, read_planes

PREFIX = "engine."
MODULE = "decode_step"
SCOPE_STAT = "tf_op"              # an op's name-stack path


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int = 0, end: Optional[int] = None):
    """(field number, value) of one protobuf message; a length-delimited
    value is its ``(start, end)`` in ``buf``."""
    end = len(buf) if end is None else end
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            v, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unknown protobuf wire type {wire}")
        yield key >> 3, v


def _text(buf: bytes, span: Tuple[int, int]) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def op_scopes(buf: bytes) -> Dict[str, List[list]]:
    """``[program_id, op name, tf_op]`` for each op of each device plane
    of a serialized XSpace that has such ops.  ``ProfileData`` shows an
    event's own stats only; ``tf_op`` sits in the op's event metadata (XPlane field 4:
    XEventMetadata name 2, stats 5; XStat metadata_id 1, uint64 3,
    int64 4, str 5, ref 7; XStatMetadata name 2 in field 5)."""
    out: Dict[str, List[list]] = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pf, v in _fields(buf, *plane):
            if pf == 2:
                name = _text(buf, v)
            elif pf in (4, 5):          # map entries: key 1, value 2
                val = next((x for k, x in _fields(buf, *v) if k == 2), None)
                if val is None:
                    continue
                if pf == 4:
                    metas.append(val)
                else:
                    got = dict(_fields(buf, *val))
                    if 1 in got and 2 in got:
                        stat_names[got[1]] = _text(buf, got[2])
        if not name.startswith("/device:") or "CPU" in name:
            continue
        rows = []
        for m in metas:
            op, stats = "", {}
            for k, v in _fields(buf, *m):
                if k == 2:
                    op = _text(buf, v)
                elif k == 5:
                    st = dict(_fields(buf, *v))
                    key = stat_names.get(st.get(1))
                    if 5 in st:
                        stats[key] = _text(buf, st[5])
                    elif 7 in st:
                        stats[key] = stat_names.get(st[7], "")
                    else:
                        stats[key] = st.get(3, st.get(4))
            if SCOPE_STAT in stats and "program_id" in stats:
                rows.append([stats["program_id"], op, stats[SCOPE_STAT]])
        if rows:
            out[name] = rows
    return out


def load(path: str) -> Dict:
    """``trace_reduce.load`` plus each device plane's ``op_scopes``."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        buf = f.read()
    device, host = read_planes(ProfileData.from_serialized_xspace(buf).planes)
    return {"device": device, "host": host, "op_scopes": op_scopes(buf)}


def idle_gaps(trace: Dict, t0: float, t1: float) -> List[Tuple[float, float]]:
    """Stretches of ``[t0, t1]`` with no device event, as
    ``trace_reduce.reduce`` finds them."""
    union: List[Tuple[float, float]] = []
    for lines in trace["device"].values():
        events = lines["ops"] or lines["modules"]
        union.extend(clip([(s, s + d) for _, s, d in events], t0, t1))
    gaps, prev = [], t0
    for a, b in merge(union) + [(t1, t1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    return gaps


def innermost(host: List[Event]) -> List[Tuple[float, float, str]]:
    """Disjoint pieces of time, each with the innermost ``engine.*``
    span open over it (spans of one thread nest)."""
    spans = sorted(((s, s + d, n) for n, s, d in host
                    if n.startswith(PREFIX)), key=lambda x: (x[0], -x[1]))
    pieces: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []      # (end, name), innermost last
    t = float("-inf")

    def until(when: float) -> None:
        nonlocal t
        while stack and stack[-1][0] <= when:
            end, name = stack.pop()
            if end > t:
                pieces.append((t, end, name))
                t = end
        if stack and when > t:
            pieces.append((t, when, stack[-1][1]))
        t = max(t, when)

    for a, b, name in spans:
        until(a)
        stack.append((b, name))
    until(float("inf"))
    return pieces


def idle_by_span(trace: Dict, t0: float, t1: float) -> Dict[str, float]:
    """Idle seconds of the window by the innermost open ``engine.*``
    span; what no such span covers is ``none``."""
    gaps = idle_gaps(trace, t0, t1)
    pieces = innermost(trace["host"])
    out: Dict[str, float] = {}
    i = j = 0
    while i < len(gaps) and j < len(pieces):
        a = max(gaps[i][0], pieces[j][0])
        b = min(gaps[i][1], pieces[j][1])
        if b > a:
            out[pieces[j][2]] = out.get(pieces[j][2], 0.0) + b - a
        if gaps[i][1] < pieces[j][1]:
            i += 1
        else:
            j += 1
    out["none"] = sum(b - a for a, b in gaps) - sum(out.values())
    return {k: v / 1e9 for k, v in out.items()}


def scope_of(path: str, scopes: Sequence[str]) -> str:
    """The first of ``scopes`` that names a part of the op's scope path,
    or ``other``."""
    parts = path.split("/")
    return next((s for s in scopes if s in parts), "other")


def self_times(events: List[Event]) -> List[float]:
    """Each event's duration less that of the events nested in it (a
    ``while`` op holds its body's ops) on one line."""
    out = [float(d) for _, _, d in events]
    stack: List[Tuple[float, int]] = []      # (end, index)
    for i in sorted(range(len(events)),
                    key=lambda i: (events[i][1], -events[i][2])):
        _, s, d = events[i]
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            end, parent = stack[-1]
            out[parent] -= min(d, end - s)
        stack.append((s + d, i))
    return out


def _program_id(module: str) -> Optional[int]:
    m = re.search(r"\((-?\d+)\)$", module)
    return int(m.group(1)) % 2 ** 64 if m else None


def scope_s(trace: Dict, t0: float, t1: float,
            scopes: Sequence[str]) -> Dict[str, float]:
    """Device seconds of the ``XLA Ops`` line inside ``decode_step``
    modules that overlap the window, by the first of ``scopes`` in each
    op's path (``other`` where none is), each op counted for its own
    time (less the ops nested in it)."""
    out: Dict[str, float] = {}
    for plane, lines in trace["device"].items():
        paths = {(pid % 2 ** 64, op): path for pid, op, path
                 in trace.get("op_scopes", {}).get(plane, [])}
        mods = sorted((s, s + d, _program_id(n))
                      for n, s, d in lines["modules"]
                      if MODULE in n and s < t1 and s + d > t0)
        ops = lines.get("sync_ops", [])
        j = 0
        for (name, s, _), own in sorted(zip(ops, self_times(ops)),
                                        key=lambda o: o[0][1]):
            while j < len(mods) and mods[j][1] <= s:
                j += 1
            if j < len(mods) and mods[j][0] <= s:
                key = scope_of(paths.get((mods[j][2], name), ""), scopes)
                out[key] = out.get(key, 0.0) + own / 1e9
    return out


def reduce(trace: Dict, t0: float, t1: float,
           scopes: Sequence[str]) -> Dict:
    """The two numbers of the window this module adds to
    ``trace_reduce.reduce``'s."""
    return {"idle_by_span": idle_by_span(trace, t0, t1),
            "scope_s": scope_s(trace, t0, t1, scopes)}


def snapshot(eng) -> Optional[Dict]:
    """The engine's span table and queue counters now; None for an
    engine that keeps no span table."""
    spans = getattr(eng, "spans", None)
    if spans is None:
        return None
    from repro.serving import trace
    return {"spans": {k: list(v) for k, v in spans.items()},
            "outside_jit": list(trace.OUTSIDE),
            "queue_wait_s": eng.queue_wait_seconds,
            "queue_admissions": eng.queue_admissions}


def delta(before: Optional[Dict], after: Optional[Dict]) -> Optional[Dict]:
    """What a window added: per span ``{count, s, self_s, jit_s,
    jit_events}`` (JIT outside every span under ``none``), and the
    queue counters."""
    if before is None or after is None:
        return None
    spans = {}
    for name, row in after["spans"].items():
        was = before["spans"].get(name, [0, 0.0, 0.0, 0.0, 0])
        d = [x - y for x, y in zip(row, was)]
        if d[0] or d[4]:
            spans[name] = dict(zip(("count", "s", "self_s", "jit_s",
                                    "jit_events"), d))
    jit_s, jit_n = (x - y for x, y in zip(after["outside_jit"],
                                          before["outside_jit"]))
    if jit_n:
        spans["none"] = {"count": 0, "s": 0.0, "self_s": 0.0,
                         "jit_s": jit_s, "jit_events": jit_n}
    return {"spans": spans,
            "queue_wait_s": after["queue_wait_s"] - before["queue_wait_s"],
            "queue_admissions": (after["queue_admissions"]
                                 - before["queue_admissions"])}


def numeric_leaves(tree: Dict, prefix: str = "") -> Dict[str, float]:
    """Every real-number leaf of nested dicts (NumPy's too), keyed by its
    path joined with dots (``block_pool.free_blocks``); bools and None
    are left out."""
    out: Dict[str, float] = {}
    for k, v in tree.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(numeric_leaves(v, key + "."))
        elif isinstance(v, numbers.Real) and not isinstance(v, bool):
            out[key] = v
    return out


def leaves_delta(before: Dict[str, float],
                 after: Dict[str, float]) -> Dict[str, float]:
    """What a window added to each numeric leaf; a leaf that appeared in
    it counts from 0."""
    return {k: v - before.get(k, 0) for k, v in after.items()}
