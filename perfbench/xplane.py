"""From a profiler trace (``.xplane.pb``) to device time: per-device op
intervals, busy and idle time, time in convolutions and in collectives,
the part of the collectives that nothing else hides, and a breakdown.

Device planes are ``/device:TPU:<n>``.  Their ``XLA Ops`` line holds one
event per executed HLO op, named by the op's HLO text
(``%fusion.98 = f32[...] fusion(...), calls=...``); the ``Async XLA Ops``
line holds the in-flight span of each asynchronous op.  What an op does
comes from the compiled program's HLO (``hlo.kinds``), looked up by the
instruction name; collectives are also known by their opcode.  Event times
count from the trace's ``profile_start_time`` (wall-clock ns), so the
benchmark's own host spans, taken with ``time.time_ns()``, are put on the
same clock.  The traced window is the ``window`` span.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Tuple

import hlo

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
WINDOW = "window"
NAME = re.compile(r"^%?([^\s=]+)")


class Op(NamedTuple):
    start: int            # ns
    end: int              # ns
    name: str             # the HLO instruction's name
    kind: str             # conv, matmul, collective or other


class Trace(NamedTuple):
    ops: Dict[int, List[Op]]                 # device id -> ops by start
    in_flight: Dict[int, List[Op]]           # device id -> async spans
    spans: List[Tuple[int, int, str]]        # host spans, trace clock


def _op(e, kinds: Dict[str, str]) -> Op:
    text = e.name
    name = NAME.match(text).group(1)
    rhs = text.split(" = ", 1)[-1]
    opcode = hlo.OPCODE.search(" " + rhs)
    kind = ("collective" if opcode and hlo.COLLECTIVE.match(opcode.group(1))
            else kinds.get(name, "other"))
    return Op(int(e.start_ns), int(e.end_ns), name, kind)


def load(path: Optional[str] = None, data: Optional[bytes] = None,
         kinds: Optional[Dict[str, str]] = None,
         host_spans=()) -> Trace:
    """Read a trace.  ``kinds`` is ``hlo.kinds`` of the traced program;
    ``host_spans`` are ``(name, start, end)`` in wall-clock ns."""
    from jax.profiler import ProfileData
    pd = (ProfileData.from_file(path) if data is None
          else ProfileData.from_serialized_xspace(data))
    kinds = kinds or {}
    ops: Dict[int, List[Op]] = {}
    in_flight: Dict[int, List[Op]] = {}
    start = 0
    for plane in pd.planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats)["profile_start_time"]
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name in (OPS_LINE, ASYNC_LINE):
                dest = ops if line.name == OPS_LINE else in_flight
                dev = dest.setdefault(int(m.group(1)), [])
                dev.extend(_op(e, kinds) for e in line.events)
    for dev in list(ops.values()) + list(in_flight.values()):
        dev.sort()
    spans = [(s - start, e - start, name) for name, s, e in host_spans]
    return Trace(ops, in_flight, spans)


def union(intervals) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _clip(ops, lo: int, hi: int):
    return [(max(o.start, lo), min(o.end, hi)) for o in ops
            if o.end > lo and o.start < hi]


def _minus(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Length of the union ``a`` not covered by the union ``b``."""
    covered, j = 0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            covered += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return _length(a) - covered


def window(trace: Trace) -> Tuple[int, int]:
    spans = [(s, e) for s, e, n in trace.spans if n == WINDOW]
    if not spans:
        raise ValueError("no window span was given")
    return spans[-1]


def _host_span_at(trace: Trace, lo: int, hi: int) -> str:
    """The benchmark's host span (other than the window) that overlaps
    [lo, hi] most."""
    best, best_len = "none", 0
    for s, e, n in trace.spans:
        ov = min(e, hi) - max(s, lo)
        if n != WINDOW and ov > best_len:
            best, best_len = n, ov
    return best


def reduce(trace: Trace, top: int = 10) -> dict:
    """Sums inside the window, in seconds: per chip (averaged over the
    devices) for busy and exposed time, summed over devices for the time
    in each kind of op."""
    lo, hi = window(trace)
    n = max(len(trace.ops), 1)
    busy, exposed, n_coll = 0, 0, 0
    by_kind: Dict[str, int] = defaultdict(int)
    by_name: Dict[str, int] = defaultdict(int)
    gaps: List[Tuple[int, int]] = []
    for dev in sorted(trace.ops):
        ops = trace.ops[dev]
        iv = union(_clip(ops, lo, hi))
        busy += _length(iv)
        colls = [o for o in ops + trace.in_flight.get(dev, [])
                 if o.kind == "collective"]
        n_coll += len(colls)
        others = union(_clip([o for o in ops if o.kind != "collective"],
                             lo, hi))
        exposed += _minus(union(_clip(colls, lo, hi)), others)
        for o in ops:
            t = min(o.end, hi) - max(o.start, lo)
            if t > 0:
                by_kind[o.kind] += t
                by_name[f"{o.name} ({o.kind})"] += t
        if dev == min(trace.ops):
            edges = [lo] + [x for s, e in iv for x in (s, e)] + [hi]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    ns = 1e-9
    return {
        "devices": len(trace.ops),
        "window_s": (hi - lo) * ns,
        "busy_s": busy / n * ns,
        "conv_s": by_kind["conv"] * ns,
        "matmul_s": by_kind["matmul"] * ns,
        "collective_ops": n_coll,
        "collective_s": by_kind["collective"] * ns,
        "collective_exposed_s": exposed / n * ns,
        "device_ops": [[k, v / n * ns] for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_host_span_at(trace, s, e), (e - s) * ns]
                      for s, e in gaps[:top]],
    }
