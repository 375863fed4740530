"""Telemetry sinks: JSONL event stream + Chrome-trace exporter + merge.

Per-process layout under a ``--trace-dir``:

    trace_p<i>.jsonl   every event of cluster process i, one JSON per line
                       (written live by :class:`JsonlSink`; the last line
                       is the final metrics snapshot)
    trace.json         the merged Chrome trace — load it in
                       ``chrome://tracing`` or https://ui.perfetto.dev

Single-process runs merge their own lone file at ``Run.close``; cluster
runs leave the per-process files to the SUPERVISOR's merge
(``launch.cluster``) — workers cannot merge, they'd race each other.
Timestamps in the JSONL are per-process ``time.monotonic`` (durations and
the heartbeat survive NTP jumps); each file's first line, the ``meta``
event, carries its recorder's anchor (``mono`` and the ``wall_ns`` read
beside it), and the Chrome trace puts every event on the wall clock
through it: ``ts`` is microseconds since the epoch, the clock of a JAX
profiler trace.  Merged processes therefore share one timeline, as far as
the hosts' wall clocks agree.
"""
from __future__ import annotations

import glob
import json
import os
import re
from typing import Dict, List, Optional

_SPAN_META = ("kind", "ph", "t0", "t1", "dur", "depth", "tid")


def trace_path(trace_dir: str, process_index: int) -> str:
    return os.path.join(trace_dir, f"trace_p{process_index}.jsonl")


class JsonlSink:
    """Recorder listener that streams every event to a JSONL file.
    Line-buffered so a SIGKILLed worker loses at most one event; writes
    after ``close`` are dropped (the recorder may outlive the sink)."""

    def __init__(self, path: str):
        self.path = path
        self._f = open(path, "w", buffering=1)
        self._closed = False

    def __call__(self, ev: dict) -> None:
        if not self._closed:
            self._f.write(json.dumps(ev, separators=(",", ":")) + "\n")

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self._f.close()


def read_jsonl(path: str) -> List[dict]:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def to_chrome_events(events: List[dict], pid: int = 0,
                     name: Optional[str] = None) -> List[dict]:
    """Recorder events -> Chrome trace ``traceEvents`` (``ph: "X"``
    complete spans, ``ph: "i"`` instants; ``ts``/``dur`` in microseconds).
    ``ts`` is on the wall clock, through the anchor of the ``meta`` event
    among ``events``; without one the timestamps count from the earliest
    event."""
    spans = [e for e in events if "t0" in e]
    meta = next((e for e in events
                 if e.get("kind") == "meta" and "wall_ns" in e), None)
    if meta is not None:
        mono0, base_us = meta["mono"], meta["wall_ns"] / 1e3
    else:
        mono0, base_us = min((e["t0"] for e in spans), default=0.0), 0.0
    out = []
    if name:
        out.append({"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                    "args": {"name": name}})
    for e in spans:
        args = {k: v for k, v in e.items() if k not in _SPAN_META}
        ts = base_us + (e["t0"] - mono0) * 1e6
        if e.get("ph") == "span":
            # one row per recording thread — Chrome stacks "X" events
            # that nest in time into the flame rows itself
            out.append({"name": e["kind"], "cat": "span", "ph": "X",
                        "ts": ts, "dur": e["dur"] * 1e6, "pid": pid,
                        "tid": e.get("tid", 0), "args": args})
        else:
            out.append({"name": e["kind"], "cat": "instant", "ph": "i",
                        "ts": ts, "pid": pid, "tid": 0, "s": "p",
                        "args": args})
    return out


def write_chrome_trace(path: str,
                       events_by_pid: Dict[int, List[dict]],
                       names: Optional[Dict[int, str]] = None) -> str:
    trace_events = []
    for pid in sorted(events_by_pid):
        nm = (names or {}).get(pid)
        trace_events.extend(to_chrome_events(events_by_pid[pid], pid=pid,
                                             name=nm))
    with open(path, "w") as f:
        json.dump({"traceEvents": trace_events, "displayTimeUnit": "ms"}, f)
    return path


def merge_process_traces(trace_dir: str,
                         out_name: str = "trace.json") -> Optional[str]:
    """Merge every ``trace_p*.jsonl`` in ``trace_dir`` into one Chrome
    trace (one pid per cluster process).  Returns the merged path, or
    ``None`` when no per-process files exist yet."""
    files = sorted(glob.glob(os.path.join(trace_dir, "trace_p*.jsonl")))
    if not files:
        return None
    by_pid: Dict[int, List[dict]] = {}
    names: Dict[int, str] = {}
    for path in files:
        m = re.search(r"trace_p(\d+)\.jsonl$", path)
        pid = int(m.group(1)) if m else len(by_pid)
        events = read_jsonl(path)
        by_pid[pid] = events
        meta = next((e for e in events if e.get("kind") == "meta"), {})
        names[pid] = f"{meta.get('process', 'proc')}[{pid}]"
    return write_chrome_trace(os.path.join(trace_dir, out_name),
                              by_pid, names)
