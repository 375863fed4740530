"""Structured telemetry: spans/counters -> sinks -> the autotune loop.

``events`` records (one recorder per process, ``process_recorder``),
``sinks`` persist (JSONL + Chrome trace on the wall clock), ``metrics``
aggregate (p50/p99 histograms over a bounded window), ``scopes`` names the
train step's phases on the device (``jax.named_scope``; ``phase_of`` reads
them back), and ``autotune`` closes the loop — feeding measured per-bucket
collective times back into the §3.2 balance model that picks the
fusion-buffer size (``RunSpec.comm="auto"``)."""
from repro.telemetry.autotune import (
    CommProbe,
    autotune_comm,
    choose_bucket_bytes,
    fit_comm_model,
    measured_hw,
)
from repro.telemetry.events import (
    NULL_RECORDER,
    NullRecorder,
    Recorder,
    RunRecorder,
    make_recorder,
    process_recorder,
)
from repro.telemetry.metrics import Counter, Gauge, Histogram
from repro.telemetry.sinks import (
    JsonlSink,
    merge_process_traces,
    read_jsonl,
    to_chrome_events,
    trace_path,
    write_chrome_trace,
)

__all__ = [
    "CommProbe", "autotune_comm", "choose_bucket_bytes", "fit_comm_model",
    "measured_hw", "NULL_RECORDER", "NullRecorder", "Recorder",
    "RunRecorder", "make_recorder", "process_recorder", "Counter", "Gauge", "Histogram", "JsonlSink",
    "merge_process_traces", "read_jsonl", "to_chrome_events", "trace_path",
    "write_chrome_trace",
]
