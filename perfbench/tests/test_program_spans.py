"""The readers of the program's own spans, on the CPU: each reads the
median of its span from the process recorder, and nothing where the span
was never recorded."""
import time

import numpy as np
import pytest

import harness
import program_spans


@pytest.fixture
def recorder(monkeypatch):
    """A fresh process recorder for the test."""
    from repro.telemetry import Recorder, events
    rec = Recorder()
    monkeypatch.setattr(events, "_PROCESS", rec)
    return rec


@pytest.mark.parametrize("metric,span", [("h2d_ms", "data.h2d"),
                                         ("step_dispatch_ms",
                                          "step.dispatch")])
def test_reader_is_the_median_of_its_span(recorder, metric, span):
    reader = harness.load_module("metrics", f"{metric}.py")
    assert reader.read({}) is None
    for ms in (3.0, 1.0, 2.0, 50.0):
        recorder.record_span(span, 10.0, 10.0 + ms / 1e3)
    recorder.record_span("other.span", 0.0, 1.0)
    assert reader.read({}) == pytest.approx(2.5)


def test_no_process_recorder_reads_nothing(monkeypatch):
    """A program without a process recorder (before the program had one)
    gives no reading and does not raise."""
    import repro.telemetry
    monkeypatch.delattr(repro.telemetry, "process_recorder")
    assert program_spans.median_ms("data.h2d") is None


@pytest.mark.parametrize("metric", ["h2d_ms", "step_dispatch_ms"])
def test_readers_read_a_cpu_run_through_the_data_layer(recorder, metric):
    """A run driven as the harness drives it: its own Prefetcher and
    placer, steps through ``Run.step``."""
    import jax

    import small
    import traffic as traffic_gen
    c = small.small_cell("cd-dnn_b1024_1chip", small.DNN, batch=16)
    pool = traffic_gen.make_pool(dict(c["traffic"], pool=2), c["cfg"], 7)
    run = harness.build(c, 7)
    batches = harness.feed(run, pool)
    for i in range(4):
        m = run.step(next(batches), i)
    jax.block_until_ready(m)
    batches.close()
    run.close()
    reader = harness.load_module("metrics", f"{metric}.py")
    deadline = time.monotonic() + 10.0
    while reader.read({}) is None and time.monotonic() < deadline:
        time.sleep(0.01)          # data.h2d closes on the observer thread
    v = reader.read({})
    assert v is not None and np.isfinite(v) and v >= 0
