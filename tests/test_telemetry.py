"""Telemetry subsystem tests: recorder span semantics, sinks/Chrome trace,
metrics histograms, the comm="auto" autotuner (exact on a synthetic timing
table, end-to-end loss-equal in a subprocess), the heartbeat redesign
(monotonic payload vs NTP-jumped mtimes), and the benchmark regression gate.

Forced-device-count runs go through subprocesses (same isolation policy as
tests/test_cluster.py) so the rest of the suite keeps the single real CPU
device."""
import functools
import json
import os
import subprocess
import sys
import tempfile
import textwrap
import time

import numpy as np
import pytest

REPO = os.path.join(os.path.dirname(__file__), "..")
SRC = os.path.join(REPO, "src")


def run_py(code: str, devices: int = 8, timeout: int = 420,
           extra_env=None) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    env.update(extra_env or {})
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


# ---------------------------------------------------------------------------
# Recorder: span nesting, ordering, listeners, lifecycle
# ---------------------------------------------------------------------------

def test_span_nesting_and_ordering():
    from repro.telemetry import Recorder
    r = Recorder()
    with r.span("step", step=1):
        with r.span("compile", step=1):
            pass
        with r.span("ckpt_write", step=1):
            pass
    r.event("note", x=3)
    kinds = [e["kind"] for e in r.events]
    # children finish (and are emitted) before their parent
    assert kinds == ["compile", "ckpt_write", "step", "note"]
    by_kind = {e["kind"]: e for e in r.events}
    step, compile_, ckpt = (by_kind[k] for k in
                            ("step", "compile", "ckpt_write"))
    # monotonic-timestamp invariants: parent brackets its children, the
    # sibling spans don't overlap, durations are consistent
    assert step["t0"] <= compile_["t0"] <= compile_["t1"] <= step["t1"]
    assert compile_["t1"] <= ckpt["t0"]
    for e in (step, compile_, ckpt):
        assert e["dur"] == pytest.approx(e["t1"] - e["t0"])
    assert step["depth"] == 0
    assert compile_["depth"] == 1 and ckpt["depth"] == 1
    assert by_kind["note"]["ph"] == "instant"
    assert by_kind["note"]["x"] == 3


def test_span_durations_feed_histograms_and_listeners_see_events():
    from repro.telemetry import Recorder
    r = Recorder()
    seen = []
    r.add_listener(seen.append)
    with r.span("step", step=1):
        pass
    r.count("steps")
    r.count("items_tok", 128)
    r.gauge("lr", 1e-3)
    assert [e["kind"] for e in seen] == ["step"]
    m = r.metrics()
    assert m["counters"] == {"steps": 1, "items_tok": 128}
    assert m["gauges"] == {"lr": 1e-3}
    assert m["histograms"]["span/step_s"]["count"] == 1


def test_recorder_close_is_idempotent_and_emits_metrics():
    from repro.telemetry import Recorder
    r = Recorder()
    r.count("steps")
    r.close()
    r.close()
    assert r.events[-1]["kind"] == "metrics"
    assert sum(e["kind"] == "metrics" for e in r.events) == 1


def test_null_recorder_overhead_is_cheap():
    """The no-op default must be cheap enough to leave in every hot path:
    bound 100k span enters+exits well under a second (they are attribute
    lookups returning a cached null object)."""
    from repro.telemetry import NULL_RECORDER
    assert not NULL_RECORDER.enabled
    t0 = time.perf_counter()
    for _ in range(100_000):
        with NULL_RECORDER.span("step", step=1):
            pass
    dt = time.perf_counter() - t0
    assert dt < 2.0, f"null span overhead {dt:.3f}s for 100k spans"
    assert NULL_RECORDER.hist("x").count == 0   # null histogram, no state


# ---------------------------------------------------------------------------
# the process recorder: wall clock, bounded memory, data and step spans
# ---------------------------------------------------------------------------

def test_spans_export_on_the_wall_clock():
    """The recorder's anchor puts monotonic spans on time.time_ns(), the
    clock of a profiler trace: each exported span lies inside wall-clock
    readings taken around it (to the anchor's read skew, 0.1 ms)."""
    from repro.telemetry import Recorder
    r = Recorder()
    slack = 100_000
    for _ in range(3):
        before = time.time_ns()
        with r.span("data.wait"):
            time.sleep(0.002)
        after = time.time_ns()
        name, t0, t1 = r.spans_ns()[-1]
        assert name == "data.wait"
        assert before - slack <= t0 < t1 <= after + slack
        assert t1 - t0 >= 2_000_000


def test_histograms_and_event_ring_stay_bounded():
    from repro.telemetry import Histogram, JsonlSink, Recorder
    from repro.telemetry.metrics import WINDOW
    r = Recorder()
    for _ in range(WINDOW + 100):
        with r.span("step.dispatch"):
            pass
    assert len(r.events) == WINDOW
    assert r.hist("span/step.dispatch_s").count == WINDOW
    h, every = Histogram(WINDOW), Histogram()
    for v in range(WINDOW + 904):
        h.observe(float(v))
        every.observe(float(v))
    assert h.count == WINDOW and h.percentile(0) == 904.0
    # only a recorder's histograms are windowed
    assert every.count == WINDOW + 904 and every.percentile(0) == 0.0
    # a sink writes the events, so the ring keeps none of them
    with tempfile.TemporaryDirectory() as td:
        sink = JsonlSink(os.path.join(td, "t.jsonl"))
        r.add_sink(sink)
        r.events.clear()
        with r.span("step.dispatch"):
            pass
        r.remove(sink)
        sink.close()
        assert len(r.events) == 0
        assert len(open(os.path.join(td, "t.jsonl")).readlines()) == 1


def test_data_place_nests_in_data_wait():
    import jax

    from repro.data.pipeline import Prefetcher, make_placer
    from repro.telemetry import Recorder
    r = Recorder()
    src = iter([{"x": np.full((32, 8), i, np.float32)} for i in range(3)])
    pf = Prefetcher(src, place=make_placer(None, None, r), recorder=r)
    got = [jax.block_until_ready(b) for b in pf]
    pf.close()
    assert [float(b["x"][0, 0]) for b in got] == [0.0, 1.0, 2.0]
    deadline = time.monotonic() + 10.0
    while (sum(e["kind"] == "data.h2d" for e in list(r.events)) < 3
           and time.monotonic() < deadline):
        time.sleep(0.01)
    by = {k: [e for e in r.events if e["kind"] == k]
          for k in ("data.wait", "data.place", "data.h2d")}
    assert [len(v) for v in by.values()] == [4, 3, 3]  # + the end of data
    for wait, place, h2d in zip(by["data.wait"], by["data.place"],
                                by["data.h2d"]):
        assert wait["t0"] <= place["t0"] <= place["t1"] <= wait["t1"]
        assert place["depth"] == wait["depth"] + 1
        # data.h2d runs from the start of placement to readiness
        assert h2d["t0"] == place["t0"] and h2d["t1"] >= place["t1"]


def test_h2d_ends_after_the_placed_arrays_are_ready():
    """The observer thread closes data.h2d only once every array of the
    batch is ready; the submitting thread does not wait."""
    import threading

    from repro.data import pipeline
    from repro.telemetry import Recorder

    class Landing:                      # an array whose copy lands on cue
        def __init__(self, gate):
            self.gate = gate

        def block_until_ready(self):
            assert self.gate.wait(10.0)
            return self

    r = Recorder()
    gate = threading.Event()
    t0 = r.clock()
    pipeline._observer("data.h2d").submit(r, t0, {"a": Landing(gate),
                                                  "b": Landing(gate)})
    time.sleep(0.05)
    assert not any(e["kind"] == "data.h2d" for e in r.events)
    landed = r.clock()
    gate.set()
    deadline = time.monotonic() + 10.0
    while not r.events and time.monotonic() < deadline:
        time.sleep(0.005)
    h2d, = [e for e in r.events if e["kind"] == "data.h2d"]
    assert h2d["t0"] == t0 and h2d["t1"] >= landed


def test_batch_spread_over_a_mesh_is_its_own_span():
    """On a mesh of four (CPU) devices data.h2d ends when the copy has
    landed and data.reshard, from the spread's dispatch inside data.place,
    when the sharded batch is ready; a one-device mesh has no spread."""
    code = textwrap.dedent("""
        import json, time
        import numpy as np
        import jax
        from jax.sharding import Mesh
        from repro.core.sharding import ShardingRules
        from repro.data.pipeline import make_placer
        from repro.telemetry import Recorder
        out = {}
        for n in (4, 1):
            r = Recorder()
            mesh = Mesh(np.array(jax.devices()[:n]).reshape(n, 1),
                        ("data", "model"))
            place = make_placer(mesh, ShardingRules(), r)
            b = place({"images": np.ones((8, 4, 4, 3), np.float32),
                       "labels": np.zeros((8,), np.int32)})
            jax.block_until_ready(b)
            out[n] = {"shards": len(b["images"].addressable_shards)}
            kinds = ("data.place", "data.h2d", "data.reshard")
            deadline = time.monotonic() + 10
            while (sum(e["kind"] in kinds[1:] for e in r.events) < min(n, 2)
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            time.sleep(0.05)
            for k in kinds:
                out[n][k] = [(e["t0"], e["t1"]) for e in r.events
                             if e["kind"] == k]
        print(json.dumps(out))
    """)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(root, "src"))
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    four, one = out["4"], out["1"]
    assert four["shards"] == 4 and one["shards"] == 1
    (p0, p1), = four["data.place"]
    (h0, h1), = four["data.h2d"]
    (s0, s1), = four["data.reshard"]
    assert h0 == p0 and p0 <= s0 <= p1 and s1 >= s0
    assert len(one["data.h2d"]) == 1 and one["data.reshard"] == []


def test_run_step_and_fit_emit_step_dispatch_and_counters():
    from repro.api import RunSpec, compile_run
    from repro.telemetry import process_recorder
    rec = process_recorder()

    def counters():
        c = rec.metrics()["counters"]
        return c.get("step.steps", 0), c.get("step.samples", 0)

    run = compile_run(RunSpec(arch="cd-dnn", smoke=True, parallel="serial",
                              steps=3, batch=4, log_every=1,
                              schedule="constant"))
    seen = []
    run.telemetry.add_listener(seen.append)
    steps0, samples0 = counters()
    run.step(next(run.data), 0)
    run.fit(start_step=0, log_fn=lambda *_: None)
    run.close()
    dispatch = [e["step"] for e in seen if e["kind"] == "step.dispatch"]
    assert dispatch == [1, 1, 2, 3]
    assert {"data.wait", "data.place"} <= {e["kind"] for e in seen}
    assert counters() == (steps0 + 4, samples0 + 16)
    assert rec.hist("span/step.dispatch_s").count >= 4


def test_closed_run_detaches_its_sinks(tmp_path):
    from repro.api import RunSpec, compile_run
    from repro.telemetry import process_recorder, read_jsonl, trace_path
    rec = process_recorder()
    run = compile_run(RunSpec(arch="cd-dnn", smoke=True, parallel="serial",
                              batch=4, telemetry=str(tmp_path)))
    run.step(next(run.data), 0)
    run.close()
    assert not rec._sinks and not rec._listeners
    path = trace_path(str(tmp_path), rec.process_index)
    n = len(read_jsonl(path))
    with rec.span("step.dispatch", step=9):     # after close: not written
        pass
    lines = read_jsonl(path)
    assert len(lines) == n
    meta = lines[0]
    assert meta["kind"] == "meta"
    assert (meta["mono"], meta["wall_ns"]) == tuple(rec.anchor)
    kinds = {e["kind"] for e in lines}
    assert {"step.dispatch", "data.wait", "metrics"} <= kinds
    # the aggregates outlive the run
    assert rec.hist("span/step.dispatch_s").count >= 1


# ---------------------------------------------------------------------------
# metrics: histogram percentiles against numpy
# ---------------------------------------------------------------------------

def test_histogram_percentiles_match_numpy():
    from repro.telemetry import Histogram
    rng = np.random.default_rng(0)
    vals = rng.lognormal(size=257)
    h = Histogram()
    for v in vals:
        h.observe(float(v))
    assert h.count == 257
    assert h.percentile(50) == pytest.approx(np.percentile(vals, 50))
    assert h.percentile(99) == pytest.approx(np.percentile(vals, 99))
    s = h.summary()
    assert s["mean"] == pytest.approx(vals.mean())
    assert s["max"] == pytest.approx(vals.max())
    empty = Histogram()
    assert empty.percentile(50) is None
    assert empty.summary()["p99"] is None


# ---------------------------------------------------------------------------
# sinks: JSONL round trip and Chrome trace schema
# ---------------------------------------------------------------------------

def test_jsonl_sink_and_chrome_trace_schema(tmp_path):
    from repro.telemetry import (
        Recorder,
        JsonlSink,
        merge_process_traces,
        read_jsonl,
        trace_path,
    )
    before = time.time_ns()
    r = Recorder(process="train", process_index=0)
    sink = JsonlSink(trace_path(str(tmp_path), 0))
    r.add_sink(sink)
    mono, wall = r.anchor
    r.event("meta", process="train", process_index=0, clock="monotonic",
            mono=mono, wall_ns=wall)
    with r.span("step", step=1):
        with r.span("compile", step=1):
            pass
    r.close()
    sink.close()
    after = time.time_ns()

    lines = read_jsonl(trace_path(str(tmp_path), 0))
    assert [e["kind"] for e in lines][:3] == ["meta", "compile", "step"]

    merged = merge_process_traces(str(tmp_path))
    assert merged == os.path.join(str(tmp_path), "trace.json")
    doc = json.loads(open(merged).read())        # strict: valid JSON only
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    span_evs = [e for e in evs if e.get("ph") == "X"]
    assert {e["name"] for e in span_evs} == {"step", "compile"}
    for e in span_evs:
        # Chrome trace contract: complete events carry µs ts + dur, pid/tid
        assert set(e) >= {"name", "ph", "ts", "dur", "pid", "tid"}
        assert e["ts"] >= 0 and e["dur"] >= 0
    assert any(e.get("ph") == "M" and e["args"]["name"] == "train[0]"
               for e in evs)
    assert any(e.get("ph") == "i" for e in evs)   # instants present
    # ts is wall-clock µs through the meta anchor: the profiler's clock
    for e in span_evs:
        assert before / 1e3 - 1 <= e["ts"] <= e["ts"] + e["dur"] \
            <= after / 1e3 + 1


def test_merge_process_traces_empty_dir_returns_none(tmp_path):
    from repro.telemetry import merge_process_traces
    assert merge_process_traces(str(tmp_path)) is None


# ---------------------------------------------------------------------------
# autotune: exact fit on a synthetic timing table
# ---------------------------------------------------------------------------

def test_fit_comm_model_recovers_synthetic_constants():
    from repro.telemetry import CommProbe, choose_bucket_bytes, fit_comm_model
    G, lat, bw = 8, 5e-6, 6.8e9            # the FDR table constants
    probes = [CommProbe(nbytes=n, backend="lax",
                        seconds=2 * (G - 1) * lat + 2 * (G - 1) / G * n / bw)
              for n in (4096, 65536, 1 << 20, 4 << 20)]
    got_lat, got_bw = fit_comm_model(probes, G)
    assert got_lat == pytest.approx(lat, rel=1e-6)
    assert got_bw == pytest.approx(bw, rel=1e-6)
    # the chosen bucket is the §3.2 closed form at the fitted constants
    from repro.core.balance import optimal_bucket_bytes
    from repro.telemetry.autotune import measured_hw
    total = 128 << 20
    want = int(optimal_bucket_bytes(float(total), G, measured_hw(lat, bw)))
    assert choose_bucket_bytes(total, G, lat, bw) == want
    assert want == pytest.approx(
        np.sqrt(total * lat * bw * G), rel=1e-6)   # sqrt(B*SWlat*BW*G)


def test_fit_comm_model_degenerate_group():
    from repro.telemetry import choose_bucket_bytes, fit_comm_model
    from repro.telemetry.autotune import MAX_BANDWIDTH, MIN_LATENCY_S
    lat, bw = fit_comm_model([], 1)
    assert lat == MIN_LATENCY_S and bw == MAX_BANDWIDTH
    # G=1: no wire time, one whole-tree bucket
    assert choose_bucket_bytes(10 << 20, 1, lat, bw) == 10 << 20


def test_autotune_picks_measured_optimal_bucket_on_mesh():
    """Drive the real autotuner (real mesh, real schedules) but with a FAKE
    clock advanced by the synthetic ring model — the fitted constants and
    the chosen bucket must then be exactly the model's closed form."""
    out = run_py("""
        import numpy as np
        import jax, jax.numpy as jnp
        from repro.comm.bucketer import CommConfig
        from repro.launch.mesh import make_host_mesh
        from repro.telemetry.autotune import autotune_comm
        from repro.telemetry.events import Recorder

        mesh = make_host_mesh(1)
        G = 8
        params = {"w": jnp.zeros((200_000,), jnp.float32),
                  "b": jnp.zeros((1000,), jnp.float32)}
        rec = Recorder()
        comm = autotune_comm(params, mesh, ("data",), CommConfig(),
                             recorder=rec, reps=1, log=print)
        plan = [e for e in rec.events if e["kind"] == "autotune_plan"]
        assert len(plan) == 1, rec.events
        p = plan[0]
        assert p["group"] == G and p["chosen_backend"] == comm.backend
        assert p["bucket_bytes"] == comm.bucket_bytes
        probes = [e for e in rec.events if e["kind"] == "collective"]
        assert len(probes) >= 2
        assert all(e["phase"] == "autotune-probe" for e in probes)
        # bucket plan stays inside the clamp range and is G-padded sane
        total = (200_000 + 1000) * 4
        assert 1 <= comm.bucket_bytes <= total
        print("OK bucket", comm.bucket_bytes, "backend", comm.backend)
    """, devices=8)
    assert "OK" in out


@pytest.mark.parametrize("exc,skipped", [(NotImplementedError, True),
                                         (ValueError, True),
                                         (RuntimeError, False)])
def test_autotune_skips_only_validation_rejections(monkeypatch, exc,
                                                   skipped):
    """An alternative backend whose validation rejects the run is skipped;
    any other failure (a kernel the device's compiler refuses) propagates
    instead of quietly leaving the base backend chosen."""
    import jax.numpy as jnp

    from repro.comm import CommConfig, backends
    from repro.launch.mesh import make_host_mesh
    from repro.telemetry.autotune import autotune_comm

    class Refusing:
        def part_reduce(self, x, axis_name, dim=0):
            raise exc("refused")

        def part_broadcast(self, x, axis_name, dim=0):
            return x

    monkeypatch.setitem(backends._FACTORIES, "pallas-ring", Refusing)
    run = functools.partial(
        autotune_comm, {"w": jnp.zeros((4096,), jnp.float32)},
        make_host_mesh(1), ("data",), CommConfig(),
        backends=("lax", "pallas-ring"), reps=1)
    if skipped:
        logs = []
        assert run(log=logs.append).backend == "lax"
        assert any("'pallas-ring' rejected" in ln for ln in logs), logs
    else:
        with pytest.raises(exc, match="refused"):
            run(log=lambda *_: None)


def test_comm_auto_run_matches_fixed_comm_loss_and_emits_trace():
    """The acceptance criterion: a --comm auto run completes with the same
    final loss as the fixed-comm run to tight tolerance (the §3.4 update is
    bucket-size INVARIANT, but the autotuner also picks the wire format
    jointly and int8's per-hop quantization is lossy), emits a loadable
    Chrome trace containing step.dispatch/data.*/collective spans, and
    logs the autotuned plan including the chosen wire format.  The second
    run's events stay out of the first run's trace: its close detached
    the sink."""
    with tempfile.TemporaryDirectory() as td:
        out = run_py(f"""
            import json
            from repro.launch.train import main
            quiet_args = ["--arch", "vgg-a", "--smoke", "--steps", "4",
                          "--batch", "8", "--schedule", "constant",
                          "--parallel", "zero1"]
            h_auto = main(quiet_args + ["--comm", "auto",
                                        "--trace-dir", {td!r}])
            h_fix = main(quiet_args)
            diff = abs(h_auto[-1]["loss"] - h_fix[-1]["loss"])
            assert diff <= 1e-3 * abs(h_fix[-1]["loss"]), (h_auto, h_fix)
            evs = json.load(open({td!r} + "/trace.json"))["traceEvents"]
            plan = next(e for e in evs
                        if e.get("name") == "autotune_plan")
            assert plan["args"]["chosen_wire_format"] in (
                "fp32", "bf16", "int8"), plan
            names = {{e.get("name") for e in evs}}
            for want in ("step.dispatch", "data.wait", "data.place",
                         "data.h2d", "collective", "autotune_plan",
                         "autotune"):
                assert want in names, (want, names)
            steps = sorted(e["args"]["step"] for e in evs
                           if e.get("name") == "step.dispatch"
                           and e.get("ph") == "X")
            assert steps == [1, 2, 3, 4], steps
            print("LOSS_EQUAL")
        """, devices=8)
        assert "LOSS_EQUAL" in out


# ---------------------------------------------------------------------------
# spec plumbing: TelemetrySpec coercion + comm="auto" validation
# ---------------------------------------------------------------------------

def test_runspec_telemetry_coercion_and_comm_auto_validation():
    from repro.api import RunSpec, TelemetrySpec
    s = RunSpec(arch="vgg-a", telemetry="/tmp/tr")
    assert isinstance(s.telemetry, TelemetrySpec)
    assert s.telemetry.trace_dir == "/tmp/tr"
    RunSpec(arch="vgg-a", parallel="zero1", comm="auto")      # valid
    with pytest.raises(ValueError, match="auto"):
        RunSpec(arch="vgg-a", parallel="zero1", comm="fastest-please")
    with pytest.raises(ValueError, match="comm-capable"):
        RunSpec(arch="vgg-a", parallel="dp", comm="auto")
    with pytest.raises(ValueError):
        TelemetrySpec(autotune_reps=0)
    with pytest.raises(ValueError):
        RunSpec(arch="vgg-a", telemetry=123)


def test_train_cli_rejects_comm_auto_conflicts():
    import argparse

    from repro.launch.train import add_run_args, check_run_args
    ap = add_run_args(argparse.ArgumentParser())
    with pytest.raises(SystemExit):
        check_run_args(ap, ap.parse_args(
            ["--arch", "vgg-a", "--parallel", "zero1", "--comm", "auto",
             "--bucket-mb", "4"]))
    with pytest.raises(SystemExit):
        check_run_args(ap, ap.parse_args(
            ["--arch", "vgg-a", "--parallel", "dp", "--comm", "auto"]))
    # clean combination passes
    check_run_args(ap, ap.parse_args(
        ["--arch", "vgg-a", "--parallel", "zero1", "--comm", "auto"]))


# ---------------------------------------------------------------------------
# serve: latency histograms == external computation (asserted ONCE, here;
# benchmarks/serve_load.py now consumes latency_stats instead of re-deriving)
# ---------------------------------------------------------------------------

def test_server_latency_stats_match_external_numpy():
    from repro.api import ServeSpec, compile_serve
    spec = ServeSpec(arch="llama3-8b", smoke=True, max_batch=2,
                     page_size=8, num_pages=16, max_prompt=8,
                     max_new_tokens=4, prefill_bucket=8)
    server = compile_serve(spec)
    rng = np.random.default_rng(0)
    for _ in range(5):
        server.submit(rng.integers(1, 100, size=4).astype(np.int32), 3)
    done = server.drain()
    assert len(done) == 5
    stats = server.latency_stats()
    e2e = np.array([r.latency for r in done])
    ttft = np.array([r.first_token_t - r.submit_t for r in done])
    assert stats["n"] == 5
    assert stats["e2e_p50_s"] == pytest.approx(np.percentile(e2e, 50))
    assert stats["e2e_p99_s"] == pytest.approx(np.percentile(e2e, 99))
    assert stats["ttft_p50_s"] == pytest.approx(np.percentile(ttft, 50))
    assert stats["ttft_p99_s"] == pytest.approx(np.percentile(ttft, 99))
    server.reset_latency_stats()
    assert server.latency_stats()["n"] == 0
    assert server.latency_stats()["e2e_p50_s"] is None


def test_server_latency_stats_cover_every_request():
    """The latency aggregates span every finished request, not a recent
    window: the slow early requests stay in p99 past 4096 requests."""
    from repro.api import ServeSpec, compile_serve
    from repro.api.serve import Request
    from repro.telemetry.metrics import WINDOW
    server = compile_serve(ServeSpec(arch="llama3-8b", smoke=True,
                                     max_batch=2, page_size=8, num_pages=16,
                                     max_prompt=8, max_new_tokens=4,
                                     prefill_bucket=8))
    done, prompt = [], np.ones(4, np.int32)
    for i in range(WINDOW + 1000):
        t = time.perf_counter() - (10.0 if i < 1000 else 0.0)
        server._finish(0, Request(i, prompt, 1, t, first_token_t=t),
                       done)
    stats = server.latency_stats()
    e2e = np.array([r.latency for r in done])
    assert stats["n"] == WINDOW + 1000
    assert stats["e2e_p99_s"] == pytest.approx(np.percentile(e2e, 99))
    assert stats["e2e_p99_s"] > 9.0


def test_server_emits_prefill_and_decode_spans():
    from repro.api import ServeSpec, compile_serve
    from repro.telemetry import Recorder
    rec = Recorder()
    spec = ServeSpec(arch="llama3-8b", smoke=True, max_batch=2,
                     page_size=8, num_pages=16, max_prompt=8,
                     max_new_tokens=2, prefill_bucket=8)
    server = compile_serve(spec, recorder=rec)
    server.submit(np.ones(4, np.int32), 2)
    server.drain()
    kinds = {e["kind"] for e in rec.events}
    assert "prefill" in kinds and "decode" in kinds
    pre = next(e for e in rec.events if e["kind"] == "prefill")
    assert pre["tokens"] == 4 and pre["bucket"] == 8


# ---------------------------------------------------------------------------
# heartbeat redesign: monotonic payload beats NTP-jumped mtimes
# ---------------------------------------------------------------------------

def _fake_handle(tmpdir, name="hb"):
    from repro.cluster.launcher import WorkerHandle

    class _Alive:
        returncode = None

        def poll(self):
            return None

    return WorkerHandle(proc=_Alive(), process_id=0,
                        hb_file=os.path.join(tmpdir, name), log_file=None)


def test_heartbeat_write_parse_round_trip(tmp_path):
    from repro.cluster.launcher import parse_heartbeat, write_heartbeat
    p = str(tmp_path / "hb")
    assert parse_heartbeat(p) is None
    write_heartbeat(p, 7, 123.5)
    hb = parse_heartbeat(p)
    assert (hb.step, hb.mono) == (7, 123.5)
    # legacy bare-int files still parse, mono-less
    with open(p, "w") as f:
        f.write("42")
    hb = parse_heartbeat(p)
    assert hb.step == 42 and hb.mono is None
    with open(p, "w") as f:
        f.write("not json at all {")
    assert parse_heartbeat(p) is None


def test_staleness_tracks_payload_change_not_wall_clock(tmp_path):
    from repro.cluster.launcher import write_heartbeat
    h = _fake_handle(str(tmp_path))
    now = time.monotonic()
    spawned = now - 100.0
    # no beat yet: stale since spawn
    assert h.staleness(now, spawned) == pytest.approx(100.0, abs=1.0)
    write_heartbeat(h.hb_file, 3, 50.0)
    # first observation of the payload: fresh from the supervisor's view
    assert h.staleness(now, spawned) == pytest.approx(0.0, abs=1e-6)
    # same payload 80s later: 80s stale — even though we now smash the
    # file's MTIME to look brand new (an NTP forward jump must not mask
    # a genuine hang)
    os.utime(h.hb_file, (time.time() + 3600, time.time() + 3600))
    assert h.staleness(now + 80.0, spawned) == pytest.approx(80.0, abs=1e-6)
    # the payload changes (worker made a step): fresh again, regardless of
    # an mtime far in the PAST (NTP backward jump must not false-trigger)
    write_heartbeat(h.hb_file, 4, 51.0)
    os.utime(h.hb_file, (0, 0))
    assert h.staleness(now + 81.0, spawned) == pytest.approx(0.0, abs=1e-6)


def test_staleness_legacy_mtime_fallback(tmp_path):
    h = _fake_handle(str(tmp_path))
    with open(h.hb_file, "w") as f:
        f.write("5")
    now = time.monotonic()
    # fresh mtime -> fresh
    assert h.staleness(now, now - 500.0) < 5.0
    # old mtime -> stale by about that much
    old_wall = time.time() - 300.0
    os.utime(h.hb_file, (old_wall, old_wall))
    assert h.staleness(now, now - 500.0) == pytest.approx(300.0, abs=5.0)


def test_heartbeat_listener_rides_step_spans(tmp_path):
    from repro.cluster.launcher import (
        make_heartbeat_listener,
        parse_heartbeat,
    )
    from repro.telemetry import Recorder
    r = Recorder()
    hb = str(tmp_path / "hb")
    r.add_listener(make_heartbeat_listener(hb))
    with r.span("data.wait", step=1):
        pass
    assert parse_heartbeat(hb) is None        # only step spans beat
    with r.span("step.dispatch", step=1):
        pass
    beat = parse_heartbeat(hb)
    assert beat.step == 1 and beat.mono is not None
    step_ev = next(e for e in r.events if e["kind"] == "step.dispatch")
    # the beat stays on the monotonic clock (NTP jumps cannot fake it)
    assert beat.mono == pytest.approx(step_ev["t1"])
    assert abs(beat.mono - time.monotonic()) < 60.0


def test_cluster_run_merges_per_process_traces():
    """2 real worker processes with --trace-dir: the supervisor must merge
    both workers' JSONL traces into one Chrome trace whose step spans are
    per-process ordered, on the wall clock both processes share."""
    with tempfile.TemporaryDirectory() as td:
        env = dict(os.environ, PYTHONPATH=SRC)
        t_start = time.time_ns() / 1e3
        out = subprocess.run(
            [sys.executable, "-m", "repro.launch.cluster",
             "--processes", "2", "--arch", "vgg-a", "--smoke",
             "--steps", "3", "--batch", "8", "--schedule", "constant",
             "--run-dir", td, "--trace-dir", td],
            env=env, capture_output=True, text=True, timeout=420)
        assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
        evs = json.load(open(os.path.join(td, "trace.json")))["traceEvents"]
        by_pid = {}
        for e in evs:
            if e.get("name") == "step.dispatch" and e.get("ph") == "X":
                by_pid.setdefault(e["pid"], []).append(e)
        assert set(by_pid) == {0, 1}, sorted(by_pid)
        for pid, spans in by_pid.items():
            spans.sort(key=lambda e: e["args"]["step"])
            assert [e["args"]["step"] for e in spans] == [1, 2, 3]
            # within a process the timestamps are ordered and
            # non-overlapping (step N ends before step N+1 begins)
            for a, b in zip(spans, spans[1:]):
                assert a["ts"] + a["dur"] <= b["ts"] + 1e-3
        # both processes share the wall clock: every step lies inside the
        # test's own window of time.time_ns() readings
        t_end = time.time_ns() / 1e3
        for spans in by_pid.values():
            for e in spans:
                assert t_start <= e["ts"] <= e["ts"] + e["dur"] <= t_end


# ---------------------------------------------------------------------------
# benchmark regression gate
# ---------------------------------------------------------------------------

def _run_checker(fresh, baseline):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmarks",
                                      "check_regression.py"),
         "--fresh-dir", fresh, "--baseline-dir", baseline,
         "--files", "BENCH_kernels.json"],
        capture_output=True, text=True, timeout=60)


def test_check_regression_bands(tmp_path):
    base = {"benchmark": "kernels_micro",
            "rows": {"kernel/x": {"us": 100.0, "derived": "ok=True"}},
            "gates": {"n_kernels": 4, "all_ok": True}}
    bdir, fdir = tmp_path / "base", tmp_path / "fresh"
    bdir.mkdir(), fdir.mkdir()
    (bdir / "BENCH_kernels.json").write_text(json.dumps(base))

    # identical -> pass
    (fdir / "BENCH_kernels.json").write_text(json.dumps(base))
    out = _run_checker(str(fdir), str(bdir))
    assert out.returncode == 0, out.stdout

    # wall-clock drift (2x) stays advisory -> pass with a warning
    drift = json.loads(json.dumps(base))
    drift["rows"]["kernel/x"]["us"] = 200.0
    (fdir / "BENCH_kernels.json").write_text(json.dumps(drift))
    out = _run_checker(str(fdir), str(bdir))
    assert out.returncode == 0, out.stdout
    assert "WARN" not in out.stdout       # 2x is inside the 8x band
    drift["rows"]["kernel/x"]["us"] = 5000.0
    (fdir / "BENCH_kernels.json").write_text(json.dumps(drift))
    out = _run_checker(str(fdir), str(bdir))
    assert out.returncode == 0 and "WARN" in out.stdout, out.stdout

    # oracle gate flip -> hard fail
    bad = json.loads(json.dumps(base))
    bad["gates"]["all_ok"] = False
    (fdir / "BENCH_kernels.json").write_text(json.dumps(bad))
    out = _run_checker(str(fdir), str(bdir))
    assert out.returncode == 1 and "all_ok" in out.stdout, out.stdout

    # a baselined metric vanishing from the fresh report -> hard fail
    gone = json.loads(json.dumps(base))
    del gone["gates"]["all_ok"]
    (fdir / "BENCH_kernels.json").write_text(json.dumps(gone))
    out = _run_checker(str(fdir), str(bdir))
    assert out.returncode == 1 and "missing" in out.stdout, out.stdout

    # fresh report absent entirely -> hard fail
    os.remove(fdir / "BENCH_kernels.json")
    out = _run_checker(str(fdir), str(bdir))
    assert out.returncode == 1, out.stdout
