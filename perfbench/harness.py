"""One run of one cell: set-up, the measured window, the check against the
reference, and the result line.

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration (``configs/<name>.json``, its family's reference in
``families/<family>.py``: ``program_config``, ``init``, ``loss`` and,
beyond the conv/fc arithmetic of ``flops.py``, its own
``step_flops_per_sample``) and a traffic mix (``traffic/<name>.json``,
whose ``run`` may also name the optimizer and its weight decay); its
limits are ``limits/<cell>.json`` and each per-layer metric is read by
``metrics/<metric>.py``.  Nothing here names a cell, a family or a param,
so a cell is added by adding those files.

Set-up builds the program's run through ``repro.api.compile_run``, makes
the traffic pool from the seed, and feeds it through the program's data
layer (``Prefetcher`` with ``make_placer``).  The first three steps go
through ``Run.step`` on three distinct batches; their losses and the
params before, after one step and after three are kept for the check.
The same run then drives the window.
"""
from __future__ import annotations

import contextlib
import gc
import glob
import importlib.util
import itertools
import json
import math
import os
import shutil
import sys
import tempfile
import time

import compare
import flops
import hlo
import reference
import traffic as traffic_gen
import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CHECK_STEPS = 3
IN_FLIGHT = 2          # steps the host may enqueue ahead of the device
TRACE_SECONDS = 2.0    # the traced window, after the measured one


def load_json(*parts) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(*parts):
    path = os.path.join(HERE, *parts)
    spec = importlib.util.spec_from_file_location(
        "perfbench_" + "_".join(parts).replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(bench: dict, name: str) -> dict:
    """Everything one workload of ``bench`` needs, loaded by name."""
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise SystemExit(f"no workload {name!r}; BENCHMARK.json has "
                         f"{sorted(wl)}")
    w = wl[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in moved
                                  else [])]
    return {"name": name, "chips": w["chips"], "cfg": cfg,
            "traffic": traffic_gen.load(w["traffic"]),
            "limits": load_json("limits", f"{name}.json"),
            "end_to_end": e2e, "per_layer": per_layer}


def family(cfg: dict):
    return load_module("families", f"{cfg['family']}.py")


def step_flops_per_sample(cfg: dict) -> int:
    """FLOPs per sample of a training step: the family module's own count
    where it keeps one, else ``flops.py``'s conv and fc arithmetic."""
    count = getattr(family(cfg), "step_flops_per_sample", None)
    return count(cfg) if count else flops.step_flops_per_sample(cfg)


class CompileCounter:
    """Counts JAX's compile events (tracing, lowering, backend compiles)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event.startswith("/jax/core/compile"):
            self.n += 1


def use_compile_cache() -> None:
    """The program's persistent compile cache (``$JAX_COMPILATION_CACHE_DIR``
    or ``<checkout>/.jax_cache``), keeping every program, however quick to
    compile, for the next run."""
    import jax
    from repro.launch.compile_cache import use_compile_cache as program_cache
    program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def build(c: dict, seed: int):
    from repro.api import RunSpec, compile_run
    from repro.comm.bucketer import CommConfig
    r = c["traffic"]["run"]
    # an absent key keeps RunSpec's default
    chosen = {k: r[k] for k in ("optimizer", "weight_decay") if k in r}
    spec = RunSpec(arch=family(c["cfg"]).program_config(c["cfg"]),
                   parallel=r["parallel"], comm=CommConfig(**r["comm"]),
                   schedule=r["schedule"], lr=r["lr"],
                   momentum=r["momentum"], grad_clip=r["grad_clip"],
                   batch=c["traffic"]["batch"], seed=seed, **chosen)
    return compile_run(spec)


def feed(run, pool):
    from repro.data.pipeline import Prefetcher, make_placer
    return Prefetcher(itertools.cycle(pool),
                      place=make_placer(run.mesh, run.rules))


def host_params(run) -> dict:
    """The run's params on the host, by leaf name, named as the reference
    names its own (``compare.named_leaves``)."""
    return compare.named_leaves(run.params)


def first_steps(run, batches) -> dict:
    """Drive ``run`` through its first steps on ``batches``, keeping what
    the check compares."""
    out = {"losses": [], "p0": host_params(run)}
    for i in range(CHECK_STEPS):
        metrics = run.step(next(batches), i)
        out["losses"].append(float(metrics["loss"]))
        if i == 0:
            out["p1"] = host_params(run)
    out["p_last"] = host_params(run)
    return out


def measure(run, batches, step0: int, seconds: float) -> dict:
    """The window: enqueue steps until ``seconds`` have passed, with at
    most ``IN_FLIGHT`` steps ahead of the device, then wait for the last.
    Host spans (``data_wait``, ``dispatch``, ``sync``, ``window``) are kept
    in wall-clock ns for the trace."""
    import jax
    losses, waits, spans = [], [], []

    def span(name, t):
        spans.append((name, t, time.time_ns()))

    i = step0
    t0, w0 = time.perf_counter(), time.time_ns()
    while True:
        t = time.time_ns()
        batch = next(batches)
        span("data_wait", t)
        waits.append((spans[-1][2] - t) * 1e-9)
        t = time.time_ns()
        metrics = run.step(batch, i)
        span("dispatch", t)
        losses.append(metrics["loss"])
        i += 1
        if len(losses) > IN_FLIGHT:
            t = time.time_ns()
            losses[-IN_FLIGHT - 1].block_until_ready()
            span("sync", t)
        if time.perf_counter() - t0 >= seconds:
            break
    t = time.time_ns()
    jax.block_until_ready((run.params, run.opt_state))
    span("sync", t)
    t1 = time.perf_counter()
    span("window", w0)
    losses = [float(x) for x in jax.device_get(losses)]
    return {"t0": t0, "window_s": t1 - t0, "steps": len(losses),
            "failed": sum(not math.isfinite(x) for x in losses),
            "data_wait_s": waits, "spans": spans, "next_step": i}


def compiled_step(run, batch, step: int):
    """The timed step as compiled (from the cache): its memory analysis
    and, for attributing the trace's ops, what each instruction does."""
    import jax
    scope = (jax.set_mesh(run.mesh) if run.mesh is not None
             else contextlib.nullcontext())
    with scope:
        compiled = run.jit_step.lower(run.params, run.opt_state, step,
                                      batch).compile()
    ma = compiled.memory_analysis()
    return ({"argument": ma.argument_size_in_bytes,
             "temp": ma.temp_size_in_bytes,
             "output": ma.output_size_in_bytes,
             "alias": ma.alias_size_in_bytes}, hlo.kinds(compiled.as_text()))


def traced(run, batches, step0: int, seconds: float, kinds: dict) -> dict:
    """A short window of its own under the profiler, reduced to device
    time.  Host and Python tracing are off: the host tracer records every
    chunk of the host-side transposes that place an image batch, which
    slowed the host it measured threefold; the window's host spans are
    the benchmark's own."""
    import jax
    tmp = tempfile.mkdtemp(prefix="perfbench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            win = measure(run, batches, step0, seconds)
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        reduced = xplane.reduce(xplane.load(path, kinds=kinds,
                                            host_spans=win["spans"]))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    reduced["steps"] = win["steps"]
    reduced["host_window_s"] = win["window_s"]
    return reduced


def peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


def check(c: dict, seed: int, pool, prog: dict) -> dict:
    """The reference on the first steps' batches, and each number the
    check compares."""
    ref = reference.train_steps(family(c["cfg"]), c["cfg"],
                                c["traffic"]["run"], seed,
                                pool[:CHECK_STEPS])
    return compare.readings(prog, ref)


def run_cell(c: dict, seed: int, seconds: float, trace: bool,
             t_start: float, devices, peaks: dict) -> dict:
    """Set-up, window, check.  Returns the result line's object."""
    compiles = CompileCounter()

    pool = traffic_gen.make_pool(c["traffic"], c["cfg"], seed)
    run = build(c, seed)
    batches = feed(run, pool)
    prog = first_steps(run, batches)

    n_before = compiles.n
    win = measure(run, batches, CHECK_STEPS, seconds)
    window_compiles = compiles.n - n_before
    setup_s = win["t0"] - t_start
    reduced = ma = None
    if trace:
        ma, kinds = compiled_step(run, next(batches), win["next_step"])
        reduced = traced(run, batches, win["next_step"] + 1,
                         min(TRACE_SECONDS, seconds), kinds)
        reduced["samples"] = reduced["steps"] * c["traffic"]["batch"]
    mem_peak = peak_bytes(devices)
    batches.close()
    run.close()
    del run, batches
    gc.collect()

    numbers = check(c, seed, pool, prog)
    limits = c["limits"]
    correct = compare.judge(numbers, limits) and win["failed"] == 0

    samples = win["steps"] * c["traffic"]["batch"]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem_peak}
    if trace:
        ctx = {"cfg": c["cfg"], "traffic": c["traffic"], "peaks": peaks,
               "chips": len(devices), "steps": win["steps"],
               "samples": samples, "window_s": win["window_s"],
               "data_wait_s": win["data_wait_s"], "trace": reduced,
               "memory_analysis": ma}
        metrics = {}
        for m in c["per_layer"]:
            v = load_module("metrics", f"{m['name']}.py").read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    else:
        values = {"samples_per_s": samples / win["window_s"],
                  "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in c["end_to_end"]}
    out = {"correct": bool(correct), "attempted": win["steps"],
           "failed": win["failed"], "metrics": metrics, "device": device,
           "window_compiles": window_compiles, "setup_s": setup_s}
    if trace:
        out["traced_samples_per_s"] = (reduced["samples"]
                                       / reduced["host_window_s"])
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                     for k in limits}
    for k in limits:
        print(f"check {k}: {numbers[k]!r} limit {limits[k]!r}",
              file=sys.stderr)
    return out
