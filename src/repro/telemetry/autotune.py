"""``comm="auto"``: close the §3.2 loop with MEASURED comm constants.

The balance model (``core.balance``) predicts the optimal fusion-buffer
size as ``b* = sqrt(B * SWlat * BW * G)`` — but until now SWlat/BW came
from the ``backend_hw`` hardware table.  Here they are measured: before
the optimizer strips are laid out (the ZeRO-1 state layout depends on the
bucket plan, so the size must be fixed BEFORE ``init_fn`` — see
``checkpoint.replan``, which refuses mid-run bucket changes), the
autotuner drives the run's REAL collective schedule over the run's REAL
mesh with per-bucket roundtrips at several candidate bucket sizes, fits

    t(bucket) = 2*(G-1)*SWlat + 2*(G-1)/G * bytes/BW

by least squares over the timed samples (``ring_collective_time``'s exact
form), and hands the fitted constants to ``optimal_bucket_bytes``.  Each
timed roundtrip is recorded as a ``collective`` telemetry span and the
chosen plan as an ``autotune_plan`` event, so the decision is auditable in
the trace.

The probe buffers are dummies in the wire dtype — only shapes matter for
timing — and every bucket's roundtrip goes through one shared jitted
function, so XLA compiles once per DISTINCT padded size, not per bucket.
In multi-process runs every process probes in lockstep (same deterministic
plan); the per-sample times are allgathered and averaged so every process
fits identical constants and picks the SAME plan — divergent bucket plans
across members would deadlock the first real collective.

The probes time the DENSE fp32 roundtrip; compressed wire formats
(``CommConfig.wire_format``) are then predicted analytically from the
fitted (SWlat, BW) via ``core.balance``'s bytes-on-wire models, and the
winner is the jointly-best (backend, wire_format, bucket_bytes) triple.
``topk`` is never auto-chosen — it is lossy AND stateful (error-feedback
residual in the optimizer state), so it stays an explicit opt-in.

When ``cache_path`` is set (the cluster launcher exports
``ENV_AUTOTUNE_CACHE`` pointing into the run dir), the chosen plan is
persisted keyed by the probe inputs (group size, axes, gradient bytes,
candidate sets) and an elastic relaunch with the SAME key skips the probe
entirely; a world-size change misses the key and re-probes — the
elastic supervisor also deletes the file outright on shrink/grow so stale
ring constants can never leak across a topology change.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.comm.bucketer import CommConfig, plan_buckets
from repro.comm.schedule import group_axes, make_schedule
from repro.configs.base import HardwareConfig
from repro.core.balance import optimal_bucket_bytes, wire_reduce_factor

# clamps for degenerate fits (a 1-member group, or noise driving the least
# squares negative): keep the constants positive and finite so the closed
# form — and the JSON the plan event serializes to — stay well-defined
MIN_LATENCY_S = 1e-9
MAX_BANDWIDTH = 1e15

# env var the cluster launcher sets on every worker: path of the per-run
# autotune plan cache (see module docstring)
ENV_AUTOTUNE_CACHE = "REPRO_AUTOTUNE_CACHE"


@dataclass(frozen=True)
class CommProbe:
    """One timed roundtrip of one fusion buffer."""
    nbytes: int          # wire bytes of the bucket (padded_size * itemsize)
    seconds: float       # best-of-reps wall time of reduce+broadcast
    backend: str


def measured_hw(sw_latency: float, link_bw: float,
                name: str = "measured") -> HardwareConfig:
    """A ``HardwareConfig`` carrying MEASURED comm constants — the compute
    fields are placeholders (the bucket optimum never reads them)."""
    return HardwareConfig(name=name, peak_flops=1.0, mem_bw=1.0,
                          link_bw=max(link_bw, 1.0),
                          sw_latency=max(sw_latency, MIN_LATENCY_S))


def fit_comm_model(probes: Sequence[CommProbe],
                   G: int) -> Tuple[float, float]:
    """Least-squares (SWlat, BW) from per-bucket roundtrip times under the
    §3.2 ring model ``t = 2*(G-1)*SWlat + 2*(G-1)/G * nbytes/BW``.

    Exact on a synthetic table generated from the model (tested); on real
    measurements the clamps keep a noisy fit physical."""
    if G <= 1 or not probes:
        return MIN_LATENCY_S, MAX_BANDWIDTH
    A = np.array([[2.0 * (G - 1), 2.0 * (G - 1) / G * p.nbytes]
                  for p in probes])
    y = np.array([p.seconds for p in probes])
    sol, *_ = np.linalg.lstsq(A, y, rcond=None)
    lat = float(max(sol[0], MIN_LATENCY_S))
    inv_bw = float(max(sol[1], 1.0 / MAX_BANDWIDTH))
    return lat, min(1.0 / inv_bw, MAX_BANDWIDTH)


def choose_bucket_bytes(total_bytes: int, G: int, sw_latency: float,
                        link_bw: float, wire_format: str = "fp32",
                        topk_ratio: float = 0.05) -> int:
    """``optimal_bucket_bytes`` with measured constants in place of the
    ``backend_hw`` table (G<=1 degenerates to one whole-tree bucket).
    ``wire_format`` applies the bytes-on-wire factor — a compressed reduce
    wire amortizes the latency term over a larger optimal bucket."""
    b = optimal_bucket_bytes(float(total_bytes), G,
                             measured_hw(sw_latency, link_bw),
                             wire_format=wire_format, topk_ratio=topk_ratio)
    return max(1, int(b))


def _cache_key(G, axes, total_bytes, backends, wire_formats) -> dict:
    return {"G": int(G), "axes": list(axes),
            "total_bytes": int(total_bytes),
            "backends": list(backends), "wire_formats": list(wire_formats)}


def _load_cached_plan(path: str, key: dict):
    """The persisted plan, or None on any miss (absent/corrupt/other key)."""
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    return data.get("plan") if data.get("key") == key else None


def _save_cached_plan(path: str, key: dict, plan: dict) -> None:
    """Atomic write (tmp + rename) — co-located workers may race."""
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump({"key": key, "plan": plan}, f, indent=1)
        os.replace(tmp, path)
    except OSError:
        pass  # an unwritable cache just means re-probing next launch


def _probe_sizes(params, G: int, total_bytes: int,
                 itemsize: int, max_sizes: int = 6) -> List[int]:
    """Distinct padded bucket sizes (elements) across a ladder of candidate
    bucket byte-sizes — the model needs >= 2 distinct message sizes to
    separate the latency and bandwidth terms, so a degenerate tree (one
    big tensor) gets a synthetic small buffer added."""
    sizes = set()
    for divisor in (16, 4, 1):
        cand = max(total_bytes // divisor, itemsize)
        for b in plan_buckets(params, G, cand).buckets:
            sizes.add(b.padded_size)
    if len(sizes) < 2:
        # ~1/32 of the largest buffer, rounded up to a multiple of G (the
        # padding contract every real bucket obeys)
        small = max(-(-(max(sizes) // 32) // G) * G, G)
        sizes.add(small)
    ranked = sorted(sizes)
    if len(ranked) > max_sizes:
        idx = np.linspace(0, len(ranked) - 1, max_sizes).astype(int)
        ranked = [ranked[i] for i in sorted(set(idx.tolist()))]
    return ranked


def _roundtrip_fn(mesh, axis_arg, base: CommConfig, backend: str, G: int):
    """One jitted replicated-in/replicated-out reduce+broadcast roundtrip —
    the exact wire path ``optim.dist.UpdatePlan`` drives, minus the
    optimizer.  ``step=0`` binds the step-scheduled backends (gossip)."""
    wire = base.wire_dtype
    sched = make_schedule(axis_arg, base.hierarchical, backend,
                          base.cross_backend, step=0)

    def rt(buf):
        return sched.broadcast(sched.reduce(buf, wire) / G)

    return jax.jit(jax.shard_map(rt, mesh=mesh, in_specs=(P(),),
                                 out_specs=P(), check_vma=False))


def _rejection(mesh, axis_arg, base: CommConfig, backend: str, G: int,
               sizes: Sequence[int]) -> Optional[Exception]:
    """The ``ValueError`` / ``NotImplementedError`` with which a backend's
    own validation rejects this mesh or these buffer sizes, else None.
    Tracing the roundtrip runs that validation and compiles nothing; a
    compile or runtime failure later, in the timed probe, propagates — a
    kernel the device refuses must never turn into "auto chose lax"."""
    fn = _roundtrip_fn(mesh, axis_arg, base, backend, G)
    try:
        with jax.set_mesh(mesh):
            for n in sizes:
                fn.trace(jax.ShapeDtypeStruct((int(n),), base.wire_dtype))
    except (ValueError, NotImplementedError) as e:
        return e
    return None


def _time_backend(mesh, axis_arg, base: CommConfig, backend: str, G: int,
                  sizes: Sequence[int], reps: int, recorder,
                  clock=time.perf_counter) -> List[CommProbe]:
    """Best-of-``reps`` roundtrip time per buffer size on one backend;
    every timed rep is a ``collective`` telemetry span."""
    wire = base.wire_dtype
    itemsize = np.dtype(wire).itemsize
    fn = _roundtrip_fn(mesh, axis_arg, base, backend, G)
    probes = []
    with jax.set_mesh(mesh):
        for n in sizes:
            buf = jnp.zeros((int(n),), wire)
            jax.block_until_ready(fn(buf))          # compile outside timing
            best = float("inf")
            for r in range(reps):
                with recorder.span("collective", phase="autotune-probe",
                                   backend=backend, elements=int(n),
                                   nbytes=int(n) * itemsize, rep=r):
                    t0 = clock()
                    jax.block_until_ready(fn(buf))
                    best = min(best, clock() - t0)
            probes.append(CommProbe(nbytes=int(n) * itemsize,
                                    seconds=best, backend=backend))
    return probes


def _sync_times(probes: List[CommProbe]) -> List[CommProbe]:
    """Average each probe's time across cluster processes so every member
    fits the same constants (identical plans or the group deadlocks)."""
    if jax.process_count() <= 1:
        return probes
    from jax.experimental import multihost_utils
    times = np.array([p.seconds for p in probes], np.float64)
    gathered = multihost_utils.process_allgather(times)
    mean = np.asarray(gathered).reshape(jax.process_count(), -1).mean(0)
    return [dataclasses.replace(p, seconds=float(t))
            for p, t in zip(probes, mean)]


def autotune_comm(params, mesh, data_axes, base: CommConfig,
                  recorder=None, backends: Optional[Sequence[str]] = None,
                  reps: int = 2, log=print,
                  wire_formats: Optional[Sequence[str]] = None,
                  cache_path: Optional[str] = None) -> CommConfig:
    """Measure, fit, choose: returns ``base`` with ``bucket_bytes`` (and
    possibly ``backend`` / ``wire_format``) replaced by the jointly
    optimal measured plan.

    ``backends`` is the candidate set (the mode's ``MODE_CAPS.backends``);
    ``base.backend`` is always probed first and is the fallback when an
    alternative's validation rejects this mesh (``ValueError`` /
    ``NotImplementedError``); any other probe failure is raised.
    ``wire_formats`` is the mode's wire-format capability set; ``topk`` is
    filtered out (lossy AND stateful — explicit opt-in only, see module
    docstring).
    ``cache_path`` short-circuits the probe when a persisted plan's key
    matches this launch."""
    from repro.telemetry.events import NULL_RECORDER
    recorder = recorder if recorder is not None else NULL_RECORDER
    axes, axis_arg, G = group_axes(mesh, data_axes)
    wire_itemsize = np.dtype(base.wire_dtype).itemsize
    leaves = jax.tree.leaves(params)
    total_bytes = sum(leaf.size for leaf in leaves) * wire_itemsize
    sizes = _probe_sizes(params, G, total_bytes, wire_itemsize)

    candidates = [base.backend]
    for b in backends or ():
        if b not in candidates:
            candidates.append(b)
    formats = [base.wire_format]
    for fmt in wire_formats or ():
        if fmt != "topk" and fmt not in formats:
            formats.append(fmt)

    key = _cache_key(G, axes, total_bytes, candidates, formats)
    if cache_path:
        plan = _load_cached_plan(cache_path, key)
        if plan is not None:
            comm = dataclasses.replace(
                base, bucket_bytes=int(plan["bucket_bytes"]),
                backend=plan["chosen_backend"],
                wire_format=plan["chosen_wire_format"])
            recorder.event("autotune_plan", group=G, cached=True,
                           total_bytes=int(total_bytes), probes=0, **plan)
            log(f"comm=auto: cached plan ({cache_path}) -> "
                f"bucket_bytes={comm.bucket_bytes} backend={comm.backend} "
                f"wire_format={comm.wire_format}")
            return comm

    fits = {}
    all_probes: List[CommProbe] = []
    for backend in candidates:
        rejected = (None if backend == base.backend else
                    _rejection(mesh, axis_arg, base, backend, G, sizes))
        if rejected is not None:
            log(f"comm=auto: backend {backend!r} rejected this run "
                f"({type(rejected).__name__}: {rejected}); skipping")
            continue
        probes = _sync_times(_time_backend(
            mesh, axis_arg, base, backend, G, sizes, reps, recorder))
        all_probes.extend(probes)
        fits[backend] = fit_comm_model(probes, G)

    # joint choice: for each surviving backend's fitted constants, predict
    # the step wire time of every candidate format at ITS OWN optimal
    # bucket — compressed formats shrink only the reduce side (the weight
    # all-gather stays dense fp32, see core.balance.compressed_allreduce_time)
    plans = {}
    for backend, (lat, bw) in fits.items():
        for fmt in formats:
            b_star = choose_bucket_bytes(total_bytes, G, lat, bw,
                                         wire_format=fmt,
                                         topk_ratio=base.topk_ratio)
            n_coll = plan_buckets(params, G, b_star).n_collectives
            f = wire_reduce_factor(fmt, base.topk_ratio)
            t_pred = (n_coll * 2.0 * (G - 1) * lat
                      + (G - 1) / G * (1.0 + f) * total_bytes / bw) \
                if G > 1 else 0.0
            plans[(backend, fmt)] = {
                "sw_latency_s": lat, "link_bw_Bps": bw,
                "bucket_bytes": b_star, "n_collectives": n_coll,
                "predicted_s": t_pred}

    winner = min(plans, key=lambda k: (plans[k]["predicted_s"],
                                       k[0] != base.backend,
                                       k[1] != base.wire_format))
    w_backend, w_fmt = winner
    chosen = dict(plans[winner], chosen_backend=w_backend,
                  chosen_wire_format=w_fmt)
    comm = dataclasses.replace(base, bucket_bytes=chosen["bucket_bytes"],
                               backend=w_backend, wire_format=w_fmt)
    if cache_path:
        _save_cached_plan(cache_path, key, chosen)
    recorder.event("autotune_plan", group=G, total_bytes=int(total_bytes),
                   probes=len(all_probes), backends=list(fits),
                   wire_formats=list(formats), **chosen)
    log(f"comm=auto: G={G} measured SWlat={chosen['sw_latency_s']:.2e}s "
        f"BW={chosen['link_bw_Bps'] / 2 ** 30:.2f}GiB/s over "
        f"{len(all_probes)} collective probes -> "
        f"bucket_bytes={chosen['bucket_bytes']} "
        f"({chosen['bucket_bytes'] / 2 ** 20:.2f}MiB, "
        f"{chosen['n_collectives']} collectives) backend={w_backend} "
        f"wire_format={w_fmt}")
    return comm
