"""Bucket-size sweep for the gradient communication subsystem (repro.comm).

For the paper's CNN workloads this sweeps the fusion-buffer size over the
§3.2 latency+bucket model (core.balance): per step, the collective count
drops from O(#tensors) — one part-reduce/part-broadcast pair per tensor, the
seed schedule — to O(total_bytes / bucket_bytes), and the predicted gradient
round-trip time bottoms out near the closed-form optimum
``optimal_bucket_bytes`` = sqrt(B * SWlat * BW * G).  The hierarchical rows
compare one flat 128-member ring against the two-level in-pod + cross-pod
composition on the same tree.

Collective counts come from the REAL planner (repro.comm.plan_buckets over
the actual weight-tensor shapes), so they match what the bucketed
``make_distributed_update`` would issue; only the times are model-predicted.

The ``overlap_*`` rows report the predicted EXPOSED communication per step:
with the monolithic schedule every transfer is exposed (overlap off), while
the §3.1 bubble schedule (``CommConfig.overlap`` / ``--overlap``) hides each
bucket's reduce under the backprop remaining below its trigger layer —
``core.balance.bucket_bubble_schedule`` over the same real plan, with the
bucket→layer readiness metadata of ``repro.comm.overlap``.

Every predicted time is per COLLECTIVE BACKEND (``--backend {lax,
pallas-ring}``): the ``core.balance.RING_BACKEND_MODELS`` constants shift
the latency/bandwidth terms per implementation.  ``measured_rows`` times
the real executable schedule — the same ``FlatSchedule`` + backend the
bucketed update drives — on a forced-8-device host mesh (subprocess, like
tests/test_distributed.py) and pairs each wall-clock row with the model's
prediction for the same plan.  Host-mesh CPU wall clock is not ICI time —
the comparable quantities are the bucket-size TREND and the lax-vs-ring
ratio, not absolute seconds (pallas-ring runs its hop kernels in interpret
mode off-TPU, so its host numbers are pessimistic).
"""
from __future__ import annotations

import math
import os
import re
import subprocess
import sys
import textwrap

import jax

from repro.comm.bucketer import WIRE_FORMATS, plan_buckets
from repro.comm.overlap import exposed_comm
from repro.configs import XEON_E5_2666V3_10GBE as GBE, XEON_E5_2698V3_FDR as FDR, get_config
from repro.core.balance import (
    SIZE_F32,
    bucketed_allreduce_time,
    collective_count,
    compressed_allreduce_time,
    conv_comp_flops,
    fc_comp_flops,
    hierarchical_allreduce_time,
    optimal_bucket_bytes,
    ring_collective_time,
    wire_reduce_bytes,
)

MIB = 2**20
SWEEP_MIB = (0.25, 1.0, 4.0, 16.0, 32.0)
G = 64           # the paper's 256-minibatch / 4-per-node operating point
MB_NODE = 4      # data points per node at that operating point
G_PODS, G_IN = 8, 16   # two-level composition of 128 nodes

MEASURED_MIB = (0.25, 4.0)
MEASURED_DEVICES = 8
MEASURED_FORMATS = ("fp32", "int8", "topk")   # bf16 is a dense dtype cast —
#                                               shape-identical to fp32 on a
#                                               host mesh, nothing to measure
TOPK_RATIO = 0.05


def grad_tree(net: str):
    """Weight + bias leaves of a paper CNN — the family adapter's param
    specs, i.e. exactly the tree (and tree order) the real bucketed
    ``make_distributed_update`` plans over.  ``core.params.Spec`` is
    shape-only, so plan_buckets runs without materializing VGG-A.
    Returns (leaves, leaf_layer): per flat leaf, the forward layer index it
    belongs to (parsed from the spec names, e.g. ``conv3_w`` -> 3) — the
    readiness metadata the §3.1 overlap schedule needs."""
    from repro.api import adapter_for
    cfg = get_config(net)
    flat = jax.tree_util.tree_flatten_with_path(
        adapter_for(cfg).param_specs(cfg))[0]
    leaves = [leaf for _, leaf in flat]
    leaf_layer = [int(re.search(r"\d+", jax.tree_util.keystr(p)).group())
                  for p, _ in flat]
    return leaves, leaf_layer


def layer_comps(net: str):
    """Per forward layer, FLOPs per node per iteration (3 passes) at the
    G=64 operating point; pool layers contribute ~0."""
    cfg = get_config(net)
    comps = []
    for lyr in cfg.layers:
        if lyr.kind == "conv":
            comps.append(conv_comp_flops(lyr, MB_NODE))
        elif lyr.kind == "fc":
            comps.append(fc_comp_flops(lyr.ifm, lyr.ofm, MB_NODE))
        else:
            comps.append(0.0)
    return comps


def _size(leaf) -> int:
    return math.prod(leaf.shape)


def rows(backend: str = "lax"):
    out = []
    for net in ("vgg-a", "overfeat-fast"):
        leaves, leaf_layer = grad_tree(net)
        comps = layer_comps(net)
        total = sum(_size(lyr) for lyr in leaves) * SIZE_F32
        n_tensors = len(leaves)
        pre = f"comm/{net}/{backend}"
        out.append((f"{pre}/n_tensors", n_tensors, ""))
        out.append((f"{pre}/grad_MiB", total / MIB, ""))
        # the serialization granularity of each schedule is its largest
        # single message: the biggest tensor for per-tensor, the biggest
        # fusion buffer for bucketed plans
        max_leaf = max(_size(lyr) for lyr in leaves) * SIZE_F32
        for hw, tag in ((FDR, "FDR"), (GBE, "10GbE")):
            # per-tensor baseline: the seed schedule's collective count
            t0 = bucketed_allreduce_time(total, n_tensors, 0, G, hw,
                                         fill_bytes=max_leaf, backend=backend)
            out.append((f"{pre}/{tag}/per_tensor_ms", t0 * 1e3,
                        f"n_coll={n_tensors};fill_MiB={max_leaf / MIB:.1f}"))
            for mib in SWEEP_MIB:
                plan = plan_buckets(leaves, G, int(mib * MIB))
                n_model = collective_count(total, n_tensors, mib * MIB)
                fill = max(b.size for b in plan.buckets) * SIZE_F32
                # time uses the REAL plan's count and largest buffer (the
                # planner never splits a tensor, so it can issue far fewer
                # collectives than the closed-form ceil(total/bucket) —
                # the `model=` column shows that law)
                t = bucketed_allreduce_time(total, n_tensors, mib * MIB,
                                            G, hw,
                                            n_coll=plan.n_collectives,
                                            fill_bytes=fill, backend=backend)
                out.append((f"{pre}/{tag}/bucket_{mib}MiB_ms", t * 1e3,
                            f"n_coll={plan.n_collectives};model={n_model}"))
                # §3.1 overlap: exposed-comm with the bubble schedule over
                # the SAME real plan vs. the monolithic (all-exposed) path
                comm_times = [ring_collective_time(
                    b.padded_size * SIZE_F32, G, hw, backend=backend)
                    for b in plan.buckets]
                off, on, _ = exposed_comm(plan, comm_times, comps, hw,
                                          leaf_layer=leaf_layer,
                                          efficiency=0.75)
                hidden = 100.0 * (1.0 - on / off) if off > 0 else 0.0
                out.append((
                    f"{pre}/{tag}/overlap_{mib}MiB_exposed_ms",
                    on * 1e3,
                    f"off={off * 1e3:.3f}ms;hidden={hidden:.0f}%"))
            # closed-form optimum (splittable-tensor model — the planner
            # rows above carry the real unsplittable-tensor counts)
            b_star = optimal_bucket_bytes(total, G, hw)
            t_star = bucketed_allreduce_time(total, n_tensors, b_star, G, hw,
                                             backend=backend)
            out.append((f"{pre}/{tag}/opt_bucket_MiB", b_star / MIB,
                        f"closed_form_ms={t_star * 1e3:.3f}"))
        # hierarchical vs flat at 128 nodes (8 pods x 16), 4 MiB buckets;
        # the backend drives the flat ring / the in-pod stage, the
        # cross-pod hop stays lax (make_schedule's default pairing)
        plan4 = plan_buckets(leaves, G_PODS * G_IN, 4 * MIB)
        fill4 = max(b.size for b in plan4.buckets) * SIZE_F32
        t_flat = bucketed_allreduce_time(total, n_tensors, 4 * MIB,
                                         G_PODS * G_IN, FDR,
                                         n_coll=plan4.n_collectives,
                                         fill_bytes=fill4, backend=backend)
        t_hier = hierarchical_allreduce_time(total, n_tensors, 4 * MIB,
                                             G_IN, G_PODS, FDR,
                                             pod_bw=4 * FDR.link_bw,
                                             n_coll=plan4.n_collectives,
                                             fill_bytes=fill4,
                                             backend=backend)
        out.append((f"{pre}/hier128_flat_ms", t_flat * 1e3,
                    f"ring={G_PODS * G_IN}"))
        out.append((f"{pre}/hier128_two_level_ms", t_hier * 1e3,
                    f"in_pod={G_IN};cross_pod={G_PODS}"))
    return out


def wire_rows(backend: str = "lax"):
    """Per wire format (``CommConfig.wire_format``): the format-optimal
    bucket, the predicted roundtrip at it, the reduce-side bytes on the
    wire (the broadcast side always stays dense fp32 — weights), and the
    predicted crossover: the smallest sweep bucket at which the format's
    roundtrip beats fp32's AT THE SAME BUCKET.  In the §3.2 wire-only model
    a compressed format wins at every bucket (only the bandwidth term
    shrinks), so the predicted crossover is the sweep floor — the measured
    rows record where the quantize/select compute actually pays for itself
    on a real schedule."""
    out = []
    for net in ("vgg-a", "overfeat-fast"):
        leaves, _ = grad_tree(net)
        total = sum(_size(lyr) for lyr in leaves) * SIZE_F32
        n_tensors = len(leaves)
        for hw, tag in ((FDR, "FDR"), (GBE, "10GbE")):
            pre = f"comm/{net}/{backend}/{tag}"
            for fmt in WIRE_FORMATS:
                b_star = optimal_bucket_bytes(total, G, hw, wire_format=fmt,
                                              topk_ratio=TOPK_RATIO)
                plan = plan_buckets(leaves, G, int(b_star))
                t = compressed_allreduce_time(
                    total, n_tensors, b_star, G, hw, wire_format=fmt,
                    topk_ratio=TOPK_RATIO, n_coll=plan.n_collectives,
                    backend=backend)
                rbytes = wire_reduce_bytes(total, G, plan.n_collectives,
                                           fmt, TOPK_RATIO)
                out.append((f"{pre}/wire_{fmt}_ms", t * 1e3,
                            f"opt_bucket_MiB={b_star / MIB:.2f};"
                            f"n_coll={plan.n_collectives}"))
                out.append((f"{pre}/wire_{fmt}_reduce_MiB", rbytes / MIB,
                            f"factor_vs_fp32={rbytes / total:.4f}"))
                cross = -1.0
                for mib in SWEEP_MIB:
                    p = plan_buckets(leaves, G, int(mib * MIB))
                    t_fmt = compressed_allreduce_time(
                        total, n_tensors, mib * MIB, G, hw, wire_format=fmt,
                        topk_ratio=TOPK_RATIO, n_coll=p.n_collectives,
                        backend=backend)
                    t_fp32 = compressed_allreduce_time(
                        total, n_tensors, mib * MIB, G, hw,
                        n_coll=p.n_collectives, backend=backend)
                    if t_fmt <= t_fp32:
                        cross = mib
                        break
                out.append((f"{pre}/wire_{fmt}_crossover_MiB", cross,
                            "smallest sweep bucket beating fp32 "
                            "(predicted; -1 = never)"))
    return out


# ---------------------------------------------------------------------------
# measured: the real executable schedule on a forced host mesh
# ---------------------------------------------------------------------------
_MEASURE_SNIPPET = """
    import time

    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType, PartitionSpec as P

    from repro.api import adapter_for
    from repro.comm import make_schedule, pack_bucket, plan_buckets
    from repro.configs import get_config, smoke_variant

    BACKEND = {backend!r}
    G = {devices}
    cfg = smoke_variant(get_config("vgg-a"))
    params = adapter_for(cfg).init(cfg, jax.random.PRNGKey(0))
    flat = tuple(jax.tree.leaves(params))
    mesh = jax.make_mesh((G,), ("data",), axis_types=(AxisType.Auto,))

    for fmt in {fmts}:
        sched = make_schedule("data", backend=BACKEND, wire_format=fmt)
        for mib in {mibs}:
            plan = plan_buckets(params, G, int(mib * 2**20))

            def roundtrip(leaves):
                bufs = [pack_bucket(leaves, b) for b in plan.buckets]
                return [sched.broadcast(sched.reduce(buf) / G)
                        for buf in bufs]

            specs = jax.tree.map(lambda _: P(), flat)
            fn = jax.jit(jax.shard_map(roundtrip, mesh=mesh,
                                       in_specs=(specs,),
                                       out_specs=P(), check_vma=False))
            with jax.set_mesh(mesh):
                jax.block_until_ready(fn(flat))          # compile
                best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(flat))
                    best = min(best, time.perf_counter() - t0)
            print(f"MEASURED fmt={{fmt}} mib={{mib}} ms={{best * 1e3:.4f}} "
                  f"n_coll={{plan.n_collectives}} "
                  f"bytes={{plan.total_padded * 4}}")
"""


def measured_rows(backend: str = "lax", devices: int = MEASURED_DEVICES):
    """Wall-clock the real ``FlatSchedule(backend)`` bucket round-trip over
    the vgg-a SMOKE tree on ``devices`` forced host CPU devices (subprocess
    so the forced device count never leaks into the caller, and it never
    takes a chip — these rows are CPU figures), per wire format,
    paired with the §3.2 model's prediction for the same plan in the
    derived column.  Adds per-format measured CROSSOVER rows: the smallest
    measured bucket where the compressed roundtrip actually beats fp32
    (-1 = never — on a host mesh the shared-memory 'wire' is nearly free,
    so the quantize/select compute usually dominates; on real links the
    bandwidth win flips it, which is exactly what the crossover row
    tracks)."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
        PYTHONPATH=os.pathsep.join(
            p for p in (os.path.join(os.path.dirname(__file__), "..", "src"),
                        os.environ.get("PYTHONPATH")) if p))
    code = textwrap.dedent(
        _MEASURE_SNIPPET.format(backend=backend, devices=devices,
                                mibs=MEASURED_MIB, fmts=MEASURED_FORMATS))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    if proc.returncode != 0:
        raise RuntimeError(f"measure subprocess failed:\n{proc.stderr[-2000:]}")
    out = []
    ms_by = {}
    for line in proc.stdout.splitlines():
        m = re.match(r"MEASURED fmt=(\w+) mib=([\d.]+) ms=([\d.]+) "
                     r"n_coll=(\d+) bytes=(\d+)", line)
        if not m:
            continue
        fmt, mib, ms, n_coll, nbytes = (m.group(1), float(m.group(2)),
                                        float(m.group(3)), int(m.group(4)),
                                        int(m.group(5)))
        pred = compressed_allreduce_time(
            nbytes, n_coll, mib * MIB, devices, FDR, wire_format=fmt,
            topk_ratio=TOPK_RATIO, n_coll=n_coll, backend=backend)
        ms_by[(fmt, mib)] = ms
        out.append((f"comm/vgg-a-smoke/{backend}/measured_{fmt}_{mib}MiB_ms",
                    ms,
                    f"predicted_FDR_ms={pred * 1e3:.4f};n_coll={n_coll};"
                    f"G={devices}"))
    for fmt in MEASURED_FORMATS:
        if fmt == "fp32":
            continue
        cross = next((mib for mib in MEASURED_MIB
                      if (fmt, mib) in ms_by and ("fp32", mib) in ms_by
                      and ms_by[(fmt, mib)] <= ms_by[("fp32", mib)]), -1.0)
        out.append((f"comm/vgg-a-smoke/{backend}/measured_crossover_"
                    f"{fmt}_MiB", float(cross),
                    "smallest measured bucket beating fp32 (-1 = never; "
                    "host-mesh wall clock, advisory)"))
    return out


def report(backends, measured: bool = True) -> dict:
    """The persisted BENCH_comm.json payload: every predicted and measured
    row per backend, plus the regression gates CI asserts.

    The gates sit on the PREDICTED side only — the §3.2 model is
    deterministic, so ``bucketed faster than per-tensor`` and ``two-level
    faster than one flat 128-ring`` must hold on every run; the measured
    host-mesh wall clocks are recorded for trend inspection but not hard-
    gated (CPU wall clock at smoke scale is runner-noise-bound, and the
    bucketing win is a latency-term effect the forced host mesh does not
    reproduce)."""
    out = {"benchmark": "comm_bucket_sweep",
           "predicted": {}, "measured": {}, "gates": {}}
    speedups, hiers, reductions = {}, {}, {}
    for backend in backends:
        pred = {}
        for name, v, derived in rows(backend) + wire_rows(backend):
            pred[name] = {"value": v, "derived": derived}
        out["predicted"][backend] = pred
        for net in ("vgg-a", "overfeat-fast"):
            pre = f"comm/{net}/{backend}"
            for tag in ("FDR", "10GbE"):
                t0 = pred[f"{pre}/{tag}/per_tensor_ms"]["value"]
                tb = pred[f"{pre}/{tag}/bucket_4.0MiB_ms"]["value"]
                speedups[f"{net}/{tag}/{backend}"] = t0 / tb
            hiers[f"{net}/{backend}"] = (
                pred[f"{pre}/hier128_flat_ms"]["value"]
                / pred[f"{pre}/hier128_two_level_ms"]["value"])
            # the acceptance gate counts REDUCE-side wire bytes at each
            # format's own optimal bucket (the broadcast side is identical
            # dense fp32 for every format, so it cancels)
            reductions[f"{net}/{backend}"] = (
                pred[f"{pre}/FDR/wire_fp32_reduce_MiB"]["value"]
                / pred[f"{pre}/FDR/wire_int8_reduce_MiB"]["value"])
        if measured:
            out["measured"][backend] = {
                name: {"value": v, "derived": derived}
                for name, v, derived in measured_rows(backend)}
    out["gates"] = {
        "predicted_bucketed_speedup": speedups,
        "predicted_hier128_speedup": hiers,
        "predicted_int8_bytes_reduction": reductions,
        "min_predicted_bucketed_speedup": min(speedups.values()),
        "min_predicted_hier128_speedup": min(hiers.values()),
        "min_predicted_int8_bytes_reduction": min(reductions.values()),
    }
    return out


def main(argv=None):
    import argparse
    import json
    import os.path

    from repro.comm import COLLECTIVE_BACKENDS
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="lax",
                    choices=list(COLLECTIVE_BACKENDS))
    ap.add_argument("--no-measured", action="store_true",
                    help="skip the host-mesh wall-clock section "
                         "(model-predicted rows only)")
    ap.add_argument("--out", default=None,
                    help="also sweep EVERY backend and persist the full "
                         "predicted-vs-measured report + regression gates "
                         "as JSON (CI: benchmarks/BENCH_comm.json)")
    args = ap.parse_args(argv)
    print(f"{'metric':48s} {'value':>12s}  derived")
    all_rows = rows(args.backend) + wire_rows(args.backend)
    if not args.no_measured:
        all_rows += measured_rows(args.backend)
    for name, v, derived in all_rows:
        print(f"{name:48s} {v:12.4f}  {derived}")
    if args.out:
        rep = report(list(COLLECTIVE_BACKENDS),
                     measured=not args.no_measured)
        out = args.out if os.path.isabs(args.out) else os.path.join(
            os.path.dirname(os.path.abspath(__file__)), args.out)
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "w") as f:
            json.dump(rep, f, indent=2)
            f.write("\n")
        print(f"# wrote {out}  "
              f"(min bucketed speedup "
              f"{rep['gates']['min_predicted_bucketed_speedup']:.2f}x, "
              f"min hier128 speedup "
              f"{rep['gates']['min_predicted_hier128_speedup']:.2f}x, "
              f"min int8 bytes reduction "
              f"{rep['gates']['min_predicted_int8_bytes_reduction']:.2f}x)")
    return all_rows


if __name__ == "__main__":
    main()
