"""Training launcher: ``python -m repro.launch.train --arch <id> [--smoke]``.

A thin argparse shim over the declarative run-assembly API: flags build a
``repro.api.RunSpec``, ``compile_run`` does the assembly (family resolution,
mesh, placement, update-path selection), and ``Run.fit`` trains.

    # the paper's §3.4 strip update through the bucketed comm subsystem,
    # with each bucket's reduce issued inside backprop (§3.1 overlap)
    python -m repro.launch.train --arch vgg-a --smoke \\
        --parallel zero1 --bucket-mb 4 --wire-dtype bf16 --overlap

    # same, on the explicit Pallas ring collectives instead of lax
    python -m repro.launch.train --arch vgg-a --smoke \\
        --parallel zero1 --comm-backend pallas-ring

    # compressed bytes-on-wire: int8-quantized hops fused into the ring
    # (or --wire-format topk for sparsified + error-feedback)
    python -m repro.launch.train --arch vgg-a --smoke \\
        --parallel zero1 --comm-backend pallas-ring --wire-format int8

    # the relaxed-consistency modes on the same pipeline: bounded
    # staleness (apply last step's reduce) / GossipGraD partner exchange
    python -m repro.launch.train --arch vgg-a --smoke --parallel stale-sync
    python -m repro.launch.train --arch vgg-a --smoke --parallel gossip

A ``--ckpt-dir`` run periodically checkpoints AND auto-resumes: relaunching
the same command picks up from the latest saved step (params, optimizer
strips and data-stream position), not from step 0.

Without --smoke the config is the published full width; on a TPU host it
shards across the detected devices (``chip_smoke.py`` at the repo root
drives full-width vgg-a this way)."""
from __future__ import annotations

import argparse

from repro.api import (
    MIB,
    MODE_CAPS,
    PARALLEL_MODES,
    SCHEDULES,
    MeshSpec,
    RunSpec,
    compile_run,
)
from repro.comm import COLLECTIVE_BACKENDS, WIRE_FORMATS, CommConfig
from repro.configs import ALL_ARCHS
from repro.launch.compile_cache import use_compile_cache

WIRE_DTYPES = {"fp32": "float32", "bf16": "bfloat16"}


def comm_flags_set(args) -> bool:
    """True when any explicit-bucketed-collectives flag departs from its
    default (these require a comm-capable --parallel mode — see
    ``MODE_CAPS``)."""
    return (args.bucket_mb is not None or args.wire_dtype != "fp32"
            or args.overlap or args.comm_backend != "lax"
            or args.cross_backend is not None
            or args.wire_format is not None)


def spec_from_args(args, cluster: bool = False) -> RunSpec:
    comm = None
    if getattr(args, "comm", None) == "auto":
        # measured-feedback autotune: compile_run times the real per-bucket
        # collectives and picks bucket size/backend (repro.telemetry.autotune)
        comm = "auto"
    elif comm_flags_set(args):
        caps = MODE_CAPS[args.parallel]
        bucket_mb = 4.0 if args.bucket_mb is None else args.bucket_mb
        # the argparse default "lax" means "the mode's default backend" —
        # gossip's semantics live in its backend, so the name maps there
        backend = args.comm_backend
        if backend == "lax" and caps.default_backend is not None:
            backend = caps.default_backend
        # gossip stays flat even multi-pod: a hierarchical schedule would
        # scope the partner rotation to each pod (see api.assemble)
        hierarchical = ((args.pods > 1 or cluster)
                        and args.parallel != "gossip")
        comm = CommConfig(bucket_bytes=int(bucket_mb * MIB),
                          reduce_dtype=WIRE_DTYPES[args.wire_dtype],
                          hierarchical=hierarchical,
                          overlap=args.overlap,
                          backend=backend,
                          cross_backend=args.cross_backend or "lax",
                          wire_format=args.wire_format,
                          topk_ratio=args.topk_ratio)
    ckpt_every = 0
    if args.ckpt_dir:
        ckpt_every = args.ckpt_every if args.ckpt_every \
            else max(args.steps // 5, 1)
    return RunSpec(
        arch=args.arch, smoke=args.smoke, parallel=args.parallel,
        mesh=MeshSpec(pods=args.pods, model_ways=args.model_ways,
                      cluster=cluster),
        comm=comm, optimizer=args.optimizer, lr=args.lr,
        schedule=args.schedule,
        steps=args.steps, batch=args.batch, seq=args.seq, seed=args.seed,
        log_every=args.log_every, ckpt_every=ckpt_every,
        ckpt_dir=args.ckpt_dir,
        telemetry=getattr(args, "trace_dir", None))


def add_run_args(ap: argparse.ArgumentParser, parallel_default: str = "dp"):
    """The training-run flag set, shared with the multi-host launcher
    (``repro.launch.cluster``) so a cluster run is configured with exactly
    the flags a single-process run is."""
    ap.add_argument("--arch", required=True, choices=list(ALL_ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--schedule", default="warmup_cosine",
                    choices=list(SCHEDULES),
                    help="LR schedule; linear-scale-warmup is Goyal et "
                         "al.'s large-batch recipe (peak = lr x the "
                         "data-parallel ways, gradual warmup from lr)")
    ap.add_argument("--parallel", default=parallel_default,
                    choices=list(PARALLEL_MODES),
                    help="serial | dp (pjit/GSPMD) | zero1 (explicit "
                         "bucketed §3.4 strips) | zero1-gspmd | stale-sync "
                         "(bounded staleness: apply last step's reduce) | "
                         "gossip (GossipGraD rotating partner exchange)")
    ap.add_argument("--pods", type=int, default=1,
                    help="pod axis extent (>1 adds the cross-pod "
                         "hierarchical hop)")
    ap.add_argument("--model-ways", type=int, default=1)
    ap.add_argument("--bucket-mb", type=float, default=None,
                    help="fusion-buffer size in MiB for --parallel zero1 "
                         "(default 4)")
    ap.add_argument("--wire-dtype", default="fp32", choices=list(WIRE_DTYPES),
                    help="gradient part-reduce wire dtype (zero1)")
    ap.add_argument("--wire-format", default=None,
                    choices=list(WIRE_FORMATS),
                    help="gradient bytes-on-wire encoding: fp32/bf16 "
                         "(dense), int8 (per-message scales, f32 "
                         "accumulate per hop), topk ((values, indices) "
                         "sparse messages + error-feedback residual; "
                         "zero1 only).  Default: derived from --wire-dtype")
    ap.add_argument("--topk-ratio", type=float, default=0.05,
                    help="fraction of entries kept per message under "
                         "--wire-format topk")
    ap.add_argument("--overlap", action="store_true",
                    help="issue each bucket's part-reduce inside the "
                         "backward pass (§3.1 bubble schedule) instead of "
                         "reducing after value_and_grad (zero1)")
    ap.add_argument("--comm-backend", default="lax",
                    choices=list(COLLECTIVE_BACKENDS),
                    help="collective implementation for the zero1 "
                         "schedules: lax (XLA collectives) or pallas-ring "
                         "(the paper's explicit §3.4 ring; in-pod only "
                         "under --pods>1, the cross-pod hop stays lax)")
    ap.add_argument("--cross-backend", default=None,
                    choices=list(COLLECTIVE_BACKENDS),
                    help="collective implementation for the CROSS-POD hop "
                         "of the hierarchical schedule (default lax — the "
                         "right tool on the slow inter-pod/cross-host link)")
    ap.add_argument("--comm", default=None, choices=["auto"],
                    help="comm='auto': measure the real per-bucket "
                         "collectives at assembly time and autotune bucket "
                         "size + backend from the §3.2 balance model "
                         "(replaces the explicit comm flags)")
    ap.add_argument("--trace-dir", default=None,
                    help="write a per-process telemetry trace (JSONL) and a "
                         "merged Chrome trace (trace.json, load in "
                         "chrome://tracing or Perfetto) to this directory")
    ap.add_argument("--optimizer", default=None,
                    choices=["adamw", "sgd"],
                    help="default: family choice (momentum SGD for the "
                         "paper's CNN/DNN, AdamW for transformers)")
    ap.add_argument("--log-every", type=int, default=5,
                    help="log (and record in the history) every N steps; "
                         "the first and last steps always log")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0,
                    help="checkpoint period in steps (default: steps/5 "
                         "when --ckpt-dir is set)")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def check_run_args(ap: argparse.ArgumentParser, args) -> None:
    """Flag compatibility, read off the declarative ``MODE_CAPS`` table —
    the same source ``RunSpec`` validates against, so the launcher and the
    API can never disagree on what a mode supports."""
    caps = MODE_CAPS[args.parallel]
    if getattr(args, "comm", None) == "auto":
        if comm_flags_set(args):
            ap.error("--comm auto autotunes the bucket size and backend "
                     "from measurement; it cannot be combined with the "
                     "explicit comm flags (--bucket-mb / --wire-dtype / "
                     "--overlap / --comm-backend / --cross-backend)")
        if not caps.comm:
            commful = [m for m, c in MODE_CAPS.items() if c.comm]
            ap.error("--comm auto measures the explicit bucketed "
                     f"collectives, which --parallel {args.parallel} does "
                     f"not use; pick one of {commful}")
    if comm_flags_set(args) and not caps.comm:
        commful = [m for m, c in MODE_CAPS.items() if c.comm]
        ap.error("--bucket-mb / --wire-dtype / --overlap / --comm-backend "
                 "/ --cross-backend configure the explicit bucketed "
                 f"collectives, which --parallel {args.parallel} does not "
                 f"use; pick one of {commful}")
    if args.overlap and not caps.overlap:
        overlappy = [m for m, c in MODE_CAPS.items() if c.overlap]
        ap.error("--overlap (the §3.1 backward-pass reduce schedule) is "
                 f"only supported by {overlappy}, not --parallel "
                 f"{args.parallel}")
    if (caps.backends is not None and args.comm_backend != "lax"
            and args.comm_backend not in caps.backends):
        ap.error(f"--comm-backend {args.comm_backend} is not valid under "
                 f"--parallel {args.parallel}; this mode supports "
                 f"{list(caps.backends)}")
    if (args.wire_format is not None and caps.wire_formats is not None
            and args.wire_format not in caps.wire_formats):
        ap.error(f"--wire-format {args.wire_format} is not valid under "
                 f"--parallel {args.parallel}; this mode supports "
                 f"{list(caps.wire_formats)}")
    if args.wire_format == "topk" and args.overlap:
        ap.error("--wire-format topk cannot run with --overlap: the "
                 "backward-pass reduce taps are stateless, so the "
                 "error-feedback residual has nowhere to live")


def parse_run_spec(argv=None) -> RunSpec:
    """The ``RunSpec`` this launcher builds from ``argv``."""
    ap = argparse.ArgumentParser()
    add_run_args(ap)
    args = ap.parse_args(argv)
    check_run_args(ap, args)
    return spec_from_args(args)


def main(argv=None):
    spec = parse_run_spec(argv)
    use_compile_cache()
    run = compile_run(spec)
    # report the RESOLVED comm plan (run.comm), not spec.comm — the spec may
    # say the string "auto", the run carries what the autotuner picked
    print(f"arch: {run.cfg.name}  family={run.family.family}  "
          f"parallel={run.spec.parallel}  "
          f"overlap={run.comm.overlap if run.comm else False}  "
          f"backend={run.comm.backend if run.comm else 'lax'}  "
          f"mesh={dict(run.mesh.shape) if run.mesh is not None else None}")
    hist = run.fit()   # auto-resumes from the latest --ckpt-dir checkpoint
    run.close()
    if hist:
        print(f"final loss: {hist[-1]['loss']:.4f}")
    else:
        print("checkpoint already at or past --steps; nothing to train")
    return hist


if __name__ == "__main__":
    main()
