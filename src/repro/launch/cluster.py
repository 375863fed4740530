"""Multi-host launcher: ``python -m repro.launch.cluster --processes N ...``.

One module, two roles, selected by the cluster env vars:

* **supervisor** (how you invoke it): parses the SAME run flags as
  ``repro.launch.train`` plus the cluster knobs, then hands the whole argv
  to :func:`repro.cluster.elastic.run_elastic`, which spawns N worker
  processes and supervises them — a dead worker shrinks the world and the
  run resumes from the latest checkpoint at the new size.

* **worker** (how the launcher re-invokes it, detected via
  ``REPRO_PROCESS_ID``): brings up ``jax.distributed`` from the env-var
  :class:`~repro.cluster.spec.ClusterSpec` BEFORE importing anything that
  could touch jax device state, compiles the run with
  ``MeshSpec(cluster=True)`` (the "pod" mesh axis = the process boundary)
  and trains, heartbeating every step.

    # the paper's §3.4 update across 2 real processes over gloo
    python -m repro.launch.cluster --processes 2 --arch vgg-a --smoke \\
        --steps 8 --ckpt-dir /tmp/vgg-cluster

    # chaos: SIGKILL worker 1 at step 3, watch the elastic recovery
    python -m repro.launch.cluster --processes 2 --arch vgg-a --smoke \\
        --steps 8 --ckpt-dir /tmp/vgg-chaos --chaos-kill-step 3

``--verify`` additionally trains the same spec single-process in the
supervisor and asserts the final losses agree to float tolerance — the
§3.4 strip update is G-invariant, so a REAL multi-process run must land on
the single-process trajectory (this is the end-to-end proof the cross-host
collectives compute the right thing, asserted in CI)."""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

from repro.cluster.launcher import (
    ENV_HEARTBEAT_FILE,
    ENV_RESULT_FILE,
    make_heartbeat_listener,
)
from repro.cluster.spec import ClusterSpec, in_worker, initialize

# |cluster loss - single loss| tolerance for --verify: the update is
# G-invariant in exact arithmetic; fp32 reduction-order noise over a few
# smoke steps stays orders of magnitude below this
VERIFY_TOL = 5e-3


def worker_main(args) -> int:
    """One cluster member: jax.distributed up, compile, train, report."""
    spec = ClusterSpec.from_env()
    initialize(spec)
    # imports that build jit caches come AFTER distributed init
    import jax

    from repro.api import compile_run
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.train import spec_from_args

    use_compile_cache()
    run = compile_run(spec_from_args(args, cluster=True))
    if jax.process_index() == 0:
        print(f"cluster: {spec.num_processes} processes x "
              f"{spec.local_devices} devices  "
              f"mesh={dict(run.mesh.shape) if run.mesh is not None else None}"
              f"  parallel={run.spec.parallel}")
    hb = os.environ.get(ENV_HEARTBEAT_FILE)
    if hb:
        # the heartbeat rides the telemetry "step" span (the general event
        # hook that replaced the bare on_step callback); compile_run always
        # builds a live recorder, so this works with or without --trace-dir
        run.telemetry.add_listener(make_heartbeat_listener(hb))
    hist = run.fit()
    run.close()
    if jax.process_index() == 0:
        final = hist[-1]["loss"] if hist else None
        if final is not None:
            print(f"final loss: {final:.4f}")
        result_file = os.environ.get(ENV_RESULT_FILE)
        if result_file:
            payload = {"world": spec.num_processes,
                       "steps": run.spec.steps, "final_loss": final}
            with open(result_file, "w") as f:
                json.dump(payload, f)
    return 0


def _verify_single(args) -> float:
    """The same run, single-process, fresh state (no resume): the
    G-invariance reference the cluster's final loss must match, on the
    workers' platform."""
    import dataclasses

    import jax

    from repro.api import compile_run
    from repro.cluster.launcher import WORKER_PLATFORM
    from repro.launch.train import spec_from_args

    jax.config.update("jax_platforms", WORKER_PLATFORM)
    if jax.devices()[0].platform != WORKER_PLATFORM:
        raise RuntimeError(
            f"--verify reference must run on {WORKER_PLATFORM} like the "
            f"workers, but this process already uses "
            f"{jax.devices()[0].platform}")
    spec = spec_from_args(args, cluster=False)
    # telemetry stripped: the supervisor has no REPRO_PROCESS_ID, so its
    # trace_p0.jsonl would collide with worker 0's
    spec = dataclasses.replace(spec, ckpt_dir=None, ckpt_every=0,
                               telemetry=None)
    run = compile_run(spec)
    hist = run.fit(start_step=0)
    run.close()
    return hist[-1]["loss"]


def main(argv=None) -> int:
    argv = list(sys.argv[1:]) if argv is None else list(argv)
    worker = in_worker()
    from repro.launch.train import add_run_args, check_run_args

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    add_run_args(ap, parallel_default="zero1")
    ap.add_argument("--processes", type=int, default=2,
                    help="worker processes to launch (the cross-host 'pod' "
                         "axis extent)")
    ap.add_argument("--local-devices", type=int, default=1,
                    help="devices per process (forced host devices on CPU)")
    ap.add_argument("--run-dir", default=None,
                    help="supervisor scratch dir (heartbeats, worker logs, "
                         "result); default: --ckpt-dir, else a temp dir")
    ap.add_argument("--max-restarts", type=int, default=2,
                    help="elastic relaunch budget after worker failures")
    ap.add_argument("--heartbeat-timeout", type=float, default=120.0,
                    help="seconds without progress before the supervisor "
                         "declares a hang (covers jit compile, so generous)")
    ap.add_argument("--chaos-kill-step", type=int, default=None,
                    help="chaos harness: SIGKILL one worker when its "
                         "heartbeat reaches this step (first attempt only)")
    ap.add_argument("--chaos-kill-worker", type=int, default=1)
    ap.add_argument("--grow-back", action="store_true",
                    help="relaunch failed attempts at the FULL --processes "
                         "world instead of shrinking to the survivors "
                         "(transient-failure recovery policy; any world "
                         "change invalidates the cached comm=auto plan)")
    ap.add_argument("--verify", action="store_true",
                    help="also train single-process and assert the final "
                         "losses match (G-invariance, end to end)")
    args = ap.parse_args(argv)
    check_run_args(ap, args)

    if worker:
        return worker_main(args)

    from repro.cluster.elastic import ChaosSpec, run_elastic

    if args.processes < 1:
        ap.error("--processes must be >= 1")
    run_dir = args.run_dir or args.ckpt_dir \
        or tempfile.mkdtemp(prefix="repro-cluster-")
    chaos = None
    if args.chaos_kill_step is not None:
        chaos = ChaosSpec(at_step=args.chaos_kill_step,
                          worker=args.chaos_kill_worker)
    res = run_elastic(argv, run_dir, args.processes,
                      local_devices=args.local_devices,
                      max_restarts=args.max_restarts,
                      heartbeat_timeout=args.heartbeat_timeout,
                      chaos=chaos, grow_back=args.grow_back)
    final = res.result.get("final_loss") if res.result else None
    print(f"[cluster] done: world={res.final_world} "
          f"attempts={res.attempts} final_loss={final}")
    if args.trace_dir:
        # workers each wrote trace_p<pid>.jsonl (Run.close skips the merge
        # in workers); the supervisor sees them all and merges here
        from repro.telemetry import merge_process_traces
        merged = merge_process_traces(args.trace_dir)
        if merged:
            print(f"[cluster] merged Chrome trace: {merged}")
    if args.verify:
        if final is None:
            print("[cluster] verify FAILED: no final loss reported")
            return 1
        ref = _verify_single(args)
        diff = abs(final - ref)
        ok = diff <= VERIFY_TOL
        print(f"[cluster] verify: cluster={final:.6f} single={ref:.6f} "
              f"|diff|={diff:.2e} tol={VERIFY_TOL:.0e} "
              f"{'OK' if ok else 'FAILED'}")
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
