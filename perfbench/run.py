"""Run one benchmark cell once and print its result as the last line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cells are the ``workloads`` of ``BENCHMARK.json`` at the checkout's
root.  ``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1``
traces the window with the JAX profiler and prints its per-layer metrics,
the device's busy time and a breakdown.  Either way the run's first
training steps are checked against the plain reference and ``correct``
says whether every number kept within its limit; the numbers and limits
are the result's last key, ``checks``, and the last lines of standard
error.

The run needs the accelerator the cell asks for: with no TPU, fewer or
more chips than the cell's, or a device missing from ``peaks.json``, it
exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(1, os.path.join(ROOT, "src"))
# before the TPU library loads: its logs go nowhere, not to a fixed path
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    import harness
    import peaks as peaks_table
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    c = harness.cell(bench, args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"perfbench: no TPU; JAX found {len(devices)} "
                 f"{devices[0].platform} device(s)")
    if len(devices) != c["chips"]:
        sys.exit(f"perfbench: {args.workload} runs on {c['chips']} chip(s), "
                 f"JAX found {len(devices)}")
    try:
        peaks = peaks_table.lookup(devices[0].device_kind)
    except peaks_table.UnknownDevice as e:
        sys.exit(f"perfbench: {e}")
    harness.use_compile_cache()
    out = harness.run_cell(c, args.seed, args.seconds, bool(args.trace),
                           T_START, devices, peaks)
    sys.stdout.flush()
    print(json.dumps(out))


if __name__ == "__main__":
    main()
