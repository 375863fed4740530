"""Smoke test on the TPU: train full-width VGG-A through the normal entry
points, and check what comes out.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the four chips of one host

One chip: ``repro.launch.train.main`` with ``--arch vgg-a --parallel zero1
--batch 64 --steps 6`` (no ``--smoke``: 224x224 inputs, 1000 classes), every
step logged.  It prints the device kind, the compile seconds the trainer
reports, the per-step losses and the device's ``peak_bytes_in_use``, and
checks that every loss is finite.

Four chips: the cross-chip path only.  VGG-A zero1 on a (4, 1) data mesh
with the lax backend, the Pallas ring, and the Pallas ring with int8 wire
messages, each against ``--parallel serial`` on one chip at the same global
batch, seed and constant LR.  The fp32 backends must match the serial final
loss within ``repro.launch.cluster.VERIFY_TOL``, int8 within 1 %.  Each
must start from serial's params and end near serial's: the distance to
serial's final params is at most ``FP32_PARAM_TOL`` (``INT8_PARAM_TOL``)
of serial's own move from the init, and every device holds the same
params.  Params and the zero1 strip state must sit on all four devices.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or on any failure, the script exits non-zero without it.
What it prints is a smoke check, not a benchmark metric.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ONE_CHIP_ARGV = ["--arch", "vgg-a", "--parallel", "zero1", "--batch", "64",
                 "--steps", "6", "--log-every", "1"]
# global batch 128 = 32 per chip on four; the serial reference takes all
# 128 on one chip
FOUR_CHIP_STEPS = 4
FOUR_CHIP_ARGV = ["--arch", "vgg-a", "--batch", "128",
                  "--steps", str(FOUR_CHIP_STEPS), "--schedule", "constant",
                  "--seed", "0", "--log-every", "1"]
FOUR_CHIP_BACKENDS = {
    "lax": ["--comm-backend", "lax"],
    "pallas-ring": ["--comm-backend", "pallas-ring"],
    "pallas-ring+int8": ["--comm-backend", "pallas-ring",
                         "--wire-format", "int8"],
}
INT8_REL_TOL = 0.01
# a few steps barely move the loss of 1000 classes, so the params decide:
# |zero1 params - serial params| as a share of |serial params - init|.  A
# missing update scores 1, one member's own gradient ~1.7, a sum in place
# of the mean 3.  Under the TPU's default precision one chip at batch 128
# and four at 32 already differ by 0.058 for lax on a v5e; int8 wire
# messages add their quantization error to every reduced gradient
FP32_PARAM_TOL = 0.25
INT8_PARAM_TOL = 0.5


def tpu_devices():
    """All devices, or exit non-zero when JAX finds no TPU: there is no CPU
    fallback."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found {len(devs)} "
                 f"{devs[0].platform} device(s))")
    return devs


def device_bytes(dev, key: str) -> int:
    """One counter of ``dev.memory_stats()`` (``bytes_in_use``,
    ``peak_bytes_in_use``)."""
    return dev.memory_stats()[key]


def vgg_a_report() -> None:
    from repro.api.families import CNN_FAMILY
    from repro.configs import get_config
    cfg = get_config("vgg-a")
    n = sum(math.prod(s.shape)
            for s in CNN_FAMILY.param_specs(cfg).values())
    print(f"vgg-a: {n / 1e6:.1f} M params, {cfg.image_size}x"
          f"{cfg.image_size} inputs, {cfg.num_classes} classes")


def check_losses(name: str, hist, steps: int) -> list:
    losses = [h["loss"] for h in hist]
    print(f"{name}: compile {hist[0]['compile_s']:.2f} s, losses {losses}")
    if len(losses) != steps or not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"{name}: expected {steps} finite losses, "
                             f"got {losses}")
    return losses


def one_chip(dev) -> None:
    from repro.launch.train import main as train_main
    vgg_a_report()
    hist = train_main(ONE_CHIP_ARGV)
    check_losses("zero1 one chip", hist, 6)
    print(f"peak_bytes_in_use: {device_bytes(dev, 'peak_bytes_in_use')}")


def _compile(extra):
    from repro.api import compile_run
    from repro.launch.train import parse_run_spec
    return compile_run(parse_run_spec(FOUR_CHIP_ARGV + extra))


def check_spread(run, devs) -> None:
    """Params replicated and every zero1 strip sharded over all devices."""
    import jax
    want = set(devs)
    for tree in (run.params, run.opt_state):
        for leaf in jax.tree.leaves(tree):
            if leaf.sharding.device_set != want:
                raise AssertionError(
                    f"array {leaf.shape} sits on "
                    f"{sorted(d.id for d in leaf.sharding.device_set)}")
    strips = [s for s in jax.tree.leaves(run.opt_state) if s.ndim >= 2]
    for s in strips:
        rows = {sh.data.shape[0] for sh in s.addressable_shards}
        if rows != {s.shape[0] // len(devs)}:
            raise AssertionError(f"strip {s.shape} is not split by rows: "
                                 f"shard rows {rows}")
    param_bytes = sum(p.nbytes for p in jax.tree.leaves(run.params))
    for d in devs:
        used = device_bytes(d, "bytes_in_use")
        print(f"  device {d.id}: bytes_in_use {used}")
        if used < param_bytes:
            raise AssertionError(f"device {d.id} holds {used} bytes, less "
                                 f"than the {param_bytes} of params")
    print(f"  {len(strips)} zero1 strips over {len(devs)} devices, "
          f"params {param_bytes} bytes on each")


def host_params(run) -> list:
    """The run's param leaves copied to the host (a replicated leaf: one
    device's copy)."""
    import jax
    import numpy as np
    return [np.asarray(x) for x in jax.tree.leaves(jax.device_get(run.params))]


def distance(a: list, b: list) -> float:
    """Global L2 norm of ``a - b`` over all leaves, summed in float64."""
    import numpy as np
    return math.sqrt(sum(float(np.sum((x.astype(np.float64) - y) ** 2))
                         for x, y in zip(a, b)))


def check_replicas(run) -> None:
    """Every device holds the same bits of every param: the zero1 broadcast
    reached each member."""
    import numpy as np
    import jax
    for leaf in jax.tree.leaves(run.params):
        shards = leaf.addressable_shards
        first = np.asarray(shards[0].data)
        for sh in shards[1:]:
            if sh.data.shape != leaf.shape or not np.array_equal(
                    np.asarray(sh.data), first):
                raise AssertionError(f"param {leaf.shape}: device "
                                     f"{sh.device.id} differs from device "
                                     f"{shards[0].device.id}")


def four_chips(devs) -> None:
    from repro.launch.cluster import VERIFY_TOL
    if len(devs) != 4:
        raise AssertionError(f"--chips 4 needs 4 devices, JAX found "
                             f"{len(devs)}")
    vgg_a_report()
    run = _compile(["--parallel", "serial"])
    p0 = host_params(run)
    ref = check_losses("serial one chip", run.fit(), FOUR_CHIP_STEPS)[-1]
    p_ref = host_params(run)
    moved = distance(p_ref, p0)
    print(f"serial one chip: |params - init| {moved}")
    if not moved > 0:
        raise AssertionError("serial: the params did not move")
    run.close()
    del run
    gc.collect()
    for name, extra in FOUR_CHIP_BACKENDS.items():
        int8 = "int8" in name
        run = _compile(["--parallel", "zero1"] + extra)
        if dict(run.mesh.shape) != {"data": 4, "model": 1}:
            raise AssertionError(f"{name}: mesh {dict(run.mesh.shape)}")
        print(f"zero1 {name}: mesh {dict(run.mesh.shape)} "
              f"backend={run.comm.backend} wire={run.comm.wire_format}")
        if distance(host_params(run), p0) != 0:
            raise AssertionError(f"{name}: init differs from serial's")
        check_spread(run, devs)
        got = check_losses(f"zero1 {name}", run.fit(), FOUR_CHIP_STEPS)[-1]
        diff = abs(got - ref)
        tol = INT8_REL_TOL * abs(ref) if int8 else VERIFY_TOL
        print(f"zero1 {name}: final {got} vs serial {ref}, |diff| {diff} "
              f"(tol {tol})")
        if not diff <= tol:
            raise AssertionError(f"{name}: |diff| {diff} > {tol}")
        check_replicas(run)
        rel = distance(host_params(run), p_ref) / moved
        ptol = INT8_PARAM_TOL if int8 else FP32_PARAM_TOL
        print(f"zero1 {name}: |params - serial| / |serial - init| {rel} "
              f"(tol {ptol})")
        if not rel <= ptol:
            raise AssertionError(f"{name}: params off serial's by {rel} of "
                                 f"the update > {ptol}")
        for d in devs:
            print(f"  device {d.id}: peak_bytes_in_use "
                  f"{device_bytes(d, 'peak_bytes_in_use')}")
        run.close()
        del run
        gc.collect()


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args(argv)
    # before the TPU library loads: its logs go nowhere, not to /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    devs = tpu_devices()
    from repro.launch.compile_cache import use_compile_cache
    print(f"device: {devs[0].device_kind} x{len(devs)}  "
          f"compile cache: {use_compile_cache()}")
    if args.chips == 4:
        four_chips(devs)
    else:
        one_chip(devs[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
