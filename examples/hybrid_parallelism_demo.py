"""Hybrid-parallelism demo — the paper's core idea, end to end, on 8
simulated devices.

1. Uses the §3 balance equations to pick the optimal group count G for the
   CD-DNN layers (model parallel within a group, data parallel across).
2. Trains the CD-DNN with the EXPLICIT part-reduce/part-broadcast
   distributed optimizer (optim/dist.py) on a (G, N/G) mesh and verifies
   the loss curve is identical to serial SGD — the paper's Fig-5 property.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python examples/hybrid_parallelism_demo.py
"""
import os

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import AxisType

from repro.configs import get_config, smoke_variant, XEON_E5_2697V3
from repro.core import balance
from repro.core.sharding import ShardingCtx, ShardingRules
from repro.data import stream_for
from repro.models import dnn
from repro.optim import MomentumSGD
from repro.optim.dist import make_distributed_update

N_NODES = 8
MINIBATCH = 32


def main():
    cfg = get_config("cd-dnn")
    # --- 1. paper §3.3: pick G per layer ---
    print("paper §3.3 optimal G per CD-DNN layer (N=8, minibatch=32):")
    dims = [(cfg.input_dim, cfg.hidden_dim)] \
        + [(cfg.hidden_dim, cfg.hidden_dim)] * (cfg.num_hidden - 1) \
        + [(cfg.hidden_dim, cfg.output_dim)]
    for i, (fin, fout) in enumerate(dims):
        g = balance.optimal_group_count(N_NODES, MINIBATCH, fout)
        mp = balance.model_parallel_preferred(
            __import__("repro.configs.base", fromlist=["ConvLayerSpec"])
            .ConvLayerSpec("fc", ifm=fin, ofm=fout, kernel=1, out_hw=1),
            in_hw=1, minibatch=MINIBATCH)
        print(f"  layer {i}: {fin:5d}->{fout:5d}  G*={g}  "
              f"model-parallel preferred: {mp}")

    # --- 2. explicit part-reduce / part-broadcast training ---
    small = smoke_variant(cfg)
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    print(f"\nmesh: {dict(mesh.shape)}  (G=4 data-parallel groups x "
          f"2-way model parallel)")
    params = dnn.init_params(small, jax.random.PRNGKey(0))
    opt = MomentumSGD(momentum=0.9)
    init_fn, update_fn = make_distributed_update(opt, mesh,
                                                 data_axes=("data",))
    serial_state = opt.init(params)
    serial_params = params
    with jax.set_mesh(mesh):
        dist_state = init_fn(params)
        dist_params = params
        stream = stream_for(small, MINIBATCH, 0, seed=1)
        grad_fn = jax.jit(jax.value_and_grad(
            lambda p, b: dnn.loss_fn(p, small, b)))
        upd = jax.jit(update_fn)
        print("step   serial-loss  dist-loss   max|Δparam|")
        for step in range(10):
            batch = jax.tree.map(jnp.asarray, next(stream))
            l_s, g_s = grad_fn(serial_params, batch)
            serial_params, serial_state = opt.update(
                g_s, serial_state, serial_params, 0.05)
            l_d, g_d = grad_fn(dist_params, batch)
            dist_params, dist_state = upd(dist_params, g_d, dist_state, 0.05)
            delta = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
                jax.tree.leaves(serial_params), jax.tree.leaves(dist_params)))
            print(f"{step:4d}  {float(l_s):10.4f} {float(l_d):10.4f}"
                  f"   {delta:.2e}")
        assert delta < 1e-4, "distributed must track serial bitwise-tightly"
    print("\nsynchronous-SGD identity verified: the paper's part-reduce/"
          "part-broadcast update matches serial SGD (Fig 5 property).")


if __name__ == "__main__":
    main()
