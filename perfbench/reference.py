"""The plain reference of a training job: the family's network (``init``,
``loss``) under momentum SGD with global-norm clipping, in plain
``jax.numpy``, computed in blocks of rows so that a batch larger than one
chip's memory still fits.

``dtype=float32`` is the reference proper, at ``highest`` matmul precision.
``dtype=bfloat16`` is the control: the same steps with weights, activations,
gradients and the update in bfloat16.  ``fraction`` < 1 plants a fault: each
step's gradient and loss come from the first ``fraction`` of the batch's
rows only (half the batch left out; or, with ``1/G``, one member's rows, as
when the exchange between chips is left out).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _block_rows(n: int, block: int) -> int:
    block = min(block, n)
    while n % block:
        block -= 1
    return block


def train_steps(fam, cfg: dict, run: dict, seed: int, batches, *,
                dtype=jnp.float32, fraction: float = 1.0) -> dict:
    """Run ``len(batches)`` steps from the seed's init.  Returns the loss of
    each step (taken before its update) and the params, on the host, at the
    init, after the first step and after the last, keyed by leaf name."""
    precision = "highest" if dtype == jnp.float32 else "default"
    lr, mom, clip = run["lr"], run["momentum"], run["grad_clip"]
    with jax.default_matmul_precision(precision):
        params = {k: v.astype(dtype) for k, v in fam.init(cfg, seed).items()}
        vel = jax.tree.map(jnp.zeros_like, params)
        grad_fn = jax.jit(jax.value_and_grad(
            lambda p, b: fam.loss(p, b, cfg)))

        @jax.jit
        def update(params, vel, grads):
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                 for g in jax.tree.leaves(grads)))
            if clip > 0:
                scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
                grads = jax.tree.map(lambda g: (g * scale).astype(dtype),
                                     grads)
            vel = jax.tree.map(lambda v, g: (mom * v + g).astype(dtype),
                               vel, grads)
            params = jax.tree.map(lambda p, v: (p - lr * v).astype(dtype),
                                  params, vel)
            return params, vel

        out = {"losses": [], "p0": jax.device_get(params)}
        for step, batch in enumerate(batches):
            n = next(iter(batch.values())).shape[0]
            rows = int(n * fraction)
            block = _block_rows(rows, cfg["reference_block"])
            loss, grads = 0.0, None
            for lo in range(0, rows, block):
                part = {k: jnp.asarray(v[lo:lo + block])
                        for k, v in batch.items()}
                l_b, g_b = grad_fn(params, part)
                w = block / rows
                loss = loss + w * l_b
                g_b = jax.tree.map(lambda g: (w * g).astype(dtype), g_b)
                grads = g_b if grads is None else jax.tree.map(
                    jnp.add, grads, g_b)
            out["losses"].append(float(loss))
            params, vel = update(params, vel, grads)
            if step == 0:
                out["p1"] = jax.device_get(params)
        out["p_last"] = jax.device_get(params)
    return out
