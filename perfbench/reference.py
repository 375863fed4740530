"""The plain reference of a training job: the family's network (``init``,
``loss``) under global-norm clipping and the optimizer the run names, in
plain ``jax.numpy``, computed in blocks of rows so that a batch larger than
one chip's memory still fits.

Optimizers (the traffic file's ``run["optimizer"]``; absent, momentum SGD,
the paper families' own):

- ``"sgd"``: momentum SGD, ``v <- momentum * v + g (+ weight_decay * p)``,
  ``p <- p - lr * v``; ``weight_decay`` defaults to 0.
- ``"adamw"``: AdamW with ``b1`` 0.9, ``b2`` 0.95, ``eps`` 1e-8:
  ``m <- b1 m + (1 - b1) g``, ``v <- b2 v + (1 - b2) g^2``, and at step
  ``t`` (from 1) ``p <- p - lr * (m_hat / (sqrt(v_hat) + eps) + wd * p)``
  with ``m_hat = m / (1 - b1^t)``, ``v_hat = v / (1 - b2^t)``: decoupled
  decay on every leaf, ``wd`` = ``weight_decay``, 0.01 when absent.

``dtype=float32`` is the reference proper, at ``highest`` matmul precision.
``dtype=bfloat16`` is the control: the same steps with weights, activations,
gradients, optimizer state and the update in bfloat16.  ``fraction`` < 1
plants a fault: each step's gradient and loss come from the first
``fraction`` of the batch's rows only (half the batch left out; or, with
``1/G``, one member's rows, as when the exchange between chips is left
out).

``init`` may return a flat dict or a nested tree; the params are kept by
leaf name (``compare.named_leaves``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from compare import named_leaves

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.95, 1e-8
ADAMW_WEIGHT_DECAY = 0.01


def _block_rows(n: int, block: int) -> int:
    block = min(block, n)
    while n % block:
        block -= 1
    return block


def _clip(grads, clip: float, dtype):
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in jax.tree.leaves(grads)))
    if clip > 0:
        scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree.map(lambda g: (g * scale).astype(dtype), grads)
    return grads


def _sgd(run: dict, dtype):
    lr, mom, clip = run["lr"], run["momentum"], run["grad_clip"]
    wd = run.get("weight_decay") or 0.0

    def init(params):
        return jax.tree.map(jnp.zeros_like, params)

    @jax.jit
    def update(params, vel, grads):
        grads = _clip(grads, clip, dtype)
        if wd:
            grads = jax.tree.map(lambda g, p: g + wd * p, grads, params)
        vel = jax.tree.map(lambda v, g: (mom * v + g).astype(dtype),
                           vel, grads)
        params = jax.tree.map(lambda p, v: (p - lr * v).astype(dtype),
                              params, vel)
        return params, vel
    # the step number is Adam's alone: SGD's jitted update stays as it was
    return init, lambda params, vel, grads, t: update(params, vel, grads)


def _adamw(run: dict, dtype):
    lr, clip = run["lr"], run["grad_clip"]
    wd = run.get("weight_decay")
    wd = ADAMW_WEIGHT_DECAY if wd is None else wd
    b1, b2, eps = ADAM_B1, ADAM_B2, ADAM_EPS

    def init(params):
        return (jax.tree.map(jnp.zeros_like, params),
                jax.tree.map(jnp.zeros_like, params))

    @jax.jit
    def update(params, state, grads, t):
        grads = _clip(grads, clip, dtype)
        m, v = state
        m = jax.tree.map(lambda m, g: (b1 * m + (1 - b1) * g).astype(dtype),
                         m, grads)
        v = jax.tree.map(lambda v, g: (b2 * v + (1 - b2) * g * g)
                         .astype(dtype), v, grads)
        tf = t.astype(jnp.float32)
        bc1 = (1 - b1 ** tf).astype(dtype)
        bc2 = (1 - b2 ** tf).astype(dtype)
        params = jax.tree.map(
            lambda p, m, v: (p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps)
                                       + wd * p)).astype(dtype),
            params, m, v)
        return params, (m, v)
    return init, update


OPTIMIZERS = {"sgd": _sgd, "adamw": _adamw}


def train_steps(fam, cfg: dict, run: dict, seed: int, batches, *,
                dtype=jnp.float32, fraction: float = 1.0) -> dict:
    """Run ``len(batches)`` steps from the seed's init.  Returns the loss of
    each step (taken before its update) and the params, on the host, at the
    init, after the first step and after the last, keyed by leaf name."""
    precision = "highest" if dtype == jnp.float32 else "default"
    opt_init, update = OPTIMIZERS[run.get("optimizer") or "sgd"](run, dtype)
    with jax.default_matmul_precision(precision):
        params = jax.tree.map(lambda v: v.astype(dtype), fam.init(cfg, seed))
        state = opt_init(params)
        grad_fn = jax.jit(jax.value_and_grad(
            lambda p, b: fam.loss(p, b, cfg)))

        out = {"losses": [], "p0": named_leaves(params)}
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
            params, state = update(params, state, grads,
                                   jnp.asarray(step + 1, jnp.int32))
            if step == 0:
                out["p1"] = named_leaves(params)
        out["p_last"] = named_leaves(params)
    return out
