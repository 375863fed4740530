"""The fully connected family (CD-DNN): the program's configuration built
from a configuration file, and the plain reference of the network.

The reference is straightforward ``jax.numpy``: a chain of affine layers
with sigmoid hidden units and a softmax cross-entropy over the senones,
averaged over the rows.  Its weights come from the seed by the same law as
the program's (one normal draw per leaf, in the lexical order of the leaf
names, scaled by one over the square root of the fan-in).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def program_config(cfg: dict):
    """The program's ``DNNConfig`` for this configuration file."""
    from repro.configs.base import DNNConfig
    return DNNConfig(name=cfg["name"], source=cfg["source"],
                     input_dim=cfg["input_dim"], hidden_dim=cfg["hidden_dim"],
                     num_hidden=cfg["num_hidden"],
                     output_dim=cfg["output_dim"])


def _dims(cfg: dict) -> list:
    return ([cfg["input_dim"]] + [cfg["hidden_dim"]] * cfg["num_hidden"]
            + [cfg["output_dim"]])


def init(cfg: dict, seed: int) -> dict:
    shapes = {}
    for i, (a, b) in enumerate(zip(_dims(cfg)[:-1], _dims(cfg)[1:])):
        shapes[f"fc{i:02d}_w"] = (a, b)
        shapes[f"fc{i:02d}_b"] = (b,)
    names = sorted(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(names))
    params = {}
    for k, name in zip(keys, names):
        shape = shapes[name]
        params[name] = (jnp.zeros(shape, jnp.float32) if len(shape) == 1 else
                        jax.random.normal(k, shape, jnp.float32)
                        * (1.0 / np.sqrt(shape[0])))
    return params


def loss(params: dict, batch: dict, cfg: dict) -> jax.Array:
    h = batch["frames"].astype(params["fc00_w"].dtype)
    n = cfg["num_hidden"] + 1
    for i in range(n):
        h = h @ params[f"fc{i:02d}_w"] + params[f"fc{i:02d}_b"]
        if i < n - 1:
            h = jax.nn.sigmoid(h)
    logits = h.astype(jnp.float32)
    labels = batch["senones"]
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)
