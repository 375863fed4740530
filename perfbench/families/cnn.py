"""The CNN family (VGG-A and its kin): the program's configuration built from
a configuration file, and the plain reference of the network.

The reference is straightforward ``jax.numpy``: NHWC convolutions with
HWIO weights, ReLU, 2x2 max pooling, fully connected layers, softmax
cross-entropy averaged over the rows.  Its weights come from the seed by the
same law as the program's (one normal draw per leaf, in the lexical order
of the leaf names, scaled by one over the square root of the fan-in), so
both start from the same point without sharing any array.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax


def program_config(cfg: dict):
    """The program's ``CNNConfig`` for this configuration file."""
    from repro.configs.base import CNNConfig, ConvLayerSpec
    keys = ("kind", "ifm", "ofm", "kernel", "stride", "pad", "out_hw")
    return CNNConfig(
        name=cfg["name"], source=cfg["source"],
        image_size=cfg["image_size"], num_classes=cfg["num_classes"],
        layers=tuple(ConvLayerSpec(**{k: ly[k] for k in keys if k in ly})
                     for ly in cfg["layers"]))


def leaf_shapes(cfg: dict) -> dict:
    """Leaf name -> (shape, fan_in, scale, zero), named so that lexical
    order is layer order."""
    out = {}
    for i, ly in enumerate(cfg["layers"]):
        if ly["kind"] == "conv":
            k = ly["kernel"]
            out[f"conv{i:02d}_w"] = ((k, k, ly["ifm"], ly["ofm"]), ly["ifm"],
                                     1.0 / k, False)
            out[f"conv{i:02d}_b"] = ((ly["ofm"],), 1, 1.0, True)
        elif ly["kind"] == "fc":
            out[f"fc{i:02d}_w"] = ((ly["ifm"], ly["ofm"]), ly["ifm"], 1.0,
                                   False)
            out[f"fc{i:02d}_b"] = ((ly["ofm"],), 1, 1.0, True)
    return out


def init(cfg: dict, seed: int) -> dict:
    shapes = leaf_shapes(cfg)
    names = sorted(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(names))
    params = {}
    for k, name in zip(keys, names):
        shape, fan_in, scale, zero = shapes[name]
        params[name] = (jnp.zeros(shape, jnp.float32) if zero else
                        jax.random.normal(k, shape, jnp.float32)
                        * (scale / np.sqrt(fan_in)))
    return params


def loss(params: dict, batch: dict, cfg: dict) -> jax.Array:
    h = batch["images"].astype(next(iter(params.values())).dtype)
    last = max(i for i, ly in enumerate(cfg["layers"]) if ly["kind"] == "fc")
    for i, ly in enumerate(cfg["layers"]):
        if ly["kind"] == "conv":
            p = ly["pad"]
            h = lax.conv_general_dilated(
                h, params[f"conv{i:02d}_w"], (ly["stride"], ly["stride"]),
                [(p, p), (p, p)], dimension_numbers=("NHWC", "HWIO", "NHWC"))
            h = jnp.maximum(h + params[f"conv{i:02d}_b"], 0)
        elif ly["kind"] == "pool":
            h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
        elif ly["kind"] == "fc":
            h = h.reshape(h.shape[0], -1) @ params[f"fc{i:02d}_w"] \
                + params[f"fc{i:02d}_b"]
            if i != last:
                h = jnp.maximum(h, 0)
    logits = h.astype(jnp.float32)
    labels = batch["labels"]
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - picked)
