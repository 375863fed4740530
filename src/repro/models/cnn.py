"""The paper's CNN workloads (VGG-A, OverFeat-FAST) in JAX, NHWC.

Forward conv can route through the Pallas direct-conv kernel (§2 adapted,
``use_pallas=True``) or lax.conv (XLA); both match ``kernels.ref.conv2d_ref``.
Layer specs come straight from ``configs/vgg_a.py`` / ``overfeat_fast.py`` so
the model, the Table-1 balance benchmark and the scaling benchmarks share one
source of truth.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import CNNConfig
from repro.core.params import Spec, init_tree
from repro.core.sharding import ShardingCtx
from repro.kernels import ops as kops, ref as kref


def _key(kind: str, i: int, part: str) -> str:
    """Zero-padded layer index so LEXICAL dict-key order (what jax.tree
    flattening sorts by) equals FORWARD layer order — conv2 must not sort
    after conv10, or the comm bucket plan (tree order) interleaves first-
    and last-layer leaves and the §3.1 overlap schedule degrades to
    everything-ready-last (see repro.comm.overlap)."""
    return f"{kind}{i:02d}_{part}"


def param_specs(cfg: CNNConfig) -> Dict[str, Spec]:
    sp: Dict[str, Spec] = {}
    for i, lyr in enumerate(cfg.layers):
        if lyr.kind == "conv":
            # fan-in is kernel*kernel*ifm; init_tree's fan_in rule divides
            # by sqrt(ifm) (the (.., in, out) axis), so scale by 1/kernel
            sp[_key("conv", i, "w")] = Spec(
                (lyr.kernel, lyr.kernel, lyr.ifm, lyr.ofm),
                ("kernel", "kernel", "embed", "ff"), scale=1.0 / lyr.kernel)
            sp[_key("conv", i, "b")] = Spec((lyr.ofm,), ("ff",),
                                            init="zeros")
        elif lyr.kind == "fc":
            sp[_key("fc", i, "w")] = Spec((lyr.ifm, lyr.ofm),
                                          ("embed", "ff"))
            sp[_key("fc", i, "b")] = Spec((lyr.ofm,), ("ff",), init="zeros")
    return sp


def init_params(cfg: CNNConfig, key: jax.Array):
    return init_tree(param_specs(cfg), key)


def forward(params, cfg: CNNConfig, x: jax.Array,
            ctx: ShardingCtx = ShardingCtx(),
            use_pallas: bool = False) -> jax.Array:
    """x: (N, H, W, 3) -> logits (N, num_classes)."""
    h = x
    for i, lyr in enumerate(cfg.layers):
        if lyr.kind == "conv":
            w = params[_key("conv", i, "w")]
            if use_pallas:
                h = kops.conv2d(h, w, stride=lyr.stride, padding=lyr.pad)
            else:
                h = kref.conv2d_ref(h, w, stride=lyr.stride, padding=lyr.pad)
            h = jax.nn.relu(h + params[_key("conv", i, "b")])
            h = ctx.constrain(h, "batch", None, None, "ff")
        elif lyr.kind == "pool":
            h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
        elif lyr.kind == "fc":
            if h.ndim == 4:
                h = h.reshape(h.shape[0], -1)
            h = h @ params[_key("fc", i, "w")] \
                + params[_key("fc", i, "b")]
            last = (i == len(cfg.layers) - 1)
            if not last:
                h = jax.nn.relu(h)
    return h


def loss_fn(params, cfg: CNNConfig, batch: dict,
            ctx: ShardingCtx = ShardingCtx()) -> jax.Array:
    logits = forward(params, cfg, batch["images"], ctx)
    lf = logits.astype(jnp.float32)
    nll = jax.nn.logsumexp(lf, -1) - jnp.take_along_axis(
        lf, batch["labels"][:, None], axis=-1)[:, 0]
    return nll.mean()
