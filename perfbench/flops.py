"""Operations and bytes of a training step, counted from a configuration's
shapes.

The arithmetic is the paper's section 3.1 count (Das et al. 2016): a layer
with ``ifm`` input maps, ``ofm`` output maps, a ``k x k`` kernel and an
``out x out`` output does ``ifm * ofm * k * k * out * out`` multiply-adds per
sample in the forward pass, as many again for the gradient of its input,
and as many for the gradient of its weights; one multiply-add is 2 FLOPs.
A fully connected layer is the same with ``k = out = 1``.

One departure from the paper's formula: nothing needs the gradient of the
network's input, so the first layer does the forward and weight-gradient
passes only.  That is the work the step requires, and the only work these
counts credit.
"""
from __future__ import annotations

from typing import Dict, List


def layer_macs(layer: dict) -> int:
    """Forward multiply-adds per sample of one conv or fc layer."""
    if layer["kind"] == "conv":
        k, out = layer["kernel"], layer["out_hw"]
        return layer["ifm"] * layer["ofm"] * k * k * out * out
    if layer["kind"] == "fc":
        return layer["ifm"] * layer["ofm"]
    return 0


def weighted_layers(cfg: dict) -> List[dict]:
    """The layers that hold weights, in forward order, as conv/fc dicts:
    a CNN's own list, or a DNN's chain of fc layers."""
    if "layers" in cfg:
        return [ly for ly in cfg["layers"] if ly["kind"] in ("conv", "fc")]
    dims = ([cfg["input_dim"]] + [cfg["hidden_dim"]] * cfg["num_hidden"]
            + [cfg["output_dim"]])
    return [{"kind": "fc", "ifm": a, "ofm": b}
            for a, b in zip(dims[:-1], dims[1:])]


def forward_macs(cfg: dict) -> Dict[str, int]:
    """Forward multiply-adds per sample, by kind, and their total."""
    out = {"conv": 0, "fc": 0}
    for ly in weighted_layers(cfg):
        out[ly["kind"]] += layer_macs(ly)
    out["total"] = out["conv"] + out["fc"]
    return out


def step_flops_per_sample(cfg: dict, kind: str = "total") -> int:
    """FLOPs per sample that forward and backward require, for the layers of
    ``kind`` (``conv``, ``fc`` or ``total``): 3 passes of 2 FLOPs per
    multiply-add, less the first layer's input gradient."""
    layers = weighted_layers(cfg)
    total = 0
    for i, ly in enumerate(layers):
        if kind != "total" and ly["kind"] != kind:
            continue
        passes = 2 if i == 0 else 3
        total += 2 * passes * layer_macs(ly)
    return total


def conv_least_seconds(cfg: dict, samples: int, peak_flops: float,
                       peak_bytes_per_s: float, itemsize: int = 4) -> float:
    """The least time the chip could take for the convolutions of
    ``samples`` samples, forward and backward: per layer and pass, the
    larger of its FLOPs over the peak and its bytes over the memory
    bandwidth.  Bytes are the pass's minimal traffic: the two operands read
    and the result written once, at ``itemsize`` bytes an element."""
    total = 0.0
    for i, ly in enumerate(weighted_layers(cfg)):
        if ly["kind"] != "conv":
            continue
        k, out = ly["kernel"], ly["out_hw"]
        inp = out * ly.get("stride", 1)
        x = samples * inp * inp * ly["ifm"] * itemsize
        y = samples * out * out * ly["ofm"] * itemsize
        w = k * k * ly["ifm"] * ly["ofm"] * itemsize
        flops = 2.0 * samples * layer_macs(ly)
        # forward (x, w -> y), weight gradient (x, dy -> dw) and input
        # gradient (dy, w -> dx) each touch one x-, one y- and one w-sized
        # array; the first layer has no input gradient
        passes = 2 if i == 0 else 3
        total += passes * max(flops / peak_flops,
                              (x + y + w) / peak_bytes_per_s)
    return total
