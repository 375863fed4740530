"""Cells at a size a CPU test can hold: the families, traffic fields, run
settings and limits of the real cells, with small layers and batches."""
import copy

import harness

CNN = {
    "name": "cnn-small", "family": "cnn", "source": "test",
    "image_size": 16, "num_classes": 10, "reference_block": 8,
    "layers": [
        {"kind": "conv", "ifm": 3, "ofm": 8, "kernel": 3, "stride": 1,
         "pad": 1, "out_hw": 16},
        {"kind": "pool", "out_hw": 8},
        {"kind": "conv", "ifm": 8, "ofm": 16, "kernel": 3, "stride": 1,
         "pad": 1, "out_hw": 8},
        {"kind": "pool", "out_hw": 4},
        {"kind": "fc", "ifm": 256, "ofm": 32, "out_hw": 1},
        {"kind": "fc", "ifm": 32, "ofm": 10, "out_hw": 1},
    ],
}
DNN = {
    "name": "dnn-small", "family": "dnn", "source": "test",
    "input_dim": 40, "hidden_dim": 64, "num_hidden": 3, "output_dim": 32,
    "reference_block": 16,
}


def small_cell(name: str, cfg: dict, batch: int = 32) -> dict:
    """The cell ``name`` of the real benchmark, cut to ``cfg`` and
    ``batch``."""
    import json
    import os
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        c = harness.cell(json.load(f), name)
    c["cfg"] = copy.deepcopy(cfg)
    c["cfg"]["step_flops_per_sample"] = harness.step_flops_per_sample(cfg)
    c["traffic"] = dict(c["traffic"], batch=batch)
    return c
