"""The one traffic generator: a pool of batches drawn from the seed, as a
traffic file describes them.

A traffic file (``traffic/<name>.json``) gives the global batch, how many
distinct batches the pool holds, each field of a batch (shape, dtype,
distribution) and the run settings of the job.  A size in a shape, or a
bound of a distribution, may name a key of the configuration file
(``"image_size"``, ``"num_classes"``) or ``"batch"``.  Every seed draws
the same sizes; only the values differ.

Distributions: ``normal`` (standard normal), ``uniform_int`` (integers in
``[0, high)``).
"""
from __future__ import annotations

import json
import os
from typing import Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _size(v, cfg: dict, traffic: dict) -> int:
    if isinstance(v, str):
        return int(traffic[v] if v in traffic else cfg[v])
    return int(v)


def make_pool(traffic: dict, cfg: dict, seed: int) -> List[Dict[str, np.ndarray]]:
    """``traffic["pool"]`` host batches with all rows distinct, drawn from
    ``seed`` with one generator, field by field in the file's order."""
    rng = np.random.default_rng(seed)
    pool = []
    for _ in range(traffic["pool"]):
        batch = {}
        for name, f in traffic["fields"].items():
            shape = tuple(_size(s, cfg, traffic) for s in f["shape"])
            if f["dist"] == "normal":
                batch[name] = rng.standard_normal(shape, dtype=np.float32)
            elif f["dist"] == "uniform_int":
                batch[name] = rng.integers(0, _size(f["high"], cfg, traffic),
                                           size=shape).astype(f["dtype"])
            else:
                raise ValueError(f"unknown distribution {f['dist']!r}")
            batch[name] = batch[name].astype(f["dtype"], copy=False)
        pool.append(batch)
    return pool
