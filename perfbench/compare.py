"""The numbers that decide ``correct``: the program's first training steps
against the reference's on the same batches from the same init.

- ``init_gap``: the largest absolute difference of the initial params.  The
  two inits follow one law from one seed, so this is exact: limit 0.
- ``loss_gap``: the largest relative gap of a step's loss.
- ``grad_gap``: the first gradient as the optimizer got it, read from the
  params' change after one step (momentum starts at zero, so that change
  is ``-lr`` times the clipped gradient).  Per leaf, the gap between the
  program's norm and the reference's, over the reference's norm of that
  leaf or of the median leaf, whichever is larger; the worst leaf counts.
- ``update_gap``: the same measure of the params' change after the last
  step.

Leaves whose reference gradient is under a thousandth of the median leaf's
move by round-off alone and are left out of both gaps.  One leaf the
program leaves unmoved, or moves twice as far, reads about 1.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

CHUNK = 1 << 24


def _norm(a: np.ndarray) -> float:
    flat = np.ravel(a)
    total = 0.0
    for lo in range(0, flat.size, CHUNK):
        part = flat[lo:lo + CHUNK].astype(np.float64)
        total += float(part @ part)
    return float(np.sqrt(total))


def change_norms(before: Dict[str, np.ndarray],
                 after: Dict[str, np.ndarray]) -> Dict[str, float]:
    return {k: _norm(np.asarray(after[k], np.float32)
                     - np.asarray(before[k], np.float32)) for k in before}


def _worst_leaf(prog: Dict[str, float], ref: Dict[str, float], keep) -> float:
    median = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], median) for k in keep)


def readings(prog: dict, ref: dict) -> Dict[str, float]:
    """``prog`` and ``ref`` hold ``losses`` and the params ``p0``, ``p1``
    and ``p_last`` (leaf name -> host array)."""
    g_prog = change_norms(prog["p0"], prog["p1"])
    g_ref = change_norms(ref["p0"], ref["p1"])
    floor = 1e-3 * float(np.median(list(g_ref.values())))
    keep = [k for k in sorted(g_ref) if g_ref[k] >= floor]
    u_prog = change_norms(prog["p0"], prog["p_last"])
    u_ref = change_norms(ref["p0"], ref["p_last"])
    return {
        "init_gap": max(float(np.max(np.abs(
            np.asarray(prog["p0"][k], np.float32)
            - np.asarray(ref["p0"][k], np.float32)))) for k in ref["p0"]),
        "loss_gap": max(abs(a - b) / abs(b) for a, b in
                        zip(prog["losses"], ref["losses"])),
        "grad_gap": _worst_leaf(g_prog, g_ref, keep),
        "update_gap": _worst_leaf(u_prog, u_ref, keep),
    }


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """True when every number is finite and within its limit."""
    return all(np.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in limits)
