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
- ``change1_gap`` and ``change_last_gap``: per leaf, the norm of the
  difference of the two changes, ``|dp_prog - dp_ref|``, after step 1 and
  after the last step, over the same denominator; the worst leaf counts.
  Under Adam the first step moves each element by about ``lr`` times the
  sign of its gradient, so the change's norm is nearly the same whatever
  the gradient is, and ``grad_gap`` cannot see a wrong one: these can.

Leaves whose reference gradient is under a thousandth of the median leaf's
move by round-off alone and are left out of every gap.  One leaf the
program leaves unmoved, or moves twice as far, reads about 1.

Every number is computed on every run; a cell judges and prints only those
its limits file lists.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

CHUNK = 1 << 24


def _path_key(k) -> str:
    for attr in ("key", "idx", "name"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    raise TypeError(f"unknown pytree path entry {k!r}")


def named_leaves(tree) -> Dict[str, np.ndarray]:
    """A param tree on the host, by leaf name.  A flat dict keeps its keys;
    in a nested tree each leaf is named by its path joined with ``/``,
    tuple and list indices as numbers (``blocks/0/attn/wq``).  A stacked
    leaf, shaped ``(repeats, ...)``, stays one leaf.  The program's params
    and the reference's are both named by this."""
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))
    return {"/".join(_path_key(k) for k in path): np.asarray(v)
            for path, v in flat}


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


def _change_gap(prog_before, prog_after, ref_before, ref_after) -> float:
    """``|dp_prog - dp_ref|`` of one leaf, in chunks; each change is taken
    in float32, as ``change_norms`` takes it."""
    flats = [np.ravel(a) for a in (prog_before, prog_after, ref_before,
                                   ref_after)]
    total = 0.0
    for lo in range(0, flats[0].size, CHUNK):
        pb, pa, rb, ra = (np.asarray(f[lo:lo + CHUNK], np.float32)
                          for f in flats)
        d = (pa - pb).astype(np.float64) - (ra - rb).astype(np.float64)
        total += float(d @ d)
    return float(np.sqrt(total))


def _worst_leaf(gap: Dict[str, float], ref: Dict[str, float], keep) -> float:
    median = float(np.median([ref[k] for k in keep]))
    return max(gap[k] / max(ref[k], median) for k in keep)


def readings(prog: dict, ref: dict) -> Dict[str, float]:
    """``prog`` and ``ref`` hold ``losses`` and the params ``p0``, ``p1``
    and ``p_last`` (leaf name -> host array)."""
    g_prog = change_norms(prog["p0"], prog["p1"])
    g_ref = change_norms(ref["p0"], ref["p1"])
    floor = 1e-3 * float(np.median(list(g_ref.values())))
    keep = [k for k in sorted(g_ref) if g_ref[k] >= floor]
    u_prog = change_norms(prog["p0"], prog["p_last"])
    u_ref = change_norms(ref["p0"], ref["p_last"])

    def norm_gap(p, r):
        return {k: abs(p[k] - r[k]) for k in keep}

    def change_gap(after):
        return {k: _change_gap(prog["p0"][k], prog[after][k], ref["p0"][k],
                               ref[after][k]) for k in keep}

    return {
        "init_gap": max(float(np.max(np.abs(
            np.asarray(prog["p0"][k], np.float32)
            - np.asarray(ref["p0"][k], np.float32)))) for k in ref["p0"]),
        "loss_gap": max(abs(a - b) / abs(b) for a, b in
                        zip(prog["losses"], ref["losses"])),
        "grad_gap": _worst_leaf(norm_gap(g_prog, g_ref), g_ref, keep),
        "update_gap": _worst_leaf(norm_gap(u_prog, u_ref), u_ref, keep),
        "change1_gap": _worst_leaf(change_gap("p1"), g_ref, keep),
        "change_last_gap": _worst_leaf(change_gap("p_last"), u_ref, keep),
    }


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """True when every number is finite and within its limit."""
    return all(np.isfinite(numbers[k]) and numbers[k] <= limits[k]
               for k in limits)
