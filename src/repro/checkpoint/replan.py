"""Re-plan zero1 strip optimizer state for a DIFFERENT world size.

An elastic restart changes G (the data-parallel group): a checkpoint saved
at G=8 holds strip leaves shaped (8, padded8/8) that a G=4 run cannot load
by shape.  But the §3.4 strip decomposition makes the conversion exact,
not approximate:

  * bucket BOUNDARIES are G-independent — ``plan_buckets`` closes buckets
    on byte capacity and dtype runs over the (world-size-agnostic) param
    tree, so both worlds agree on which elements each bucket holds; only
    ``padded_size`` (round up to a multiple of G) differs;
  * the pad tail holds zeros forever — it is zero at init, the packed
    gradient there is structurally zero (``pack_bucket`` pads with zeros),
    and the optimizer recurrences (momentum, Adam moments) keep zero at
    zero — so truncating the old pad and zero-filling the new one loses
    nothing;
  * under the hierarchical schedule rows sit in OWNER order
    (``optim.dist.owner_perm``); unpermute to value order, reslice, apply
    the new world's perm.

Combined with the G-invariance of the update itself (property-tested
against the serial optimizer), a replanned resume continues the SAME
trajectory the smaller world would have produced — which is exactly what
the chaos test asserts.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax

from repro.comm.bucketer import BucketPlan
from repro.core.collectives import padded_size
from repro.optim.dist import owner_perm


def world_meta(axes_sizes: Sequence[int], hierarchical: bool,
               bucket_bytes: int) -> Dict:
    """The JSON-able world-layout record ``ckpt.save`` stores under
    ``meta["zero1"]`` — everything ``replan_strip_state`` needs to undo
    the saved layout."""
    sizes = [int(s) for s in axes_sizes]
    g = 1
    for s in sizes:
        g *= s
    return {"G": g, "axes_sizes": sizes, "hierarchical": bool(hierarchical),
            "bucket_bytes": int(bucket_bytes)}


def _perm(world: Dict) -> Optional[np.ndarray]:
    return owner_perm(world["hierarchical"], world["axes_sizes"])


def replan_strip_leaf(arr: np.ndarray, payload: int, old_world: Dict,
                      new_world: Dict) -> np.ndarray:
    """One (G_old, padded_old/G_old) strip leaf -> (G_new, padded_new/G_new).

    ``payload`` is the bucket's real element count (G-independent); the
    regions beyond it are the always-zero pad.  A row-blocked leaf
    (G_old, rows/G_old, cols) holds its strips' elements in the 1-D
    order, so it is read as (G_old, -1)."""
    g_old, g_new = old_world["G"], new_world["G"]
    if arr.ndim < 2 or arr.shape[0] != g_old:
        raise ValueError(
            f"strip leaf has shape {arr.shape}, expected ({g_old}, ...) "
            f"for the saved world size {g_old}")
    arr = arr.reshape(g_old, -1)
    if arr.size != padded_size(payload, g_old):
        raise ValueError(
            f"strip leaf holds {arr.size} elements, bucket payload "
            f"{payload} at G={g_old} implies {padded_size(payload, g_old)} "
            "— bucket plans disagree (different bucket_bytes or params?)")
    p_old = _perm(old_world)
    if p_old is not None:
        # stored row j is strip p_old[j]; argsort inverts back to value order
        arr = arr[np.argsort(p_old)]
    flat = arr.reshape(-1)[:payload]
    out = np.zeros(padded_size(payload, g_new), dtype=arr.dtype)
    out[:payload] = flat
    out = out.reshape(g_new, -1)
    p_new = _perm(new_world)
    if p_new is not None:
        out = out[p_new]
    return out


def replan_strip_state(template_state, old_leaves: List[np.ndarray],
                       plan: BucketPlan, old_world: Dict, new_world: Dict):
    """Convert a saved opt_state (flattened as ``old_leaves``, the old
    world's shapes) into ``template_state``'s structure and the new world's
    strip shapes.

    The tree STRUCTURE is world-size-invariant (same optimizer, same bucket
    count), so leaves pair up positionally; leaves with ndim >= 2 are strip
    tensors cycling through the buckets in plan order (optimizer state is
    field-major: momentum[b0], momentum[b1], ..., m[b0], m[b1], ...), and
    everything else (e.g. the AdamW step count) passes through unchanged.
    A strip leaf takes the template's shape, so a bucket row-blocked in
    one world and flat in the other converts too.
    """
    if old_world.get("bucket_bytes") != new_world.get("bucket_bytes"):
        raise ValueError(
            f"cannot replan across bucket_bytes change: checkpoint has "
            f"{old_world.get('bucket_bytes')}, run has "
            f"{new_world.get('bucket_bytes')} (bucket boundaries are only "
            "G-independent for a fixed byte capacity)")
    flat_tpl, treedef = jax.tree.flatten(template_state)
    if len(flat_tpl) != len(old_leaves):
        raise ValueError(
            f"opt_state has {len(flat_tpl)} leaves, checkpoint has "
            f"{len(old_leaves)} — tree structure changed since the save")
    payloads = [b.size for b in plan.buckets]
    out = []
    strip_i = 0
    for tpl, old in zip(flat_tpl, old_leaves):
        old = np.asarray(old)
        if getattr(tpl, "ndim", 0) >= 2:
            new = replan_strip_leaf(old, payloads[strip_i % len(payloads)],
                                    old_world, new_world)
            strip_i += 1
            if new.size != math.prod(tpl.shape):
                raise ValueError(
                    f"replanned strip has shape {new.shape}, template "
                    f"expects {tuple(tpl.shape)}")
            new = new.reshape(tpl.shape)
            out.append(new.astype(np.asarray(tpl).dtype
                                  if not hasattr(tpl, "dtype")
                                  else tpl.dtype))
        else:
            out.append(old.reshape(getattr(tpl, "shape", old.shape)))
    if strip_i and strip_i % len(payloads):
        raise ValueError(
            f"saw {strip_i} strip leaves for {len(payloads)} buckets — "
            "state fields are not whole multiples of the bucket count")
    return jax.tree.unflatten(treedef, out)
