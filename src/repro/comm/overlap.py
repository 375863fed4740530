"""Backprop-overlapped bucket reduction — the paper's §3.1 bubble schedule,
executable.

The monolithic zero1 path reduces the whole gradient tree only after
``value_and_grad`` returns, so every byte of communication is exposed.  The
paper's overlap model instead issues each layer's weight-gradient
communication as soon as that layer's backprop finishes: the last layer's
gradients materialize first, and all but the "bubble" of each transfer hides
under the remaining backprop (plus the next forward pass).

This module realizes that schedule per fusion BUCKET with ``jax.custom_vjp``
comm hooks.  Each bucket's leaves pass through an identity ``tap`` on the
forward pass; the tap's backward rule packs the bucket's leaf cotangents into
the fusion buffer and issues the ``part_reduce`` right there — the reduce
enters the backward graph at the point where the bucket's LAST contributing
leaf gradient materializes (``Bucket.trigger_index``), with no data
dependency on the rest of backprop, so the compiler is free to overlap it
with the remaining layers' gradient computation.

The reduced strip leaves the backward pass through a gradient side channel:
every tap takes a zero-valued fp32 ``sink`` of strip shape whose custom
cotangent IS the bucket's reduced mean-gradient strip, so
``value_and_grad(hooked_loss, argnums=sinks)`` returns the strips directly
(the same trick flax's ``Module.perturb`` uses to surface intermediate
cotangents).  No monolithic post-grad reduction remains: the strips feed
``optim.dist.make_overlapped_update``, which slices, updates and
part-broadcasts exactly like the §3.4 strip update.

Everything here runs INSIDE ``jax.shard_map`` over the data axes — each
member computes the loss of its local batch shard, and the per-bucket
reduces sum the members' local gradients (divided by G: the synchronous-SGD
mean).  For the scan-based transformer stacks the param leaves are stacked
across layers, so a bucket's cotangent completes only when the whole scan
backward finishes — the schedule degrades to coarser granularity but stays
correct (the hooks are purely data-driven).

The analytic counterpart — which buckets' transfers stay exposed — is
``core.balance.bucket_bubble_schedule``, fed by :func:`bucket_triggers` /
:func:`issue_order` below; with one bucket per layer it reduces exactly to
the paper's per-layer ``bubble_schedule`` closed form (property-tested).
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.comm.bucketer import Bucket, BucketPlan, CommConfig, plan_buckets
from repro.comm.schedule import Schedule, make_schedule, reduce_mean
from repro.core.collectives import AxisNames
from repro.telemetry import scopes


# ---------------------------------------------------------------------------
# readiness metadata: bucket -> issue point of the §3.1 schedule
# ---------------------------------------------------------------------------
def bucket_triggers(plan: BucketPlan,
                    leaf_layer: Optional[Sequence[int]] = None
                    ) -> Tuple[int, ...]:
    """Per bucket, the FORWARD-order layer whose weight-gradient pass
    completes the bucket.  Backprop visits layers last-to-first, so a
    bucket's trigger is the MINIMUM layer over its leaves — the earliest
    forward layer is the last to deliver its gradient.

    ``leaf_layer`` maps flat leaf index -> forward layer index (e.g. parsed
    from the family's param-spec names); ``None`` treats each leaf as its
    own layer in tree order (``Bucket.trigger_index``).
    """
    if leaf_layer is None:
        return tuple(b.trigger_index for b in plan.buckets)
    return tuple(min(leaf_layer[s.index] for s in b.slots)
                 for b in plan.buckets)


def issue_order(triggers: Sequence[int]) -> Tuple[int, ...]:
    """Bucket indices in backprop issue order: descending trigger layer
    (a bucket completed by a LATER layer is ready earlier in backprop);
    ties break toward the later tree-order bucket.  Delegates to the single
    definition in ``core.balance.issue_order`` so the executable schedule
    and the analytic closed forms can never disagree on ordering."""
    from repro.core.balance import issue_order as _rule
    return _rule(triggers)


# ---------------------------------------------------------------------------
# the comm hooks
# ---------------------------------------------------------------------------
def _bucket_tap(bucket: Bucket, sched: Schedule, wire_dtype, G: int):
    """Identity on the bucket's leaves whose BACKWARD packs their cotangents
    into the fusion buffer and issues the part-reduce.  The reduced mean
    strip exits as the cotangent of the zero ``sink`` argument."""

    @jax.custom_vjp
    def tap(leaves, sink):
        return leaves

    def fwd(leaves, sink):
        return leaves, None

    def bwd(_, ct):
        with jax.named_scope(scopes.REDUCE):
            parts = [c.reshape(-1) for c in ct]
            pad = bucket.padded_size - bucket.size
            if pad:
                parts.append(jnp.zeros((pad,), parts[0].dtype))
            buf = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
            strip = reduce_mean(sched, buf, wire_dtype, G)
        # leaf cotangents pass through untouched — upstream backprop is
        # unaffected; the strip rides the sink's gradient channel
        return tuple(ct), strip

    tap.defvjp(fwd, bwd)
    return tap


def make_overlap_grad(loss_fn: Callable, axes: AxisNames, comm: CommConfig,
                      G: int) -> Callable:
    """Build ``overlap_grad(params, batch) -> (loss, g_strips)``, to be
    called INSIDE ``shard_map`` over ``axes``.

    ``loss_fn(params, batch)`` is the member-LOCAL loss (mesh-free ctx);
    ``loss`` returned is still local — psum/G it for the global mean.
    ``g_strips`` is one fully-reduced fp32 mean-gradient strip per bucket of
    ``plan_buckets(params, G, comm.bucket_bytes)`` — the same plan (and the
    same owner layout) ``make_overlapped_update`` consumes.  The reduces
    issued by the hooks go through ``comm.backend``'s collectives.
    """
    # wire_format rides the schedule seam here too (int8 overlap works —
    # stateless); topk never reaches this path: its error-feedback residual
    # has nowhere to live in a stateless tap, so MODE_CAPS rejects the combo
    sched = make_schedule(axes, comm.hierarchical, comm.backend,
                          comm.cross_backend, wire_format=comm.wire_format,
                          topk_ratio=comm.topk_ratio)

    def overlap_grad(params, batch):
        plan = plan_buckets(params, G, comm.bucket_bytes)
        flat, treedef = jax.tree.flatten(params)

        def hooked_loss(flat_leaves, sinks):
            out = list(flat_leaves)
            for b, sink in zip(plan.buckets, sinks):
                tapped = _bucket_tap(b, sched, comm.wire_dtype, G)(
                    tuple(out[s.index] for s in b.slots), sink)
                for s, leaf in zip(b.slots, tapped):
                    out[s.index] = leaf
            with jax.named_scope(scopes.FWD):
                return loss_fn(jax.tree.unflatten(treedef, out), batch)

        sinks = tuple(jnp.zeros((b.padded_size // G,), jnp.float32)
                      for b in plan.buckets)
        loss, strips = jax.value_and_grad(hooked_loss, argnums=1)(
            tuple(flat), sinks)
        return loss, list(strips)

    return overlap_grad


# ---------------------------------------------------------------------------
# analytic exposure: what the schedule is predicted to hide
# ---------------------------------------------------------------------------
def exposed_comm(plan: BucketPlan, comm_times: Sequence[float],
                 layer_comps: Sequence[float], hw,
                 leaf_layer: Optional[Sequence[int]] = None,
                 efficiency: float = 1.0) -> Tuple[float, float, List[float]]:
    """(exposed_off, exposed_on, bubbles): predicted exposed-comm seconds
    with the monolithic schedule (everything after backprop — the full
    ``sum(comm_times)``) vs. the §3.1 overlap schedule
    (``core.balance.overlap_exposed_time`` on the shared-link timeline).
    ``bubbles`` are the per-bucket §3.1 closed-form bubbles
    (``bucket_bubble_schedule``) for diagnosis — which transfers the
    schedule fails to hide.  All driven by this plan's readiness metadata."""
    from repro.core.balance import bucket_bubble_schedule, overlap_exposed_time
    triggers = bucket_triggers(plan, leaf_layer)
    bubbles = bucket_bubble_schedule(comm_times, triggers, layer_comps, hw,
                                     efficiency)
    off = float(sum(comm_times))
    on = float(overlap_exposed_time(comm_times, triggers, layer_comps, hw,
                                    efficiency))
    return off, on, bubbles
