"""Paper §3.4 — the distributed synchronous-SGD update, as a phase pipeline.

Between local weight-gradient computation and the SGD step, gradients are
**part-reduce**d over the data-parallel group: each group member receives the
fully-reduced gradient for a 1/G strip, applies the optimizer to ITS strip
only (optimizer state exists only for the strip — the paper's scheme is
ZeRO-1 avant la lettre), then **part-broadcast**s the updated strip so every
member again holds the full weights before the next forward pass.

That update decomposes into three separable phases over one shared layout,
and :class:`UpdatePlan` is that decomposition made explicit:

    reduce(grads)  -> g_strips     one wire-dtype part-reduce per fusion
                                   bucket, mean in fp32
    apply(strips)  -> new strips   slice this member's param strips, run
                                   the serial optimizer on its state row
    broadcast(strips) -> params    one fp32 part-broadcast per bucket,
                                   un-fuse back into tensors

Every mode is a composition of the phases, not its own builder:

  * ``make_distributed_update`` — reduce + apply + broadcast in one
    shard_map (the monolithic zero1 step);
  * ``make_overlapped_update`` — apply + broadcast only: the reduces were
    issued inside the backward pass by the ``repro.comm.overlap`` hooks
    (which share the reduce math via ``comm.schedule.reduce_mean``);
  * ``comm=None`` — the seed per-tensor schedule is the SAME pipeline over
    per-tensor buckets (``CommConfig(bucket_bytes=0)`` — ``plan_buckets``
    then closes one bucket per leaf), not a separate code path;
  * ``make_stale_sync_update`` — phase RE-SCHEDULING across steps: step t
    applies the strips reduced at step t-1 from a carried buffer (bounded
    staleness 1), which the strip-owner layout permits because reduce and
    apply touch no shared state;
  * ``parallel="gossip"`` — the same pipeline with the reduce phase's
    collectives swapped for the GossipGraD partner exchange
    (``comm.backends.gossip``; the schedule seam carries the step so the
    partner rotation advances);
  * ``make_topk_ef_update`` — the ``wire_format="topk"`` composition: the
    reduce phase's input is error-feedback-compensated (residual carried
    in strip state) and sparsified per bucket before the wire; the ring
    itself then moves (values, indices) messages.

Communication goes through ``repro.comm``: the gradient tree is coalesced
into fixed-byte fusion buffers (``CommConfig.bucket_bytes``) so each BUCKET
is one part-reduce/part-broadcast pair — collective count drops from
O(#tensors) to O(total_bytes / bucket_bytes), which is what keeps VGG-A's
many small conv/bias tensors out of the latency-bound regime of the §3.2
balance model.  The optimizer itself is elementwise, so bucketed strips,
per-tensor strips and the serial update agree to float tolerance — and the
pipeline is BIT-equal to the pre-refactor builders (pinned in
tests/test_distributed.py).

This module is the explicit shard_map realization, used by the
data-parallel examples and by the equivalence property tests
(distributed update == serial update, to float tolerance).  The production
pjit path reaches the same communication pattern through GSPMD when the
optimizer state carries data-axis sharding (see train/train_step.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.comm.bucketer import (
    BucketPlan,
    CommConfig,
    pack_bucket,
    plan_buckets,
    unpack_buckets,
)
from repro.comm.schedule import Schedule, group_axes, make_schedule, reduce_mean
from repro.telemetry import scopes

DEFAULT_COMM = CommConfig()

#: gauge of the process recorder: share of the parameter elements whose
#: bucket is row-blocked (``comm.bucketer.row_blocks``), set by
#: ``api.compile_run``
ROW_BLOCK_SHARE = "update.row_block_share"


def owner_perm(hierarchical: bool, axes_sizes) -> Optional[np.ndarray]:
    """Row j of a (G, ...) strip state tensor lands on the member at flat mesh
    index j, but under the hierarchical schedule that member OWNS strip
    owner_index = d*G_out + p — so value-initialized optimizer state must
    be laid out in owner order (zeros-init state is insensitive to this).
    None for the flat schedule (identity layout).  Public because
    ``checkpoint.replan`` needs the same layout law to convert strip state
    between world sizes."""
    if hierarchical and len(axes_sizes) == 2:
        g_out, g_in = axes_sizes
        return np.array(
            [d * g_out + p for p in range(g_out) for d in range(g_in)])
    return None


@dataclass(frozen=True)
class UpdatePlan:
    """The shared layout + phase set of the §3.4 update path: which mesh
    axes form the group, how the tree fuses into buckets, which member owns
    which strip, and the three phases every mode composes.  ``build`` is
    the one place the layout is derived, so the monolithic, overlapped,
    per-tensor, stale-sync and gossip paths can never disagree on it."""
    optimizer: Any
    mesh: Mesh
    axes: Tuple[str, ...]
    axis_arg: Any                  # single-name-or-tuple collective form
    G: int
    comm: CommConfig

    @classmethod
    def build(cls, optimizer, mesh: Mesh, data_axes=("data",),
              comm: Optional[CommConfig] = DEFAULT_COMM) -> "UpdatePlan":
        """``comm=None`` selects the seed per-tensor schedule — expressed
        as per-tensor buckets (``bucket_bytes=0`` makes ``plan_buckets``
        close one bucket per leaf), NOT a separate code path."""
        axes, axis_arg, G = group_axes(mesh, data_axes)
        if comm is None:
            comm = CommConfig(bucket_bytes=0)
        return cls(optimizer, mesh, axes, axis_arg, G, comm)

    # -- shared layout ------------------------------------------------
    def buckets(self, params) -> BucketPlan:
        return plan_buckets(params, self.G, self.comm.bucket_bytes)

    def schedule(self, step=None) -> Schedule:
        """The collective schedule, with ``step`` (may be traced) bound
        into step-scheduled backends — the gossip partner rotation — and
        the wire format bound into format-aware ones."""
        return make_schedule(self.axis_arg, self.comm.hierarchical,
                             self.comm.backend, self.comm.cross_backend,
                             step=step, wire_format=self.comm.wire_format,
                             topk_ratio=self.comm.topk_ratio)

    def owner_layout(self) -> Optional[np.ndarray]:
        return owner_perm(self.comm.hierarchical,
                          [self.mesh.shape[a] for a in self.axes])

    def state_spec(self, s) -> P:
        return _state_spec(s, self.axis_arg)

    def init_fn(self, params):
        """(G, *strip) fusion-buffer strip state placed on the mesh: (G,
        n/G) for a 1-D bucket, (G, rows/G, cols) for a row-blocked one
        (``comm.bucketer.row_blocks``) — shared
        by every mode (all consume the same plan and owner layout, so a
        checkpoint written by one path restores into another).  Traceable:
        ``jax.eval_shape`` gives the placed state's shapes from param
        shapes."""
        perm = self.owner_layout()

        def _strip_init(params):
            plan = self.buckets(params)
            flat = jax.tree.leaves(params)
            # (G, *strip): dim 0 sharded over the data axes
            strips = [pack_bucket(flat, b).reshape(
                          (self.G,) + b.strip_shape(self.G))
                      for b in plan.buckets]
            if perm is not None:
                strips = [s[perm] for s in strips]
            return self.optimizer.init(strips)

        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, self.state_spec(s)),
            jax.eval_shape(_strip_init, params))
        return jax.jit(_strip_init, out_shardings=shardings)(params)

    # -- the three phases (called INSIDE shard_map) --------------------
    def reduce(self, sched: Schedule, plan: BucketPlan, grads):
        """Phase 1: one part-reduce per BUCKET — pack gradients into the
        fusion buffer, reduce on the wire dtype, mean in fp32.  Returns
        this member's mean-gradient strip per bucket."""
        flat_grads = jax.tree.leaves(grads)
        with jax.named_scope(scopes.REDUCE):
            return [reduce_mean(sched, pack_bucket(flat_grads, b),
                                self.comm.wire_dtype, self.G)
                    for b in plan.buckets]

    def apply(self, sched: Schedule, plan: BucketPlan, params, g_strips,
              opt_state, lr):
        """Phases 2–3: slice this member's param strips, run the serial
        optimizer on its local state row (elementwise, so fusing tensors
        into one buffer, or blocking one by rows, does not change the
        math).  ``opt_state`` enters in shard_map-local layout (strips as
        (1, *strip) rows) and the new state leaves the same way."""
        flat_params = jax.tree.leaves(params)
        with jax.named_scope(scopes.APPLY):
            i = sched.owner_index()
            p_strips = []
            for b in plan.buckets:
                n = b.strip_shape(self.G)[0]
                p_strips.append(lax.dynamic_slice_in_dim(
                    pack_bucket(flat_params, b), i * n, n))
            s_local = jax.tree.map(
                lambda s: s[0] if s.ndim >= 2 else s, opt_state)
            new_p_strips, new_state = self.optimizer.update(
                g_strips, s_local, p_strips, lr)
            new_state = jax.tree.map(
                lambda s: s[None] if s.ndim >= 1 else s, new_state)
        return jax.tree.leaves(new_p_strips), new_state

    def broadcast(self, sched: Schedule, plan: BucketPlan, params,
                  new_p_strips):
        """Phase 4: one part-broadcast per bucket (always fp32 — weights
        are never quantized on the wire), then un-fuse back into tensors."""
        with jax.named_scope(scopes.BROADCAST):
            bufs = [sched.broadcast(ps) for ps in new_p_strips]
            flat = unpack_buckets(bufs, plan)
        return jax.tree.unflatten(jax.tree.structure(params), flat)

    # -- shard_map plumbing shared by the monolithic wrappers ----------
    def wrap_update(self, _update):
        """``_update(params, grads, opt_state, lr, step)`` (member code) ->
        ``update_fn(params, grads, opt_state, lr, step=0)`` under shard_map
        over the data axes.  ``step`` feeds step-scheduled backends and the
        staleness carry; step-free modes ignore it, and omitting it keeps
        the seed call shape."""
        def update_fn(params, grads, opt_state, lr, step=0):
            pspec = jax.tree.map(lambda _: P(), params)
            sspec = jax.tree.map(self.state_spec, opt_state)
            fn = jax.shard_map(
                _update, mesh=self.mesh,
                in_specs=(pspec, pspec, sspec, P(), P()),
                out_specs=(pspec, sspec),
                check_vma=False)
            return fn(params, grads, opt_state, lr,
                      jnp.asarray(step, jnp.int32))
        return update_fn


def make_distributed_update(optimizer, mesh: Mesh, data_axes=("data",),
                            comm: Optional[CommConfig] = DEFAULT_COMM):
    """Build (init_fn, update_fn) realizing the paper's update under
    shard_map over ``data_axes``: the full reduce -> apply -> broadcast
    pipeline of one :class:`UpdatePlan`.  Params/grads enter replicated
    across the data axes (grads are the LOCAL minibatch-shard gradients,
    summed over local samples); optimizer state lives as per-member strips
    sharded on dim 0 — per fusion bucket when ``comm`` is given, per tensor
    when ``comm`` is None.  The bucketed collectives run on
    ``comm.backend`` (``repro.comm.backends``).

    update_fn(params, grads, opt_state, lr, step=0)
        -> (new_params, new_opt_state)
    """
    up = UpdatePlan.build(optimizer, mesh, data_axes, comm)

    def _update(params, grads, opt_state, lr, step):
        plan = up.buckets(params)
        sched = up.schedule(step)
        g_strips = up.reduce(sched, plan, grads)
        new_p_strips, new_state = up.apply(sched, plan, params, g_strips,
                                           opt_state, lr)
        new_params = up.broadcast(sched, plan, params, new_p_strips)
        return new_params, new_state

    return up.init_fn, up.wrap_update(_update)


def make_overlapped_update(optimizer, mesh: Mesh, data_axes=("data",),
                           comm: Optional[CommConfig] = None):
    """The backprop-overlapped composition: (init_fn, local_update) where
    ``local_update`` is the apply + broadcast phases only — it consumes
    per-bucket ALREADY-REDUCED mean-gradient strips instead of a raw
    gradient tree, because the reduces were issued inside the backward pass
    by the ``repro.comm.overlap`` hooks (which run the same
    ``reduce_mean`` math), so the reduce phase no longer exists as a
    post-grad block.

    Unlike ``make_distributed_update``'s update_fn, ``local_update(params,
    g_strips, opt_state, lr)`` must be called INSIDE ``shard_map`` over the
    same data axes: the overlapped train step owns the shard_map, because
    the bucket reduces live in its ``value_and_grad`` backward pass (see
    ``train.make_overlapped_train_step``).  ``init_fn`` is the shared
    strip init — state layouts are identical, so a checkpoint written by
    one path restores into the other.
    """
    comm = DEFAULT_COMM if comm is None else comm
    up = UpdatePlan.build(optimizer, mesh, data_axes, comm)
    sched = up.schedule()

    def local_update(params, g_strips, opt_state, lr):
        plan = up.buckets(params)
        new_p_strips, new_state = up.apply(sched, plan, params, g_strips,
                                           opt_state, lr)
        new_params = up.broadcast(sched, plan, params, new_p_strips)
        return new_params, new_state

    return up.init_fn, local_update


def make_stale_sync_update(optimizer, mesh: Mesh, data_axes=("data",),
                           comm: Optional[CommConfig] = None):
    """Bounded staleness (staleness 1): step t APPLIES the mean-gradient
    strips reduced at step t-1 and carries this step's freshly-reduced
    strips for step t+1 — phase re-scheduling ACROSS steps, which the
    strip-owner layout permits because the reduce and apply phases share no
    state.  A full step of backprop/forward compute is then available to
    hide every byte of the reduce (``core.balance.stale_sync_exposed_time``
    is the model); the trade is a one-step-old gradient, bounded — unlike
    fully-async parameter-server staleness.

    opt_state wraps the zero1 strip state:

        {"stale":  per-bucket (G, *strip) carried mean-gradient strips,
         "synced": int32 flag — 0 until a reduce has been carried,
         "zero1":  the inner strip state (BIT-identical layout to the
                   synchronous modes', so zero1 checkpoints resume here
                   with the buffer re-initialized — see ``api.run``)}

    The first step (and the first step after a buffer re-init on resume)
    applies its OWN reduce — there is nothing to consume yet, so it
    degrades to the synchronous update rather than applying zeros.

    update_fn(params, grads, opt_state, lr, step=0)
        -> (new_params, new_opt_state)
    """
    comm = DEFAULT_COMM if comm is None else comm
    up = UpdatePlan.build(optimizer, mesh, data_axes, comm)

    def init_fn(params):
        plan = up.buckets(params)
        sh = NamedSharding(mesh, P(up.axis_arg))
        stale = tuple(
            jax.device_put(jnp.zeros((up.G,) + b.strip_shape(up.G),
                                     jnp.float32), sh)
            for b in plan.buckets)
        # the flag is committed replicated so restore can re-place onto
        # its sharding (an uncommitted scalar would pin to device 0)
        synced = jax.device_put(jnp.zeros((), jnp.int32),
                                NamedSharding(mesh, P()))
        return {"stale": stale, "synced": synced,
                "zero1": up.init_fn(params)}

    def _update(params, grads, opt_state, lr, step):
        plan = up.buckets(params)
        sched = up.schedule(step)
        fresh = up.reduce(sched, plan, grads)
        with jax.named_scope(scopes.APPLY):
            carried = [s[0] for s in opt_state["stale"]]
            synced = opt_state["synced"]
            # consume LAST step's reduce; an empty buffer (first step, or a
            # resume that re-initialized it) falls back to this step's own
            applied = [jnp.where(synced > 0, c, f)
                       for c, f in zip(carried, fresh)]
        new_p_strips, new_inner = up.apply(sched, plan, params, applied,
                                           opt_state["zero1"], lr)
        new_params = up.broadcast(sched, plan, params, new_p_strips)
        new_state = {"stale": tuple(f[None] for f in fresh),
                     "synced": jnp.ones((), jnp.int32),
                     "zero1": new_inner}
        return new_params, new_state

    return init_fn, up.wrap_update(_update)


def make_topk_ef_update(optimizer, mesh: Mesh, data_axes=("data",),
                        comm: Optional[CommConfig] = None):
    """The ``wire_format="topk"`` composition: top-k sparsified reduce with
    LOCAL error feedback (the memory/compensation scheme of the deep
    gradient compression line — PAPERS.md 1712.01887 / 1711.00705).  Each
    step, every member adds its carried residual to the packed bucket
    gradient, keeps the ``topk_ratio`` largest-|g| entries, and carries
    ``buffer - kept`` forward — what sparsification drops this step is
    re-offered next step, which is what keeps top-k from biasing the
    trajectory the way plain truncation would.  The sparse buckets then
    ride the normal reduce phase, whose topk-bound backend moves (values,
    indices) messages with per-hop re-selection on the ring.

    opt_state wraps the zero1 strip state:

        {"residual": per-bucket (G, padded_size) f32 — row p is member p's
                     local unsent gradient mass (sharded dim 0, so each
                     member materializes one bucket-sized row; a
                     row-blocked bucket's too, flattened),
         "zero1":    the inner strip state (BIT-identical layout to the
                     synchronous modes', so zero1 checkpoints resume here
                     with a zero residual — see ``api.run``)}

    The residual is member-LOCAL by construction, so a cross-world replan
    cannot convert it (old members' unsent mass has no owner in the new
    world); restore re-zeros it — one step of stiffer sparsification, the
    same trade the stale-sync buffer re-init makes.

    update_fn(params, grads, opt_state, lr, step=0)
        -> (new_params, new_opt_state)
    """
    from repro.comm.backends.pallas_ring import topk_chunk_k
    from repro.kernels.ref import topk_mask_ref

    comm = DEFAULT_COMM if comm is None else comm
    if comm.wire_format != "topk":
        raise ValueError(
            "make_topk_ef_update requires CommConfig(wire_format='topk'); "
            f"got {comm.wire_format!r}")
    up = UpdatePlan.build(optimizer, mesh, data_axes, comm)

    def init_fn(params):
        plan = up.buckets(params)
        sh = NamedSharding(mesh, P(up.axis_arg))
        residual = tuple(
            jax.device_put(jnp.zeros((up.G, b.padded_size), jnp.float32),
                           sh)
            for b in plan.buckets)
        return {"residual": residual, "zero1": up.init_fn(params)}

    def _update(params, grads, opt_state, lr, step):
        plan = up.buckets(params)
        sched = up.schedule(step)
        flat_grads = jax.tree.leaves(grads)
        g_strips, new_res = [], []
        for b, res in zip(plan.buckets, opt_state["residual"]):
            with jax.named_scope(scopes.REDUCE):
                buf = (pack_bucket(flat_grads, b).reshape(-1)
                       .astype(jnp.float32) + res[0])
                # floor G: every wire chunk must get at least one entry,
                # and the per-chunk k the backend re-selects with (ratio *
                # n/G, floored at 1) then carries at least the bucket's k/G
                # — mass that concentrates in one chunk beyond its
                # per-chunk k is dropped on the wire, the canonical gTop-k
                # approximation, and lands back in the residual via error
                # feedback
                k = topk_chunk_k(b.padded_size, up.comm.topk_ratio,
                                 floor=up.G)
                kept = topk_mask_ref(buf, k)
                new_res.append((buf - kept)[None])
                g_strips.append(reduce_mean(
                    sched, kept, up.comm.wire_dtype, up.G).reshape(
                        b.strip_shape(up.G)))
        new_p_strips, new_inner = up.apply(sched, plan, params, g_strips,
                                           opt_state["zero1"], lr)
        new_params = up.broadcast(sched, plan, params, new_p_strips)
        return new_params, {"residual": tuple(new_res),
                            "zero1": new_inner}

    return init_fn, up.wrap_update(_update)


def _state_spec(s, axis_arg) -> P:
    # strip tensors are (G, *strip): dim 0 sharded; scalars (e.g. AdamW
    # step count, the staleness flag) replicated
    return P(axis_arg) if getattr(s, "ndim", 0) >= 2 else P()
