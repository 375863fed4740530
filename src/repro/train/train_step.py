"""train_step assembly: loss -> grads -> synchronous-SGD update.

Two equivalent realizations of the paper's §3.4 update:

  * ``make_train_step`` (production, pjit/GSPMD): the batch is sharded over
    the data axes, so the gradient all-reduce is implicit; when
    ``zero1=True`` the optimizer state is sharded over the data axes and XLA
    factorizes the all-reduce into reduce-scatter (part-reduce) + all-gather
    (part-broadcast) around the update — the paper's exact schedule.
  * ``optim.dist.make_distributed_update`` (explicit shard_map, bucketed
    through ``repro.comm``) — used in examples/tests; equivalence is
    property-tested.  Passing its ``update_fn`` as ``dist_update`` below
    routes the whole ZeRO-1 train step through the bucketed fusion-buffer
    collectives instead of the serial ``optimizer.update``.

``make_overlapped_train_step`` is the third realization — the paper's §3.1
overlap schedule: the whole step runs inside one shard_map and each gradient
bucket's part-reduce is issued INSIDE the backward pass (repro.comm.overlap)
instead of after ``value_and_grad`` returns.

Every realization opens the step-phase scopes of ``repro.telemetry.scopes``
(``fwd``, ``clip``, ``update/reduce|apply|broadcast``), so each compiled op
names its phase; the scopes are metadata and leave the arithmetic alone.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.sharding import ShardingRules
from repro.telemetry import scopes


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def make_train_step(loss_fn: Callable, optimizer, lr_schedule,
                    grad_clip: float = 1.0,
                    dist_update: Optional[Callable] = None):
    """loss_fn(params, batch) -> scalar loss.  Returns
    step(params, opt_state, step_idx, batch) -> (params, opt_state, metrics).

    ``dist_update`` (optional): an explicit distributed update
    ``(params, grads, opt_state, lr, step) -> (new_params, new_opt_state)``
    — the ``update_fn`` built by ``optim.dist.make_distributed_update`` /
    ``make_stale_sync_update`` — replacing the serial ``optimizer.update``.
    This is the explicit ZeRO-1 path: the step's gradients flow through the
    bucketed part-reduce, the strip optimizer, and the bucketed
    part-broadcast of ``repro.comm``.  ``step_idx`` is forwarded so
    step-scheduled modes (the gossip partner rotation, the staleness carry)
    see the train step; step-free modes ignore it.  The matching
    ``opt_state`` must come from the same builder's ``init_fn``.
    """
    def scoped_loss(params, batch):
        with jax.named_scope(scopes.FWD):
            return loss_fn(params, batch)

    def train_step(params, opt_state, step_idx, batch):
        loss, grads = jax.value_and_grad(scoped_loss)(params, batch)
        with jax.named_scope(scopes.CLIP):
            gnorm = global_norm(grads)
            if grad_clip > 0:
                scale = jnp.minimum(1.0,
                                    grad_clip / jnp.maximum(gnorm, 1e-9))
                grads = jax.tree.map(lambda g: g * scale, grads)
        with jax.named_scope(scopes.APPLY):
            lr = lr_schedule(step_idx)
        if dist_update is not None:
            new_params, new_state = dist_update(params, grads, opt_state, lr,
                                                step_idx)
        else:
            with jax.named_scope(scopes.APPLY):
                new_params, new_state = optimizer.update(grads, opt_state,
                                                         params, lr)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        return new_params, new_state, metrics

    return train_step


def make_overlapped_train_step(loss_fn: Callable, lr_schedule,
                               mesh: Mesh, data_axes, comm,
                               local_update: Callable,
                               grad_clip: float = 1.0):
    """The §3.1 backprop-overlapped realization of the explicit ZeRO-1 step.

    The WHOLE step — local loss, hooked backprop, strip optimizer,
    part-broadcast — runs inside one ``shard_map`` over the data axes: each
    member computes the loss of ITS batch shard, and every gradient
    bucket's part-reduce is issued in the backward pass the moment the
    bucket's last leaf gradient materializes (``repro.comm.overlap``), so
    the compiler may hide it under the remaining backprop instead of
    serializing the whole tree reduction after ``value_and_grad``.

    ``loss_fn(params, batch)`` must be the mesh-free (serial-ctx) loss:
    inside shard_map every member's compute is local, so GSPMD sharding
    constraints do not apply.  ``local_update`` comes from
    ``optim.dist.make_overlapped_update`` (same comm config).  Matches
    ``make_train_step`` — loss, clip, metrics — to float tolerance;
    property-tested in tests/test_distributed.py.
    """
    from repro.comm.overlap import make_overlap_grad
    from repro.comm.schedule import group_axes

    _, axis_arg, G = group_axes(mesh, data_axes)
    overlap_grad = make_overlap_grad(loss_fn, axis_arg, comm, G)

    def local_step(params, opt_state, step_idx, batch):
        loss, g_strips = overlap_grad(params, batch)
        with jax.named_scope(scopes.FWD):
            loss = lax.psum(loss, axis_arg) / G
        with jax.named_scope(scopes.CLIP):
            # global grad norm from the reduced strips: every element of
            # the mean gradient lives in exactly one member's strip (bucket
            # padding is zeros), so the psum of local square-sums is the
            # full norm²
            sq = sum(jnp.sum(jnp.square(s)) for s in g_strips)
            gnorm = jnp.sqrt(lax.psum(sq, axis_arg))
            if grad_clip > 0:
                scale = jnp.minimum(1.0,
                                    grad_clip / jnp.maximum(gnorm, 1e-9))
                g_strips = [g * scale for g in g_strips]
        with jax.named_scope(scopes.APPLY):
            lr = lr_schedule(step_idx)
        new_params, new_state = local_update(params, g_strips, opt_state, lr)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        return new_params, new_state, metrics

    def train_step(params, opt_state, step_idx, batch):
        pspec = jax.tree.map(lambda _: P(), params)
        sspec = jax.tree.map(
            lambda s: P(axis_arg) if getattr(s, "ndim", 0) >= 2 else P(),
            opt_state)
        bspec = jax.tree.map(lambda _: P(axis_arg), batch)
        mspec = {"loss": P(), "grad_norm": P(), "lr": P()}
        fn = jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(pspec, sspec, P(), bspec),
            out_specs=(pspec, sspec, mspec),
            check_vma=False)
        return fn(params, opt_state, step_idx, batch)

    return train_step


def zero1_state_shardings(opt_state, param_axes, mesh: Mesh,
                          rules: ShardingRules):
    """ZeRO-1 (the paper's strip scheme via GSPMD): optimizer-state tensors
    take the param sharding PLUS 'data' on the first dim that is unsharded
    and divisible — gradients then arrive by reduce-scatter and the updated
    params leave by all-gather."""
    def one(s, axes):
        if getattr(s, "ndim", 0) == 0:
            return NamedSharding(mesh, P())
        spec = list(rules.spec(axes, s.shape, mesh))
        spec += [None] * (s.ndim - len(spec))
        used = set()
        for entry in spec:
            for a in (entry if isinstance(entry, tuple) else (entry,)):
                if a is not None:
                    used.add(a)
        extra = tuple(a for a in ("pod", "data")
                      if a in mesh.axis_names and a not in used)
        extent = 1
        for a in extra:
            extent *= mesh.shape[a]
        if extra and extent > 1:
            for i, (ax, dim) in enumerate(zip(spec, s.shape)):
                if ax is None and dim % extent == 0:
                    spec[i] = extra if len(extra) > 1 else extra[0]
                    break
        while spec and spec[-1] is None:
            spec.pop()
        return NamedSharding(mesh, P(*spec))

    # opt_state mirrors the param tree per field (mu/nu/velocity) + scalars;
    # match leaves to param axes cyclically (field trees flatten in the same
    # order as the param tree), skipping scalars
    flat_axes = jax.tree.leaves(
        param_axes, is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))

    leaves, treedef = jax.tree.flatten(opt_state)
    # state fields repeat the param tree: match cyclically by shape count
    out = []
    n = len(flat_axes)
    pi = 0
    for leaf in leaves:
        if getattr(leaf, "ndim", 0) == 0:
            out.append(NamedSharding(mesh, P()))
            continue
        out.append(one(leaf, flat_axes[pi % n]))
        pi += 1
    return jax.tree.unflatten(treedef, out)
