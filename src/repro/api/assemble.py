"""``compile_run``: RunSpec -> Run, ``compile_serve``: ServeSpec -> Server.
The one place run/deployment assembly happens.

Resolution order:

1. arch id -> config (``configs.get_config``), optionally reduced to the
   family smoke variant;
2. config -> :class:`~repro.api.families.FamilyAdapter` (the registry that
   replaced the per-call-site ``isinstance`` dispatch);
3. mesh from the ``MeshSpec`` topology (none for ``serial``), params
   initialized and placed by the logical-axis sharding rules;
4. parallelism mode -> update path: plain ``optimizer.update`` (serial/dp),
   with ``comm="auto"`` resolved FIRST — the telemetry autotuner times the
   real per-bucket collectives on the live mesh and picks bucket size /
   backend from the §3.2 balance model with measured constants
   (``repro.telemetry.autotune``; it must run before ``init_fn`` because
   the ZeRO-1 strip layout depends on the bucket plan) — then
   the explicit bucketed §3.4 phase pipeline of ``repro.comm`` +
   ``optim.dist.UpdatePlan`` (``zero1`` — monolithic reduce/apply/broadcast,
   or the §3.1 backprop-overlapped bubble schedule when
   ``CommConfig.overlap`` is set; ``stale-sync`` — the same pipeline with
   the reduce consumed one step late; ``gossip`` — the same pipeline with
   the reduce phase on the GossipGraD partner-exchange backend, flat
   schedule by default so the rotation spans the whole group; in every
   case the schedules drive the collective backend named by
   ``CommConfig.backend``), or GSPMD-sharded optimizer state
   (``zero1-gspmd``);
5. ``make_train_step`` (or ``make_overlapped_train_step``) glues loss ->
   grads -> update into the jit-ready step the returned
   :class:`~repro.api.run.Run` carries.

Steps 4-5 are :func:`assemble_step`, which needs the params' shapes and
shardings but not their values (except for ``comm="auto"``).

ROADMAP follow-ons (async modes, multi-backend collectives) plug in at
step 4 without touching any launcher — the bucket-autotuning hook already
does (``comm="auto"``).
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
from jax.sharding import Mesh

from repro.api.families import FamilyAdapter, adapter_for
from repro.api.run import Run
from repro.api.serve import Server
from repro.api.spec import MODE_CAPS, RunSpec, ServeSpec
from repro.comm.bucketer import CommConfig, plan_buckets
from repro.configs import get_config, smoke_variant
from repro.core.params import Spec
from repro.core.sharding import ShardingCtx, ShardingRules
from repro.launch.mesh import make_cluster_mesh, make_host_mesh
from repro.optim import AdamW, MomentumSGD, constant, linear_scale_warmup, warmup_cosine
from repro.optim.dist import (
    ROW_BLOCK_SHARE,
    make_distributed_update,
    make_overlapped_update,
    make_stale_sync_update,
    make_topk_ef_update,
)
from repro.telemetry import autotune_comm, make_recorder
from repro.train import make_overlapped_train_step, make_train_step, zero1_state_shardings


def _resolve_config(spec: RunSpec):
    cfg = get_config(spec.arch) if isinstance(spec.arch, str) else spec.arch
    return smoke_variant(cfg) if spec.smoke else cfg


def _make_optimizer(spec: RunSpec, family: FamilyAdapter):
    name = spec.optimizer or family.default_optimizer
    wd = spec.weight_decay
    if name == "adamw":
        return AdamW(weight_decay=0.01 if wd is None else wd)
    return MomentumSGD(momentum=spec.momentum,
                       weight_decay=0.0 if wd is None else wd)


def _make_schedule(spec: RunSpec, data_ways: int = 1):
    if spec.schedule == "constant":
        return constant(spec.lr)
    warmup = spec.warmup_steps if spec.warmup_steps is not None \
        else max(spec.steps // 20, 1)
    if spec.schedule == "linear-scale-warmup":
        # Goyal et al.: peak LR scales with the global data-parallel ways
        # (the G members splitting the global batch), gradual warmup from
        # the unscaled base LR
        return linear_scale_warmup(spec.lr, data_ways, warmup, spec.steps)
    return warmup_cosine(spec.lr, warmup, spec.steps)


def param_shardings(family: FamilyAdapter, cfg, mesh: Mesh,
                    rules: ShardingRules):
    """Each parameter's sharding on ``mesh`` under the logical-axis
    ``rules``."""
    return jax.tree.map(
        lambda s: rules.sharding(s.axes, s.shape, mesh),
        family.param_specs(cfg),
        is_leaf=lambda x: isinstance(x, Spec))


def _data_axes(mesh: Mesh) -> Tuple[str, ...]:
    """The data-parallel group axes actually present on the mesh."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


class StepParts(NamedTuple):
    """What :func:`assemble_step` builds around the params."""
    ctx: ShardingCtx
    loss_fn: Callable
    optimizer: Any
    lr_schedule: Callable
    comm: Optional[CommConfig]      # resolved; None outside the bucketed modes
    init_fn: Callable               # params -> placed optimizer state
    train_step: Callable            # (params, opt_state, step, batch) -> ...


def assemble_step(spec: RunSpec, cfg, family: FamilyAdapter,
                  mesh: Optional[Mesh], rules: ShardingRules, params,
                  telemetry) -> StepParts:
    """Steps 4-5 of the resolution order: the loss, optimizer, schedule,
    update path and train step of ``spec`` on ``mesh``.

    ``params`` are read only by ``comm="auto"``, which times collectives on
    the live arrays; otherwise they may be ``jax.ShapeDtypeStruct`` leaves,
    so the step ``compile_run`` trains can be lowered for devices that are
    described rather than attached.
    """
    ctx = ShardingCtx(mesh, rules)
    loss_fn = family.make_loss(cfg, ctx)
    optimizer = _make_optimizer(spec, family)
    data_ways = 1
    if mesh is not None:
        for a in _data_axes(mesh):
            data_ways *= mesh.shape[a]
    lr_schedule = _make_schedule(spec, data_ways)

    dist_update = None
    train_step = None
    comm = None
    init_fn = optimizer.init
    if spec.parallel in ("zero1", "stale-sync", "gossip"):
        axes = _data_axes(mesh)
        if spec.parallel == "gossip":
            # flat on purpose: hierarchical would scope the partner
            # rotation to each pod (and the in-pod group of a 1-pod-per-
            # host cluster is a single member — full sync, no gossip)
            default = CommConfig(backend="gossip", hierarchical=False)
        else:
            default = CommConfig(hierarchical=len(axes) == 2)
        if spec.comm == "auto":
            # measured-feedback autotune — BEFORE init_fn: the ZeRO-1
            # strip layout depends on the bucket plan and
            # checkpoint.replan refuses mid-run bucket changes
            reps = getattr(spec.telemetry, "autotune_reps", 2)
            import os as _os

            from repro.telemetry.autotune import ENV_AUTOTUNE_CACHE
            with telemetry.span("autotune", mode=spec.parallel):
                comm = autotune_comm(
                    params, mesh, axes, default, recorder=telemetry,
                    backends=MODE_CAPS[spec.parallel].backends, reps=reps,
                    wire_formats=MODE_CAPS[spec.parallel].wire_formats,
                    cache_path=_os.environ.get(ENV_AUTOTUNE_CACHE))
        elif spec.comm is not None:
            comm = spec.comm
        else:
            comm = default
        if spec.parallel == "stale-sync":
            init_fn, dist_update = make_stale_sync_update(
                optimizer, mesh, data_axes=axes, comm=comm)
        elif comm.wire_format == "topk":
            # spec validation pinned this to the monolithic zero1 pipeline
            # (no overlap, no stale-sync, no gossip): the error-feedback
            # residual needs the strip-state carry of the EF composition
            init_fn, dist_update = make_topk_ef_update(
                optimizer, mesh, data_axes=axes, comm=comm)
        elif comm.overlap:
            # §3.1 bubble schedule: the whole step runs in one shard_map and
            # each bucket's part-reduce is issued inside the backward pass
            # (comm hooks), so the loss must be the mesh-free local loss —
            # GSPMD constraints do not apply inside shard_map
            if spec.mesh.model_ways > 1:
                raise ValueError(
                    "CommConfig.overlap runs the whole step inside a "
                    "shard_map over the data axes with a mesh-free loss — "
                    "a model axis would be silently replicated (full "
                    "redundant compute per model member), so overlap "
                    "currently requires model_ways == 1 "
                    f"(got model_ways={spec.mesh.model_ways})")
            init_fn, local_update = make_overlapped_update(
                optimizer, mesh, data_axes=axes, comm=comm)
            train_step = make_overlapped_train_step(
                family.make_loss(cfg, ShardingCtx()), lr_schedule, mesh,
                axes, comm, local_update, grad_clip=spec.grad_clip)
        else:
            init_fn, dist_update = make_distributed_update(
                optimizer, mesh, data_axes=axes, comm=comm)
    elif spec.parallel == "zero1-gspmd":
        def init_fn(params):
            opt_state = optimizer.init(params)
            st_sh = zero1_state_shardings(opt_state, family.param_axes(cfg),
                                          mesh, rules)
            return jax.tree.map(jax.device_put, opt_state, st_sh)

    # the update plan's share of elements in row-blocked buckets; 0 where
    # the mode builds no plan
    telemetry.gauge(ROW_BLOCK_SHARE, 0.0 if comm is None else plan_buckets(
        params, data_ways, comm.bucket_bytes).row_block_share)
    if train_step is None:
        train_step = make_train_step(loss_fn, optimizer, lr_schedule,
                                     grad_clip=spec.grad_clip,
                                     dist_update=dist_update)
    return StepParts(ctx=ctx, loss_fn=loss_fn, optimizer=optimizer,
                     lr_schedule=lr_schedule, comm=comm, init_fn=init_fn,
                     train_step=train_step)


def compile_run(spec: RunSpec, rules: Optional[ShardingRules] = None) -> Run:
    """Assemble a ready-to-train :class:`Run` from a declarative ``spec``.

    ``rules`` overrides the logical-axis sharding rule table (defaults to
    the paper-faithful hybrid-parallel rules).
    """
    cfg = _resolve_config(spec)
    family = adapter_for(cfg)
    telemetry = make_recorder(spec.telemetry)

    mesh = None
    if spec.parallel != "serial":
        if spec.mesh.cluster:
            mesh = make_cluster_mesh(spec.mesh.model_ways)
        else:
            mesh = make_host_mesh(spec.mesh.model_ways, pods=spec.mesh.pods)
    rules = rules if rules is not None else ShardingRules()

    params = family.init(cfg, jax.random.PRNGKey(spec.seed))
    if mesh is not None:
        params = jax.tree.map(jax.device_put, params,
                              param_shardings(family, cfg, mesh, rules))

    parts = assemble_step(spec, cfg, family, mesh, rules, params, telemetry)
    return Run(spec=spec, cfg=cfg, family=family, mesh=mesh, rules=rules,
               ctx=parts.ctx, loss_fn=parts.loss_fn,
               optimizer=parts.optimizer, lr_schedule=parts.lr_schedule,
               train_step=parts.train_step, params=params,
               opt_state=parts.init_fn(params), comm=parts.comm,
               telemetry=telemetry)


def compile_serve(spec: ServeSpec, params=None,
                  rules: Optional[ShardingRules] = None,
                  recorder=None) -> Server:
    """Assemble a live :class:`~repro.api.serve.Server` from a declarative
    ``spec`` (the serving twin of ``compile_run``).

    ``params`` lets a caller serve trained weights (e.g. ``run.params``
    after training); ``None`` initializes fresh ones from ``spec.seed``.
    ``recorder`` attaches a telemetry Recorder — prefill/decode/preempt
    become spans; latency histograms are always on regardless.
    Paged decode covers the attention block kinds only, so non-transformer
    families, modality frontends, M-RoPE, and codebook heads are rejected
    here — before any buffer is allocated.
    """
    from repro.configs.base import ModelConfig
    from repro.models import transformer
    from repro.models.transformer import ATTN_KINDS

    cfg = get_config(spec.arch) if isinstance(spec.arch, str) else spec.arch
    cfg = smoke_variant(cfg) if spec.smoke else cfg
    if not isinstance(cfg, ModelConfig):
        raise ValueError(
            f"compile_serve needs a token LM ModelConfig, got "
            f"{type(cfg).__name__} — serving covers the transformer family "
            "only")
    bad = [k for k in cfg.block_pattern if k not in ATTN_KINDS]
    if bad:
        raise ValueError(
            f"paged decode serves attention blocks only ({ATTN_KINDS}); "
            f"{cfg.name!r} has {bad} in its pattern")
    if cfg.frontend is not None or cfg.num_codebooks or cfg.mrope:
        raise ValueError(
            f"{cfg.name!r} uses a modality frontend / codebook heads / "
            "M-RoPE — token-in/token-out archs only for serving")

    ctx = ShardingCtx(None, rules if rules is not None else ShardingRules())
    if params is None:
        params = transformer.init_params(cfg, jax.random.PRNGKey(spec.seed))
    return Server(spec=spec, cfg=cfg, ctx=ctx, params=params,
                  recorder=recorder)
