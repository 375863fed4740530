"""The executable product of ``compile_run``: params, state, step, data, fit.

A :class:`Run` owns everything a training loop needs, already assembled and
placed: the jit-ready ``train_step``, the (mesh-placed) ``params`` and
``opt_state``, a lazily-started prefetching ``data`` iterator, and ``fit()``
— the paper's §4 composition of data handling, compute and communication
behind one object.  The low-level layers (``make_train_step``,
``make_distributed_update``) stay public and stable underneath; a Run is
just their assembly.
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.checkpoint import ckpt as ckpt_lib
from repro.core.sharding import ShardingCtx, ShardingRules
from repro.data.pipeline import Prefetcher, make_placer
from repro.telemetry.events import process_recorder
from repro.train.trainer import Trainer, TrainerConfig, dispatch_step


@dataclass
class Run:
    """An assembled training run.  Mutated in place by ``fit`` (params and
    opt_state advance; the train_step buffers are donated)."""
    spec: Any                       # the RunSpec this run was compiled from
    cfg: Any                        # resolved (possibly smoke) family config
    family: Any                     # FamilyAdapter
    mesh: Optional[Mesh]
    rules: ShardingRules
    ctx: ShardingCtx
    loss_fn: Callable
    optimizer: Any
    lr_schedule: Callable
    train_step: Callable            # (params, opt_state, step, batch) -> ...
    params: Any
    opt_state: Any
    comm: Optional[Any] = None      # the RESOLVED CommConfig of an explicit
    #                                 bucketed run (zero1/stale-sync/gossip;
    #                                 None for other modes) — needed to
    #                                 re-plan strip state across world sizes
    telemetry: Optional[Any] = None  # the run's handle on the process
    #                                 recorder (repro.telemetry
    #                                 .make_recorder): step and data
    #                                 phases are spans, listeners (cluster
    #                                 heartbeat, sinks) ride its events;
    #                                 ``close`` detaches the run's sinks
    #                                 and exports the Chrome trace when the
    #                                 spec set a trace_dir.  None
    #                                 (hand-built Runs) = the process
    #                                 recorder itself.
    _data: Optional[Prefetcher] = field(default=None, repr=False)
    _jit_step: Optional[Callable] = field(default=None, repr=False)
    _warm: bool = field(default=False, repr=False)  # jit_step executed once

    @property
    def recorder(self):
        return (self.telemetry if self.telemetry is not None
                else process_recorder())

    def _mesh_scope(self):
        return (jax.set_mesh(self.mesh) if self.mesh is not None
                else contextlib.nullcontext())

    def _make_data(self, skip: int = 0) -> Prefetcher:
        """Fresh prefetcher over the family's seeded stream, with ``skip``
        batches consumed HOST-side first (raw iterator — no device placement
        for batches that are immediately discarded; resume at step 100k must
        not pay 100k device_puts).  A finite stream shorter than ``skip``
        simply ends — the Prefetcher sentinel then stops the training loop
        on its first draw."""
        s = self.spec
        stream = self.family.stream(self.cfg, s.batch, s.seq, s.seed)
        for _ in range(skip):
            try:
                next(stream)
            except StopIteration:
                break
        rec = self.recorder
        return Prefetcher(stream, place=make_placer(self.mesh, self.rules,
                                                    rec), recorder=rec)

    @property
    def data(self) -> Prefetcher:
        """Background-prefetching batch iterator, device-placed for the
        run's mesh.  Created on first access (so compiling a Run never
        starts threads)."""
        if self._data is None:
            self._data = self._make_data()
        return self._data

    @property
    def jit_step(self) -> Callable:
        """THE jitted train step — one compile cache, buffers donated.
        ``step()`` and ``fit()`` both go through it: jitting per call site
        (the old ``step`` re-wrapped without ``donate_argnums``) built two
        compile caches and kept an undonated copy of the params alive,
        doubling peak param memory when mixing the two."""
        if self._jit_step is None:
            self._jit_step = jax.jit(self.train_step, donate_argnums=(0, 1))
        return self._jit_step

    def step(self, batch, step_idx: int = 0):
        """Run one (jit) train step on an explicit batch; advances the run's
        params/opt_state and returns the metrics dict.  The call only
        enqueues the step (``step.dispatch``); the metrics are futures."""
        with self._mesh_scope():
            self.params, self.opt_state, metrics = dispatch_step(
                self.recorder, self.jit_step, self.params, self.opt_state,
                step_idx, batch)
        self._warm = True
        return metrics

    def _zero1_world(self):
        """This run's zero1 world layout (the ``checkpoint.replan`` meta
        record), or None when the run has no strip state."""
        if self.comm is None or self.mesh is None:
            return None
        from repro.checkpoint.replan import world_meta
        axes = tuple(a for a in ("pod", "data")
                     if a in self.mesh.axis_names)
        return world_meta([self.mesh.shape[a] for a in axes],
                          self.comm.hierarchical, self.comm.bucket_bytes)

    def _ckpt_meta(self):
        world = self._zero1_world()
        return {"zero1": world} if world is not None else None

    def _restore_replan(self, step: int, template=None):
        """Strict restore failed on shape: the checkpoint was saved at a
        different world size.  Re-plan the strip opt_state for THIS world
        (see ``checkpoint.replan`` for why this is exact); params are
        replicated, so their shapes never depend on G and restore
        strictly.  ``template`` is the opt_state tree to restore into —
        defaults to the run's own; the stale-sync interop path passes the
        INNER zero1 template when the checkpoint has the bare layout."""
        from repro.checkpoint.replan import replan_strip_state
        from repro.comm.bucketer import plan_buckets
        template = self.opt_state if template is None else template
        wrap = None
        if isinstance(template, dict) and set(template) == {"residual",
                                                            "zero1"}:
            # topk error-feedback wrapper: the residual is member-LOCAL
            # unsent gradient mass sized by the OLD world's padded buckets
            # — old members' mass has no owner in the new world, so there
            # is no exact conversion.  Replan only the inner strips (the
            # sub-template keeps the checkpoint's ``opt_state:zero1/...``
            # key paths) and restart the residual at zero: one step of
            # stiffer sparsification, the stale-buffer re-init trade.
            template = {"zero1": template["zero1"]}
            wrap = self._reinit_residual
        new_world = self._zero1_world()
        old_world = ckpt_lib.read_manifest(
            self.spec.ckpt_dir, step)["meta"].get("zero1")
        if new_world is None or old_world is None:
            raise ValueError(
                f"checkpoint step {step} does not match this run's shapes "
                "and carries no zero1 world meta to re-plan from")
        trees, _ = ckpt_lib.restore(self.spec.ckpt_dir, step,
                                    params=self.params)
        old_leaves = ckpt_lib.restore_loose(self.spec.ckpt_dir, step,
                                            "opt_state", template)
        plan = plan_buckets(self.params, new_world["G"],
                            self.comm.bucket_bytes)
        trees["opt_state"] = replan_strip_state(
            template, old_leaves, plan, old_world, new_world)
        if wrap is not None:
            trees["opt_state"] = wrap(trees["opt_state"]["zero1"])
        return trees

    def _stale_wrapped(self) -> bool:
        """True when this run's opt_state is the stale-sync wrapper dict
        around the inner zero1 strip state."""
        return (isinstance(self.opt_state, dict)
                and set(self.opt_state) == {"stale", "synced", "zero1"})

    def _reinit_stale(self, inner):
        """Wrap a restored INNER zero1 strip state for a stale-sync run:
        fresh zero staleness buffer, ``synced=0`` so the first resumed step
        applies its own reduce instead of garbage (see
        ``optim.dist.make_stale_sync_update``)."""
        return {"stale": tuple(jnp.zeros_like(s)
                               for s in self.opt_state["stale"]),
                "synced": jnp.zeros((), jnp.int32),
                "zero1": inner}

    def _ef_wrapped(self) -> bool:
        """True when this run's opt_state is the topk error-feedback
        wrapper dict around the inner zero1 strip state."""
        return (isinstance(self.opt_state, dict)
                and set(self.opt_state) == {"residual", "zero1"})

    def _reinit_residual(self, inner):
        """Wrap a restored INNER zero1 strip state for a topk EF run with a
        zero residual (this world's bucket shapes — the carried mass of a
        bare or foreign-world checkpoint is unrecoverable; see
        ``optim.dist.make_topk_ef_update``)."""
        return {"residual": tuple(jnp.zeros_like(r)
                                  for r in self.opt_state["residual"]),
                "zero1": inner}

    def restore(self, step: int):
        """Load checkpoint ``step`` from ``spec.ckpt_dir`` and place the
        restored trees back onto this run's shardings (zero1 strip
        opt_state lands on its data-axis strips, not unplaced on device 0).
        A zero1 checkpoint saved at a DIFFERENT world size is re-planned
        (``checkpoint.replan``) instead of rejected — the elastic
        shrink-and-resume path.  A stale-sync or topk-EF run additionally
        accepts a BARE zero1 checkpoint (the strip layouts are identical by
        construction): the inner state restores and the wrapper buffer
        (staleness carry / error-feedback residual) re-initializes, costing
        one synchronous / one stiffer-sparsified step on resume."""
        opt_tpl, wrap = self.opt_state, None
        if self._stale_wrapped() or self._ef_wrapped():
            keys = ckpt_lib.read_manifest(
                self.spec.ckpt_dir, step)["trees"].get("opt_state", ())
            if not any(k.startswith("opt_state:zero1/") for k in keys):
                opt_tpl = self.opt_state["zero1"]
                wrap = (self._reinit_stale if self._stale_wrapped()
                        else self._reinit_residual)
        try:
            trees, _ = ckpt_lib.restore(self.spec.ckpt_dir, step,
                                        params=self.params,
                                        opt_state=opt_tpl)
        except ValueError:
            trees = self._restore_replan(step, template=opt_tpl)
        if wrap is not None:
            trees["opt_state"] = wrap(trees["opt_state"])
        placed = jax.tree.map(
            lambda cur, new: jax.device_put(new, cur.sharding),
            {"params": self.params, "opt_state": self.opt_state}, trees)
        self.params, self.opt_state = placed["params"], placed["opt_state"]

    def fit(self, start_step: Optional[int] = None, log_fn=print):
        """Train for ``spec.steps`` steps; returns the metrics history.

        ``start_step=None`` (the default) resumes from the latest checkpoint
        in ``spec.ckpt_dir`` when one exists — params and opt_state are
        restored onto the run's shardings and the (deterministic, seeded)
        data stream is fast-forwarded one batch per completed step so the
        trajectory continues exactly where the interrupted run left off.
        Pass ``start_step=0`` to force a fresh run.  Per-step hooks attach
        to ``run.telemetry`` (``add_listener``) — every trainer phase is an
        event; the cluster launcher's heartbeat listens for the
        "step.dispatch" span, which replaced the old bare ``on_step``
        callback."""
        s = self.spec
        if start_step is None:
            start_step = 0
            if s.ckpt_dir:
                latest = ckpt_lib.latest_step(s.ckpt_dir)
                if latest is not None:
                    self.restore(latest)
                    start_step = latest
                    log_fn(f"resuming from checkpoint step {latest} "
                           f"({s.ckpt_dir})")
                    if latest < s.steps:
                        # re-align the data stream: drop any cached
                        # (already advanced) prefetcher and rebuild with
                        # one host-side skip per completed step.  Close the
                        # prefetcher directly — ``self.close()`` would also
                        # finalize the telemetry recorder mid-fit.
                        if self._data is not None:
                            self._data.close()
                        self._data = self._make_data(skip=latest)
        if start_step >= s.steps:
            # nothing to train (checkpoint at or past --steps): don't spin
            # up the prefetch thread / device-place batches for a no-op
            return []
        tcfg = TrainerConfig(total_steps=s.steps, log_every=s.log_every,
                             ckpt_every=s.ckpt_every, ckpt_dir=s.ckpt_dir,
                             ckpt_meta=self._ckpt_meta(),
                             recorder=self.recorder)
        trainer = Trainer(self.jit_step, tcfg, jit=False, warm=self._warm)
        with self._mesh_scope():
            self.params, self.opt_state, history = trainer.fit(
                self.params, self.opt_state, self.data,
                start_step=start_step, log_fn=log_fn)
        if history:
            # the first executed step always logs, so non-empty history ==
            # jit_step has really run (a source that dies before step one
            # must NOT mark the cache warm)
            self._warm = True
        return history

    def close(self):
        if self._data is not None:
            self._data.close()
            self._data = None
        rec = self.telemetry
        if rec is not None and getattr(rec, "enabled", False):
            # detaches the run's sinks; the process recorder's aggregates
            # stay readable after the run
            rec.close()
            if rec.trace_dir:
                # single-process runs merge their own Chrome trace; cluster
                # workers leave the merge to the supervisor, which sees every
                # process's trace_p*.jsonl
                from repro.cluster.spec import in_worker
                if not in_worker():
                    from repro.telemetry import merge_process_traces
                    merge_process_traces(rec.trace_dir)

    def __enter__(self) -> "Run":
        return self

    def __exit__(self, *exc):
        self.close()
