"""Training loop: metrics, timing, periodic checkpointing.

The paper's framework design (§4) separates data handling, compute and
communication; here the data pipeline prefetches on a background thread
(data/pipeline.py), compute+comm are one jit'd train_step (XLA owns the
overlap), and checkpointing is host-side."""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Optional

import jax

from repro.checkpoint import ckpt as ckpt_lib
from repro.telemetry.events import process_recorder


def _batch_items(batch) -> tuple:
    """(count, unit) of work in one batch for throughput accounting.

    LM/VLM batches carry a ``tokens`` (or codebook-label) tensor and report
    tok/s; the paper's own vision/ASR workloads (vgg-a, overfeat-fast,
    cd-dnn) have no token tensor — count batch rows and report samples/s
    instead of a flat 0 tok/s."""
    if "tokens" in batch:
        return int(batch["tokens"].size), "tok"
    if "codebook_labels" in batch:            # audio LM: seq x codebooks
        return int(batch["codebook_labels"].size), "tok"
    for v in batch.values():
        shape = getattr(v, "shape", ())
        if shape:
            return int(shape[0]), "samples"
    return 0, "samples"


def dispatch_step(rec, step_fn, params, opt_state, step: int, batch):
    """Enqueue one train step: the ``step.dispatch`` span around the
    (jitted) call, and the ``step.steps`` and ``step.samples`` counters.
    ``Run.step`` and ``Trainer.fit`` both dispatch through here."""
    with rec.span("step.dispatch", step=step + 1):
        out = step_fn(params, opt_state, step, batch)
    rec.count("step.steps")
    rec.count("step.samples", int(jax.tree.leaves(batch)[0].shape[0]))
    return out


@dataclass
class TrainerConfig:
    total_steps: int = 100
    log_every: int = 10
    ckpt_every: int = 0            # 0 = disabled
    ckpt_dir: Optional[str] = None
    ckpt_meta: Optional[dict] = None   # stored in the checkpoint manifest
    #                                    (zero1 world layout for elastic
    #                                    world-size replan — see
    #                                    checkpoint.replan)
    recorder: Optional[Any] = None     # telemetry Recorder; every phase of
    #                                    the loop becomes a span
    #                                    (step.dispatch, compile,
    #                                    ckpt_write) and listeners see each
    #                                    completed event — the general hook
    #                                    that replaced the bare on_step
    #                                    heartbeat callback (the cluster
    #                                    heartbeat rides the
    #                                    "step.dispatch" span's end event).
    #                                    None = the process recorder.


@dataclass
class Trainer:
    train_step: Callable            # (params, opt_state, step, batch) -> ...
    cfg: TrainerConfig = field(default_factory=TrainerConfig)
    jit: bool = True                # False: train_step is already jitted
    #                                 (e.g. Run.jit_step's shared cache)
    warm: bool = False              # True: step_fn has executed before —
    #                                 first step is NOT a compile, time it
    #                                 like any other (Run re-fit/resume)

    def fit(self, params, opt_state, data_iter: Iterable,
            start_step: int = 0, log_fn=print):
        history = []
        step_fn = jax.jit(self.train_step, donate_argnums=(0, 1)) \
            if self.jit else self.train_step
        rec = self.cfg.recorder if self.cfg.recorder is not None \
            else process_recorder()
        t0 = time.perf_counter()
        t_compile = 0.0
        items_seen, unit = 0, "tok"
        for step in range(start_step, self.cfg.total_steps):
            try:
                batch = next(data_iter)
            except StopIteration:
                # finite source ran dry (Prefetcher signals exhaustion as
                # StopIteration): end training with the progress made, do
                # not lose params/opt_state/history to an escaping exception
                log_fn(f"data exhausted at step {step} "
                       f"(of {self.cfg.total_steps}); stopping")
                break
            first = step == start_step and not self.warm
            params, opt_state, metrics = dispatch_step(
                rec, step_fn, params, opt_state, step, batch)
            if first:
                # the first step is dominated by jit compile: block, report
                # it separately, and restart the throughput clock so the
                # logged rate measures steady-state steps only
                with rec.span("compile", step=step + 1):
                    jax.block_until_ready(metrics["loss"])
                t_compile = time.perf_counter() - t0
                t0 = time.perf_counter()
            else:
                n, unit = _batch_items(batch)
                items_seen += n
            # the FINAL step always logs, so history[-1] is the true end
            # state (callers label checkpoints / report final loss from it)
            if ((step + 1) % self.cfg.log_every == 0 or step == start_step
                    or step + 1 == self.cfg.total_steps):
                loss = float(metrics["loss"])
                dt = time.perf_counter() - t0
                rate = items_seen / dt if dt > 0 else 0.0
                tail = (f"compile {t_compile:6.1f} s" if first
                        else f"{rate:9.0f} {unit}/s")
                log_fn(f"step {step + 1:5d}  loss {loss:8.4f}  "
                       f"gnorm {float(metrics['grad_norm']):7.3f}  "
                       f"lr {float(metrics['lr']):.2e}  {tail}")
                entry = dict(step=step + 1, loss=loss,
                             grad_norm=float(metrics["grad_norm"]))
                if first:
                    entry["compile_s"] = t_compile
                history.append(entry)
            if (self.cfg.ckpt_every and self.cfg.ckpt_dir
                    and (step + 1) % self.cfg.ckpt_every == 0):
                with rec.span("ckpt_write", step=step + 1):
                    ckpt_lib.save(self.cfg.ckpt_dir, step + 1,
                                  meta=self.cfg.ckpt_meta,
                                  params=params, opt_state=opt_state)
        return params, opt_state, history
