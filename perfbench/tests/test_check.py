"""``correct`` against faults planted under a whole run.

Each test drives the harness's run (set-up, window, check) at a size the
CPU holds, past the look for a chip, with the cell's real limits.  A sound
run must come out correct; each fault a training cell can have, planted in
the timed path, and the control (the reference in bfloat16 put in the
program's place) must come out not correct.
"""
import time

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import harness
import reference
import small

CELLS = [("vgg-a_b128_1chip", small.CNN), ("cd-dnn_b1024_1chip", small.DNN)]
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2**31 + 77


def _run(name, cfg, **run):
    c = small.small_cell(name, cfg)
    c["traffic"]["run"] = dict(c["traffic"]["run"], **run)
    return harness.run_cell(c, SEED, 0.5, False, time.perf_counter(),
                            jax.devices(), PEAKS)


def _patch_step(monkeypatch, wrap):
    """Build every run's train step through ``wrap(step) -> step``."""
    import repro.api.assemble as assemble
    real = assemble.make_train_step
    monkeypatch.setattr(assemble, "make_train_step",
                        lambda *a, **k: wrap(real(*a, **k)))


@pytest.mark.parametrize("name,cfg", CELLS)
def test_sound_run_is_correct(name, cfg):
    out = _run(name, cfg)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("name,cfg", CELLS)
def test_state_left_unchanged_is_not_correct(name, cfg, monkeypatch):
    def wrap(step):
        def unchanged(params, opt_state, i, batch):
            _, _, metrics = step(params, opt_state, i, batch)
            return params, opt_state, metrics
        return unchanged
    _patch_step(monkeypatch, wrap)
    out = _run(name, cfg)
    assert not out["correct"]
    assert out["checks"]["update_gap"]["value"] > 0.9


@pytest.mark.parametrize("name,cfg", CELLS)
def test_half_the_batch_left_out_is_not_correct(name, cfg, monkeypatch):
    def wrap(step):
        def half(params, opt_state, i, batch):
            return step(params, opt_state, i, jax.tree.map(
                lambda x: x[: x.shape[0] // 2], batch))
        return half
    _patch_step(monkeypatch, wrap)
    assert not _run(name, cfg)["correct"]


@pytest.mark.parametrize("exchanged", [True, False])
@pytest.mark.parametrize("name,cfg", CELLS)
def test_exchange_left_out_is_not_correct(name, cfg, exchanged, monkeypatch):
    """Each member computes the gradient of its own rows.  With the zero1
    reduce in place that is still the sound update; with the reduce left
    out each member updates its strips with its own gradient, and no
    collective carries a gradient between chips.  Clipping is off: a
    member's own gradient norm is not the global one."""
    import repro.api.assemble as assemble
    import repro.optim.dist as dist
    from jax import lax

    from repro.api.families import adapter_for
    from repro.core.sharding import ShardingCtx

    program_cfg = harness.family(cfg).program_config(cfg)
    local_loss = adapter_for(program_cfg).make_loss(program_cfg,
                                                    ShardingCtx())

    def own_strip(sched, buf, wire_dtype, G):
        n = buf.shape[0] // G
        return lax.dynamic_slice(buf, (sched.owner_index() * n,), (n,))

    def member(params, batch):
        loss, grads = jax.value_and_grad(local_loss)(params, batch)
        return lax.pmean(loss, "data"), grads

    def make_train_step(loss_fn, optimizer, lr_schedule, grad_clip,
                        dist_update):
        def train_step(params, opt_state, i, batch):
            mesh = jax.sharding.get_abstract_mesh()
            loss, grads = jax.shard_map(
                member, mesh=mesh,
                in_specs=(P(), P("data")), out_specs=(P(), P()),
                check_vma=False)(params, batch)
            params, opt_state = dist_update(params, grads, opt_state,
                                            lr_schedule(i), i)
            return params, opt_state, {"loss": loss}
        return train_step

    if not exchanged:
        monkeypatch.setattr(dist, "reduce_mean", own_strip)
    monkeypatch.setattr(assemble, "make_train_step", make_train_step)
    assert _run(name, cfg, grad_clip=0.0)["correct"] == exchanged


@pytest.mark.parametrize("name,cfg", CELLS)
def test_control_in_bfloat16_is_not_correct(name, cfg, monkeypatch):
    """The reference in bfloat16, put in the program's place."""
    c = small.small_cell(name, cfg)

    def control(run, batches):
        pool = [next(batches) for _ in range(harness.CHECK_STEPS)]
        return reference.train_steps(
            harness.family(c["cfg"]), c["cfg"], c["traffic"]["run"], SEED,
            [jax.device_get(b) for b in pool], dtype=jnp.bfloat16)
    monkeypatch.setattr(harness, "first_steps", control)
    out = _run(name, cfg)
    assert not out["correct"], out["checks"]
