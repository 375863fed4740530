"""``correct`` against faults planted under a whole run.

Each test drives the harness's run (set-up, window, check) at a size the
CPU holds, past the look for a chip, with the cell's real limits.  A sound
run must come out correct; each fault a training cell can have, planted in
the timed path, and the control (the reference in bfloat16 put in the
program's place) must come out not correct.  The same holds with AdamW
named in the run's settings, where the program and the reference both run
it and the cell also judges the changes (``change1_gap``,
``change_last_gap``).
"""
import time

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import harness
import reference
import small

CELLS = [("vgg-a_b128_1chip", small.CNN), ("cd-dnn_b1024_1chip", small.DNN),
         ("cd-dnn_b1024_4chip", small.DNN)]
ADAMW_CELLS = CELLS[:2]        # one of each family
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2**31 + 77


# Under AdamW a cell judges the changes themselves.  Sound CPU runs read
# them under 1e-5, every planted fault and the control over 0.2; a cell on
# the chip sets its own limits from its own readings.
ADAMW = {"optimizer": "adamw"}
ADAMW_LIMITS = {"change1_gap": 0.05, "change_last_gap": 0.05}


def _run(name, cfg, limits=None, **run):
    c = small.small_cell(name, cfg)
    c["traffic"]["run"] = dict(c["traffic"]["run"], **run)
    c["limits"] = dict(c["limits"], **(limits or {}))
    return harness.run_cell(c, SEED, 0.5, False, time.perf_counter(),
                            jax.devices(), PEAKS)


def _patch_step(monkeypatch, wrap):
    """Build every run's train step through ``wrap(step) -> step``."""
    import repro.api.assemble as assemble
    real = assemble.make_train_step
    monkeypatch.setattr(assemble, "make_train_step",
                        lambda *a, **k: wrap(real(*a, **k)))


def _unchanged(step):
    """A step that returns its state unchanged."""
    def unchanged(params, opt_state, i, batch):
        _, _, metrics = step(params, opt_state, i, batch)
        return params, opt_state, metrics
    return unchanged


def _half(step):
    """A step that leaves half of the batch out, the mean over the rest."""
    def half(params, opt_state, i, batch):
        return step(params, opt_state, i, jax.tree.map(
            lambda x: x[: x.shape[0] // 2], batch))
    return half


def _patch_control(monkeypatch, name, cfg, **run):
    """The reference in bfloat16, put in the program's place."""
    c = small.small_cell(name, cfg)
    c["traffic"]["run"] = dict(c["traffic"]["run"], **run)

    def control(run, batches):
        pool = [next(batches) for _ in range(harness.CHECK_STEPS)]
        return reference.train_steps(
            harness.family(c["cfg"]), c["cfg"], c["traffic"]["run"], SEED,
            [jax.device_get(b) for b in pool], dtype=jnp.bfloat16)
    monkeypatch.setattr(harness, "first_steps", control)


@pytest.mark.parametrize("name,cfg", CELLS)
def test_sound_run_is_correct(name, cfg):
    out = _run(name, cfg)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("name,cfg", CELLS)
def test_state_left_unchanged_is_not_correct(name, cfg, monkeypatch):
    _patch_step(monkeypatch, _unchanged)
    out = _run(name, cfg)
    assert not out["correct"]
    assert out["checks"]["update_gap"]["value"] > 0.9


@pytest.mark.parametrize("name,cfg", CELLS)
def test_half_the_batch_left_out_is_not_correct(name, cfg, monkeypatch):
    _patch_step(monkeypatch, _half)
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
    _patch_control(monkeypatch, name, cfg)
    out = _run(name, cfg)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("name,cfg", ADAMW_CELLS)
def test_adamw_sound_run_is_correct(name, cfg):
    """The program's zero1 run under AdamW against the reference's AdamW,
    judged on the changes too."""
    out = _run(name, cfg, ADAMW_LIMITS, **ADAMW)
    assert out["correct"], out["checks"]
    assert set(ADAMW_LIMITS) <= set(out["checks"])


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "control_bf16"])
@pytest.mark.parametrize("name,cfg", ADAMW_CELLS)
def test_adamw_fault_is_not_correct(name, cfg, fault, monkeypatch):
    if fault == "control_bf16":
        _patch_control(monkeypatch, name, cfg, **ADAMW)
    else:
        _patch_step(monkeypatch, {"state_unchanged": _unchanged,
                                  "half_batch": _half}[fault])
    out = _run(name, cfg, ADAMW_LIMITS, **ADAMW)
    assert not out["correct"]
    checks = out["checks"]
    assert not any(checks[k]["value"] <= checks[k]["limit"]
                   for k in ADAMW_LIMITS), checks


@pytest.mark.parametrize("name,cfg", ADAMW_CELLS)
def test_adamw_half_batch_passes_grad_gap(name, cfg, monkeypatch):
    """Why the changes are compared: Adam's first step moves each element
    by about ``lr`` times the sign of its gradient, so the norm that
    ``grad_gap`` compares hardly moves when half of the batch is left
    out."""
    _patch_step(monkeypatch, _half)
    checks = _run(name, cfg, ADAMW_LIMITS, **ADAMW)["checks"]
    assert checks["grad_gap"]["value"] <= checks["grad_gap"]["limit"]
    assert checks["change1_gap"]["value"] > 10 * checks["change1_gap"][
        "limit"]
