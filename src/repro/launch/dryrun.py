import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# --- everything below may import jax ---------------------------------------
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.api.families import adapter_for
from repro.configs import (
    ASSIGNED_ARCHS,
    INPUT_SHAPES,
    TPU_V5E,
    ModelConfig,
    get_config,
    get_input_shape,
)
from repro.core import hybrid, roofline
from repro.core.roofline import parse_collectives
from repro.core.sharding import ShardingCtx
from repro.launch import specs as sp
from repro.launch.mesh import make_production_mesh, mesh_devices
from repro.models import transformer
from repro.optim import AdamW, constant
from repro.train import make_train_step, zero1_state_shardings

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "experiments", "dryrun")


def _with_shardings(tree, shardings):
    return jax.tree.map(
        lambda t, s: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=s),
        tree, shardings)


def _unstack(tree):
    """Strip the leading per-repeat dim from stacked SDS trees."""
    def one(s):
        spec = s.sharding.spec if s.sharding is not None else None
        sh = None
        if spec is not None:
            sh = NamedSharding(s.sharding.mesh, P(*tuple(spec)[1:]))
        return jax.ShapeDtypeStruct(s.shape[1:], s.dtype, sharding=sh)
    return jax.tree.map(one, tree)


def model_flops(cfg: ModelConfig, kind: str, batch: int, seq: int) -> float:
    n = cfg.param_count(active_only=True)
    if kind == "train":
        return 6.0 * n * batch * seq
    if kind == "prefill":
        return 2.0 * n * batch * seq
    return 2.0 * n * batch  # decode: one token per request


def _combine(full_cost, unit_cost, full_hlo, unit_hlo, repeats: int):
    """XLA counts a while-loop body once; totals = full + (R-1) * unit."""
    r = repeats - 1
    flops = full_cost.get("flops", 0.0) + r * unit_cost.get("flops", 0.0)
    nbytes = full_cost.get("bytes accessed", 0.0) \
        + r * unit_cost.get("bytes accessed", 0.0)
    cf = parse_collectives(full_hlo)
    cu = parse_collectives(unit_hlo)
    cf.ring_bytes += r * cu.ring_bytes
    for k, v in cu.bytes_by_kind.items():
        cf.bytes_by_kind[k] = cf.bytes_by_kind.get(k, 0) + r * v
    for k, v in cu.count_by_kind.items():
        cf.count_by_kind[k] = cf.count_by_kind.get(k, 0) + r * v
    return flops, nbytes, cf


def lower_pair(arch: str, shape_name: str, multi_pod: bool,
               rules_override=None, cfg_override=None, verbose: bool = True):
    """Lower + compile one (arch x shape x mesh); return the report row."""
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = cfg_override if cfg_override is not None else get_config(arch)
    shape = get_input_shape(shape_name)
    if shape.kind == "train" and cfg.remat == "none":
        # activation checkpointing is required at this scale (baseline policy)
        cfg = cfg.replace(remat="block")
    plan = hybrid.plan(cfg, shape, mesh, TPU_V5E)
    rules = rules_override if rules_override is not None else plan.rules
    ctx = ShardingCtx(mesh, rules)
    long_ctx = shape_name == "long_500k"

    params = sp.abstract_params(cfg, mesh, rules)
    B, S = shape.global_batch, shape.seq_len
    R = cfg.pattern_repeats

    # ---- abstract activations/positions shared by the unit program --------
    x_sds = jax.ShapeDtypeStruct(
        (B, S if shape.kind != "decode" else 1, cfg.d_model), jnp.bfloat16,
        sharding=rules.sharding(("batch", "seq", "embed"),
                                (B, S, cfg.d_model), mesh))
    pos_shape = ((B, x_sds.shape[1], 3) if cfg.mrope
                 else (B, x_sds.shape[1]))
    pos_sds = jax.ShapeDtypeStruct(
        pos_shape, jnp.int32,
        sharding=rules.sharding(("batch", "seq", None)[: len(pos_shape)],
                                pos_shape, mesh))
    shared_sds = params.get("shared")
    blocks_unit = _unstack(params["blocks"])

    t0 = time.perf_counter()
    # ======================= full program ==================================
    if shape.kind == "train":
        # family adapter resolves loss/axes (repro.api registry) — the same
        # seam compile_run uses for the concrete runs
        family = adapter_for(cfg)
        opt = AdamW(weight_decay=0.01)
        opt_state = jax.eval_shape(opt.init, params)
        st_sh = zero1_state_shardings(opt_state, family.param_axes(cfg),
                                      mesh, rules)
        opt_state = _with_shardings(opt_state, st_sh)
        batch = sp.abstract_batch(cfg, shape, mesh, rules)
        step = make_train_step(family.make_loss(cfg, ctx), opt,
                               constant(1e-3))
        step_idx = jax.ShapeDtypeStruct((), jnp.int32)
        lowered = jax.jit(step).lower(params, opt_state, step_idx, batch)
    elif shape.kind == "prefill":
        batch = sp.abstract_batch(cfg, shape, mesh, rules)
        caches = sp.abstract_caches(cfg, shape, mesh, rules, long_ctx)

        def prefill_step(params, batch, caches):
            logits, _, caches = transformer.forward(
                params, cfg, ctx,
                tokens=batch.get("tokens"),
                embeds=batch.get("patch_embeds", batch.get("frame_embeds")),
                positions=batch.get("positions"),
                caches=caches, update_cache=True, long_ctx=long_ctx)
            return logits[:, -1], caches

        lowered = jax.jit(prefill_step).lower(params, batch, caches)
    else:  # decode
        dec = sp.abstract_decode_inputs(cfg, shape, mesh, rules, long_ctx)

        def serve_step(params, batch):
            logits, _, caches = transformer.forward(
                params, cfg, ctx,
                tokens=batch.get("tokens"),
                embeds=batch.get("frame_embeds"),
                positions=batch["positions"],
                caches=batch["caches"], long_ctx=long_ctx)
            return logits[:, -1], caches

        lowered = jax.jit(serve_step).lower(params, dec)
    t_lower = time.perf_counter() - t0

    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    # ======================= unit program (one repeat) =====================
    update_cache = shape.kind == "prefill"

    def unit_fwd(block_params, shared_p, x, positions, block_caches):
        body = transformer.make_scan_body(
            cfg, ctx, shared_p, positions, long_ctx=long_ctx,
            update_cache=update_cache, have_cache=block_caches is not None)
        carry = (x, jnp.zeros((), jnp.float32))
        xs = (block_params, block_caches) if block_caches is not None \
            else block_params
        (h, aux), ys = body(carry, xs)
        return h, aux, ys

    if shape.kind == "train":
        def unit_loss(block_params, shared_p, x, positions):
            h, aux, _ = unit_fwd(block_params, shared_p, x, positions, None)
            return jnp.sum(h.astype(jnp.float32)) * 1e-6 + aux

        grad_fn = jax.grad(unit_loss, argnums=(0, 2) if shared_sds is None
                           else (0, 1, 2))
        unit_lowered = jax.jit(grad_fn).lower(
            blocks_unit, shared_sds, x_sds, pos_sds)
    else:
        caches_stacked = (sp.abstract_caches(cfg, shape, mesh, rules,
                                             long_ctx))
        caches_unit = _unstack(caches_stacked)
        unit_lowered = jax.jit(unit_fwd).lower(
            blocks_unit, shared_sds, x_sds, pos_sds, caches_unit)
    unit_compiled = unit_lowered.compile()

    # ======================= combine + roofline ============================
    cost = compiled.cost_analysis()
    unit_cost = unit_compiled.cost_analysis()
    mem = compiled.memory_analysis()
    flops, nbytes, coll = _combine(cost, unit_cost, compiled.as_text(),
                                   unit_compiled.as_text(), R)
    if verbose:
        print(f"  memory_analysis: {mem}")
        print(f"  cost_analysis (scan-corrected): flops={flops:.3e} "
              f"bytes={nbytes:.3e} coll_ring={coll.ring_bytes:.3e}")
    mem_per_dev = 0.0
    if mem is not None:
        mem_per_dev = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                       + mem.temp_size_in_bytes - mem.alias_size_in_bytes)

    mf = model_flops(cfg, shape.kind, B, S)
    mesh_desc = "2x16x16" if multi_pod else "16x16"
    rep = roofline.RooflineReport(
        arch=arch, shape=shape_name, mesh=mesh_desc,
        n_devices=mesh_devices(mesh),
        hlo_flops_per_dev=flops, hlo_bytes_per_dev=nbytes, coll=coll,
        compute_s=flops / TPU_V5E.peak_flops,
        memory_s=nbytes / TPU_V5E.mem_bw,
        collective_s=coll.ring_bytes / TPU_V5E.link_bw,
        model_flops_total=mf, mem_per_dev_bytes=mem_per_dev)
    row = rep.row()
    row.update(t_lower_s=round(t_lower, 2), t_compile_s=round(t_compile, 2),
               plan_G=plan.G, plan_model_ways=plan.model_ways,
               plan_G_opt_head=plan.G_opt_head, plan_G_opt_ff=plan.G_opt_ff,
               plan_notes=list(plan.notes),
               mem_argument_gb=(mem.argument_size_in_bytes / 2**30
                                if mem else None),
               mem_temp_gb=(mem.temp_size_in_bytes / 2**30 if mem else None),
               mem_output_gb=(mem.output_size_in_bytes / 2**30
                              if mem else None))
    return row


def run_one(arch: str, shape_name: str, multi_pod: bool, force: bool = False,
            out_dir: str = RESULTS_DIR) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    mesh_desc = "2x16x16" if multi_pod else "16x16"
    fname = os.path.join(out_dir, f"{arch}__{shape_name}__{mesh_desc}.json")
    if os.path.exists(fname) and not force:
        with open(fname) as f:
            return json.load(f)
    print(f"[dryrun] {arch} x {shape_name} x {mesh_desc} ...", flush=True)
    try:
        row = lower_pair(arch, shape_name, multi_pod)
        row["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        traceback.print_exc()
        row = dict(arch=arch, shape=shape_name, mesh=mesh_desc,
                   status="error", error=f"{type(e).__name__}: {e}")
    with open(fname, "w") as f:
        json.dump(row, f, indent=1, default=str)
    print(f"[dryrun] -> {row.get('dominant', row['status'])} "
          f"(compile {row.get('t_compile_s', '-')}s)", flush=True)
    return row


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None, choices=list(ASSIGNED_ARCHS))
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = list(ASSIGNED_ARCHS) if (args.all or args.arch is None) \
        else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                row = run_one(arch, shape, mp, force=args.force)
                failures += row["status"] != "ok"
    print(f"done; failures={failures}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
