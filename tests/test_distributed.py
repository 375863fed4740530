"""Multi-device tests — each runs in a subprocess with
--xla_force_host_platform_device_count=8 so the rest of the suite keeps the
single real CPU device (per the dry-run isolation policy)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest


SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_part_reduce_broadcast_equals_psum():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, PartitionSpec as P
        from repro.core.collectives import part_reduce, part_broadcast, \\
            part_reduce_broadcast
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)

        def f(x):
            return part_reduce_broadcast(x, "data", 0)

        def g(x):
            return jax.lax.psum(x, "data")

        with jax.set_mesh(mesh):
            a = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P(),
                                      out_specs=P(), check_vma=False))(x)
            b = jax.jit(jax.shard_map(g, mesh=mesh, in_specs=P(),
                                      out_specs=P(), check_vma=False))(x)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
        print("OK")
    """)


def test_part_reduce_strips_sum_to_full_reduction():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, PartitionSpec as P
        from repro.core.collectives import part_reduce
        mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        x = jnp.arange(16, dtype=jnp.float32)

        def f(x):
            return part_reduce(x, "data", 0)

        with jax.set_mesh(mesh):
            strips = jax.jit(jax.shard_map(
                f, mesh=mesh, in_specs=P(), out_specs=P("data"),
                check_vma=False))(x)
        np.testing.assert_allclose(np.asarray(strips), np.asarray(x) * 8)
        print("OK")
    """)


def test_distributed_sgd_equals_serial_multi_axis():
    """The paper's §3.4 update over ("pod","data") == serial SGD."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.optim import MomentumSGD
        from repro.optim.dist import make_distributed_update
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(AxisType.Auto,) * 3)
        opt = MomentumSGD(momentum=0.9, weight_decay=0.01)
        params = {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4) / 7,
                  "b": jnp.ones((5,), jnp.float32)}
        grads = jax.tree.map(lambda p: jnp.cos(p), params)
        ref_p, ref_s = opt.update(grads, opt.init(params), params, 0.05)
        init_fn, update_fn = make_distributed_update(
            opt, mesh, data_axes=("pod", "data"))
        with jax.set_mesh(mesh):
            st = init_fn(params)
            new_p, st = jax.jit(update_fn)(params, grads, st, 0.05)
            ref_p2, ref_s2 = opt.update(grads, ref_s, ref_p, 0.05)
            new_p2, st = jax.jit(update_fn)(new_p, grads, st, 0.05)
        for k in params:
            np.testing.assert_allclose(np.asarray(new_p2[k]),
                                       np.asarray(ref_p2[k]), rtol=1e-5)
        print("OK")
    """)


def test_bucketed_update_equals_per_tensor_and_serial():
    """The comm-subsystem equivalence matrix: the bucketed §3.4 update ==
    the seed per-tensor update == the serial optimizer, across bucket sizes
    (smaller than one tensor, mid, larger than the whole tree), both wire
    dtypes, and both the flat and hierarchical ("pod","data") schedules."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.comm import CommConfig
        from repro.optim import MomentumSGD
        from repro.optim.dist import make_distributed_update
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(AxisType.Auto,) * 3)
        opt = MomentumSGD(momentum=0.9, weight_decay=0.01)
        params = {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4) / 7,
                  "b": jnp.ones((5,), jnp.float32),
                  "c": jnp.cos(jnp.arange(40, dtype=jnp.float32))}
        grads = jax.tree.map(lambda p: jnp.cos(p), params)

        # serial reference: two optimizer steps
        ref_p1, ref_s = opt.update(grads, opt.init(params), params, 0.05)
        ref_p2, _ = opt.update(grads, ref_s, ref_p1, 0.05)

        def run(comm):
            init_fn, update_fn = make_distributed_update(
                opt, mesh, data_axes=("pod", "data"), comm=comm)
            with jax.set_mesh(mesh):
                st = init_fn(params)
                p1, st = jax.jit(update_fn)(params, grads, st, 0.05)
                p2, st = jax.jit(update_fn)(p1, grads, st, 0.05)
            return p2

        # per-tensor (seed) path
        pt = run(None)
        for k in params:
            np.testing.assert_allclose(np.asarray(pt[k]),
                                       np.asarray(ref_p2[k]), rtol=1e-5)

        # bucket sizes: 8 B < any tensor; 64 B mid; 1 MiB > whole tree
        for bucket_bytes in (8, 64, 1 << 20):
            for hier in (False, True):
                got = run(CommConfig(bucket_bytes=bucket_bytes,
                                     hierarchical=hier))
                for k in params:
                    np.testing.assert_allclose(
                        np.asarray(got[k]), np.asarray(ref_p2[k]),
                        rtol=1e-5, err_msg=f"{bucket_bytes}/{hier}/{k}")

        # bf16 wire: same update within bf16 rounding of the gradients
        for hier in (False, True):
            got = run(CommConfig(bucket_bytes=64, reduce_dtype="bfloat16",
                                 hierarchical=hier))
            for k in params:
                np.testing.assert_allclose(
                    np.asarray(got[k]), np.asarray(ref_p2[k]),
                    rtol=2e-2, atol=2e-3, err_msg=f"bf16/{hier}/{k}")
        print("OK")
    """)


def test_hierarchical_init_state_lands_on_owner_strips():
    """Value-initialized optimizer state must be laid out in OWNER order:
    under the hierarchical schedule member (p, d) owns strip d*G_out + p,
    not its flat mesh index p*G_in + d.  Zeros-init optimizers mask this,
    so probe with state initialized FROM the parameter strips and an update
    that consumes it."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.comm import CommConfig
        from repro.optim.dist import make_distributed_update

        class StatefulOpt:
            # state = the parameter values themselves (an EMA-like init);
            # update mixes the state in, so misaligned strips change params
            def init(self, params):
                return jax.tree.map(lambda p: p + 0.0, params)
            def update(self, grads, state, params, lr):
                new_p = jax.tree.map(
                    lambda p, g, s: p - lr * g + 0.5 * (s - p),
                    params, grads, state)
                return new_p, state

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(AxisType.Auto,) * 3)
        opt = StatefulOpt()
        params = {"w": jnp.arange(24, dtype=jnp.float32).reshape(4, 6) / 11,
                  "b": jnp.cos(jnp.arange(7, dtype=jnp.float32))}
        grads = jax.tree.map(jnp.sin, params)
        ref_p, _ = opt.update(grads, opt.init(params), params, 0.05)
        for hier in (False, True):
            comm = CommConfig(bucket_bytes=1 << 20, hierarchical=hier)
            init_fn, update_fn = make_distributed_update(
                opt, mesh, data_axes=("pod", "data"), comm=comm)
            with jax.set_mesh(mesh):
                st = init_fn(params)
                p, st = jax.jit(update_fn)(params, grads, st, 0.05)
            for k in params:
                np.testing.assert_allclose(
                    np.asarray(p[k]), np.asarray(ref_p[k]), rtol=1e-6,
                    err_msg=f"hier={hier}/{k}")
        print("OK")
    """)


def test_zero1_train_step_through_bucketer():
    """make_train_step(dist_update=...) — the explicit ZeRO-1 path through
    the bucketed fusion-buffer collectives — matches the serial train step
    (loss, grad clip and all)."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.comm import CommConfig
        from repro.optim import AdamW
        from repro.optim.dist import make_distributed_update
        from repro.optim.schedule import constant
        from repro.train import make_train_step
        rng = np.random.default_rng(0)
        params = {"w": jnp.asarray(rng.normal(size=(6, 3)), jnp.float32),
                  "b": jnp.zeros((3,), jnp.float32)}
        batch = {"x": jnp.asarray(rng.normal(size=(16, 6)), jnp.float32),
                 "y": jnp.asarray(rng.normal(size=(16, 3)), jnp.float32)}
        def loss(p, b):
            return jnp.mean((b["x"] @ p["w"] + p["b"] - b["y"]) ** 2)
        opt = AdamW(weight_decay=0.1)
        sched = constant(1e-2)

        step_serial = make_train_step(loss, opt, sched)
        p1, s1, m1 = jax.jit(step_serial)(params, opt.init(params), 0, batch)
        p1, s1, m1 = jax.jit(step_serial)(p1, s1, 1, batch)

        mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
        init_fn, update_fn = make_distributed_update(
            opt, mesh, comm=CommConfig(bucket_bytes=64))
        step_dist = make_train_step(loss, opt, sched, dist_update=update_fn)
        with jax.set_mesh(mesh):
            p2, s2, m2 = jax.jit(step_dist)(params, init_fn(params), 0, batch)
            p2, s2, m2 = jax.jit(step_dist)(p2, s2, 1, batch)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-6)
        for k in params:
            np.testing.assert_allclose(np.asarray(p1[k]), np.asarray(p2[k]),
                                       rtol=1e-5, atol=1e-6)
        print("OK")
    """)


def test_overlapped_train_step_matches_serial():
    """The §3.1 backprop-overlapped zero1 step — bucket part-reduces issued
    inside the backward pass via the comm hooks — matches the serial train
    step (loss, grad clip, params) to float tolerance, for the flat and the
    hierarchical ("pod","data") schedules across bucket sizes."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.comm import CommConfig
        from repro.optim import AdamW
        from repro.optim.dist import make_overlapped_update
        from repro.optim.schedule import constant
        from repro.train import make_overlapped_train_step, make_train_step
        rng = np.random.default_rng(0)
        params = {"w": jnp.asarray(rng.normal(size=(6, 3)), jnp.float32),
                  "b": jnp.zeros((3,), jnp.float32),
                  "v": jnp.asarray(rng.normal(size=(40,)), jnp.float32)}
        batch = {"x": jnp.asarray(rng.normal(size=(16, 6)), jnp.float32),
                 "y": jnp.asarray(rng.normal(size=(16, 3)), jnp.float32)}
        def loss(p, b):
            pred = b["x"] @ p["w"] + p["b"] + jnp.mean(p["v"])
            return jnp.mean((pred - b["y"]) ** 2)
        opt = AdamW(weight_decay=0.1)
        sched = constant(1e-2)

        step_serial = make_train_step(loss, opt, sched)
        p1, s1, m1 = jax.jit(step_serial)(params, opt.init(params), 0, batch)
        p1, s1, m1 = jax.jit(step_serial)(p1, s1, 1, batch)

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(AxisType.Auto,) * 3)
        for bucket_bytes in (8, 64, 1 << 20):
            for hier in (False, True):
                comm = CommConfig(bucket_bytes=bucket_bytes,
                                  hierarchical=hier, overlap=True)
                init_fn, local_update = make_overlapped_update(
                    opt, mesh, data_axes=("pod", "data"), comm=comm)
                step_ov = make_overlapped_train_step(
                    loss, sched, mesh, ("pod", "data"), comm, local_update)
                with jax.set_mesh(mesh):
                    p2, s2, m2 = jax.jit(step_ov)(params, init_fn(params),
                                                  0, batch)
                    p2, s2, m2 = jax.jit(step_ov)(p2, s2, 1, batch)
                tag = f"{bucket_bytes}/{hier}"
                np.testing.assert_allclose(float(m1["loss"]),
                                           float(m2["loss"]),
                                           rtol=1e-5, err_msg=tag)
                np.testing.assert_allclose(float(m1["grad_norm"]),
                                           float(m2["grad_norm"]),
                                           rtol=1e-4, err_msg=tag)
                for k in params:
                    np.testing.assert_allclose(
                        np.asarray(p1[k]), np.asarray(p2[k]),
                        rtol=1e-5, atol=1e-6, err_msg=f"{tag}/{k}")
        print("OK")
    """)


def test_sharded_train_step_matches_single_device():
    """pjit train step on a 2x2 mesh == single-device step (same loss)."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.configs import get_config, smoke_variant
        from repro.core.sharding import ShardingCtx, ShardingRules
        from repro.core.params import Spec
        from repro.models import transformer
        from repro.optim import AdamW
        from repro.optim.schedule import constant
        from repro.train import make_train_step

        cfg = smoke_variant(get_config("llama3-8b"))
        key = jax.random.PRNGKey(0)
        params = transformer.init_params(cfg, key)
        tokens = jax.random.randint(key, (4, 32), 0, cfg.vocab_size)
        opt = AdamW()
        sched = constant(1e-3)

        # single device
        ctx1 = ShardingCtx()
        step1 = make_train_step(
            lambda p, b: transformer.lm_loss(p, cfg, ctx1, b), opt, sched)
        p1, s1, m1 = jax.jit(step1)(params, opt.init(params), 0,
                                    {"tokens": tokens})

        # 2x2 mesh
        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        rules = ShardingRules()
        ctx2 = ShardingCtx(mesh, rules)
        sp = transformer.param_specs(cfg)
        shardings = jax.tree.map(
            lambda s: rules.sharding(s.axes, s.shape, mesh), sp,
            is_leaf=lambda x: isinstance(x, Spec))
        params2 = jax.tree.map(jax.device_put, params, shardings)
        step2 = make_train_step(
            lambda p, b: transformer.lm_loss(p, cfg, ctx2, b), opt, sched)
        with jax.set_mesh(mesh):
            p2, s2, m2 = jax.jit(step2)(params2, opt.init(params2), 0,
                                        {"tokens": tokens})
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=2e-3)
        # updated params agree
        la, lb = jax.tree.leaves(p1), jax.tree.leaves(p2)
        for a, b in zip(la, lb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-2, atol=2e-3)
        print("OK")
    """)


def test_moe_arch_sharded_forward():
    """MoE forward under a mesh keeps loss equal to single-device."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.configs import get_config, smoke_variant
        from repro.core.sharding import ShardingCtx, ShardingRules
        from repro.core.params import Spec
        from repro.models import transformer
        cfg = smoke_variant(get_config("qwen2-moe-a2.7b"))
        key = jax.random.PRNGKey(0)
        params = transformer.init_params(cfg, key)
        batch = {"tokens": jax.random.randint(key, (4, 32), 0,
                                              cfg.vocab_size)}
        l1 = transformer.lm_loss(params, cfg, ShardingCtx(), batch)
        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        rules = ShardingRules()
        ctx = ShardingCtx(mesh, rules)
        sp = transformer.param_specs(cfg)
        sh = jax.tree.map(lambda s: rules.sharding(s.axes, s.shape, mesh),
                          sp, is_leaf=lambda x: isinstance(x, Spec))
        params2 = jax.tree.map(jax.device_put, params, sh)
        with jax.set_mesh(mesh):
            l2 = jax.jit(lambda p, b: transformer.lm_loss(p, cfg, ctx, b))(
                params2, batch)
        np.testing.assert_allclose(float(l1), float(l2), rtol=2e-3)
        print("OK")
    """)


def test_explicit_expert_parallel_matches_tensor_parallel():
    """§Perf V7: the shard_map+all_to_all expert-parallel MoE block equals
    the TP block (dropless capacities) on a 2x4 mesh."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.configs import get_config, smoke_variant
        from repro.core.sharding import ShardingCtx, ShardingRules
        from repro.core.params import init_tree
        from repro.models import moe
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = smoke_variant(get_config("mixtral-8x22b")).replace(
            moe_capacity_factor=4.0)
        p = init_tree(moe.moe_specs(cfg), jax.random.PRNGKey(0))
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(4, 16, cfg.d_model)), jnp.float32)
        ref, aux_ref = moe.moe_block(p, x, cfg, ShardingCtx())
        ctx = ShardingCtx(mesh, ShardingRules())
        with jax.set_mesh(mesh):
            out, aux = jax.jit(lambda p, x: moe.moe_ep_block(
                p, x, cfg, ctx))(p, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(float(aux), float(aux_ref), rtol=1e-5)
        print("OK")
    """)


def test_seq_shard_carry_preserves_loss():
    """§Perf L4: sequence-sharded residual carries change memory layout,
    not math."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.configs import get_config, smoke_variant
        from repro.core.sharding import ShardingCtx, ShardingRules
        from repro.core.params import Spec
        from repro.models import transformer
        cfg = smoke_variant(get_config("llama3-8b"))
        key = jax.random.PRNGKey(0)
        params = transformer.init_params(cfg, key)
        batch = {"tokens": jax.random.randint(key, (4, 32), 0,
                                              cfg.vocab_size)}
        l0 = transformer.lm_loss(params, cfg, ShardingCtx(), batch)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        rules = ShardingRules()
        ctx = ShardingCtx(mesh, rules)
        cfg2 = cfg.replace(seq_shard_carry=True, remat="block")
        with jax.set_mesh(mesh):
            l1 = jax.jit(lambda p, b: transformer.lm_loss(
                p, cfg2, ctx, b))(params, batch)
        np.testing.assert_allclose(float(l0), float(l1), rtol=2e-3)
        print("OK")
    """)


def test_sharded_decode_attention_matches_reference():
    """§Perf D1: shard_map partial-softmax decode == unsharded decode."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.configs import get_config, smoke_variant
        from repro.core.sharding import ShardingCtx, ShardingRules
        from repro.core.params import init_tree
        from repro.models import layers
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        cfg = smoke_variant(get_config("gemma2-2b")).replace(
            attn_logit_softcap=50.0)
        p = init_tree(layers.attn_specs(cfg), jax.random.PRNGKey(0))
        B, C = 4, 32
        rng = np.random.default_rng(0)
        shp = (B, C, cfg.num_kv_heads, cfg.head_dim)
        cache = layers.AttnCache(
            jnp.asarray(rng.normal(size=shp), jnp.float32),
            jnp.asarray(rng.normal(size=shp), jnp.float32),
            jnp.asarray(20, jnp.int32))
        x = jnp.asarray(rng.normal(size=(B, 1, cfg.d_model)), jnp.float32)
        pos = jnp.full((B, 1), 20, jnp.int32)
        ref_out, ref_c = layers.attention_block(
            p, x, cfg, ShardingCtx(), pos, window=0, cache=cache)
        rules = ShardingRules().with_overrides(cache_seq=("model",))
        ctx = ShardingCtx(mesh, rules)
        with jax.set_mesh(mesh):
            out, nc = jax.jit(lambda p, x, c: layers.attention_block(
                p, x, cfg, ctx, pos, window=0, cache=c))(p, x, cache)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(nc.k), np.asarray(ref_c.k),
                                   rtol=1e-5, atol=1e-5)
        assert int(nc.length) == int(ref_c.length) == 21
        print("OK")
    """)


def test_ep_training_end_to_end_matches_tp():
    """A full train step through the EP MoE path (shard_map all_to_all under
    scan + remat + grad) matches the single-device TP path."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.configs import get_config, smoke_variant
        from repro.core.sharding import ShardingCtx, ShardingRules
        from repro.core.params import Spec
        from repro.models import transformer
        from repro.optim import AdamW
        from repro.optim.schedule import constant
        from repro.train import make_train_step
        cfg0 = smoke_variant(get_config("mixtral-8x22b")).replace(
            moe_capacity_factor=4.0)
        key = jax.random.PRNGKey(0)
        params = transformer.init_params(cfg0, key)
        batch = {"tokens": jax.random.randint(key, (4, 32), 0,
                                              cfg0.vocab_size)}
        opt = AdamW()
        step0 = make_train_step(lambda p, b: transformer.lm_loss(
            p, cfg0, ShardingCtx(), b), opt, constant(1e-3))
        p0, _, m0 = jax.jit(step0)(params, opt.init(params), 0, batch)

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        # E=4 experts divisible by model=4 -> EP path (pad=0+gate override)
        cfg1 = cfg0.replace(moe_expert_pad=4, remat="block")
        # pad params to Ep=8
        def pad_fix(path, a):
            ks = jax.tree_util.keystr(path)
            if any(w in ks for w in ["w_gate", "w_up", "w_down"]):
                return jnp.pad(a, [(0, 0), (0, 4)] + [(0, 0)] * (a.ndim - 2))
            return a
        params1 = jax.tree_util.tree_map_with_path(pad_fix, params)
        rules = ShardingRules()
        ctx = ShardingCtx(mesh, rules)
        step1 = make_train_step(lambda p, b: transformer.lm_loss(
            p, cfg1, ctx, b), opt, constant(1e-3))
        with jax.set_mesh(mesh):
            p1, _, m1 = jax.jit(step1)(params1, opt.init(params1), 0, batch)
        np.testing.assert_allclose(float(m0["loss"]), float(m1["loss"]),
                                   rtol=3e-3)
        np.testing.assert_allclose(float(m0["grad_norm"]),
                                   float(m1["grad_norm"]), rtol=2e-2)
        print("OK")
    """)


def test_pallas_ring_backend_matches_lax_collectives():
    """Backend interchangeability at the primitive level: PallasRingBackend's
    part_reduce / part_broadcast / psum agree with LaxBackend (same strip
    OWNERS, same values) over a single axis and a composed ("pod","data")
    group, in fp32 and the bf16 wire dtype."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, PartitionSpec as P
        from repro.comm import LaxBackend, PallasRingBackend
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(AxisType.Auto,) * 3)
        lax_b, ring_b = LaxBackend(), PallasRingBackend()
        rng = np.random.default_rng(0)
        for axes, spec in (("data", P("data")),
                           (("pod", "data"), P(("pod", "data")))):
            for dtype in (jnp.float32, jnp.bfloat16):
                x = jnp.asarray(rng.normal(size=(32,)), dtype)

                def f(b):
                    def inner(x):
                        strip = b.part_reduce(x, axes)
                        full = b.part_broadcast(strip, axes)
                        return strip, full, b.psum(x, axes)
                    return inner

                with jax.set_mesh(mesh):
                    outs = {}
                    for name, b in (("lax", lax_b), ("ring", ring_b)):
                        outs[name] = jax.jit(jax.shard_map(
                            f(b), mesh=mesh, in_specs=P(),
                            out_specs=(spec, P(), P()),
                            check_vma=False))(x)
                tol = 1e-6 if dtype == jnp.float32 else 3e-2
                for a, b2, what in zip(outs["lax"], outs["ring"],
                                       ("strips", "full", "psum")):
                    np.testing.assert_allclose(
                        np.asarray(a, np.float32), np.asarray(b2, np.float32),
                        rtol=tol, atol=tol, err_msg=f"{axes}/{dtype}/{what}")
        print("OK")
    """)


def test_pallas_ring_zero1_matches_serial():
    """The backend-equivalence matrix for training: zero1 through the
    pallas-ring collectives == the serial optimizer — monolithic and
    backprop-overlapped, flat and hierarchical ("pod","data"), across
    bucket sizes."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.comm import CommConfig
        from repro.optim import AdamW
        from repro.optim.dist import make_distributed_update, \\
            make_overlapped_update
        from repro.optim.schedule import constant
        from repro.train import make_overlapped_train_step, make_train_step
        rng = np.random.default_rng(0)
        params = {"w": jnp.asarray(rng.normal(size=(6, 3)), jnp.float32),
                  "b": jnp.zeros((3,), jnp.float32),
                  "v": jnp.asarray(rng.normal(size=(40,)), jnp.float32)}
        batch = {"x": jnp.asarray(rng.normal(size=(16, 6)), jnp.float32),
                 "y": jnp.asarray(rng.normal(size=(16, 3)), jnp.float32)}
        def loss(p, b):
            pred = b["x"] @ p["w"] + p["b"] + jnp.mean(p["v"])
            return jnp.mean((pred - b["y"]) ** 2)
        opt = AdamW(weight_decay=0.1)
        sched = constant(1e-2)

        step_serial = make_train_step(loss, opt, sched)
        p1, s1, m1 = jax.jit(step_serial)(params, opt.init(params), 0, batch)
        p1, s1, m1 = jax.jit(step_serial)(p1, s1, 1, batch)

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(AxisType.Auto,) * 3)
        for bucket_bytes in (64, 1 << 20):
            for hier in (False, True):
                for overlap in (False, True):
                    comm = CommConfig(bucket_bytes=bucket_bytes,
                                      hierarchical=hier, overlap=overlap,
                                      backend="pallas-ring")
                    if overlap:
                        init_fn, local_update = make_overlapped_update(
                            opt, mesh, data_axes=("pod", "data"), comm=comm)
                        step = make_overlapped_train_step(
                            loss, sched, mesh, ("pod", "data"), comm,
                            local_update)
                    else:
                        init_fn, update_fn = make_distributed_update(
                            opt, mesh, data_axes=("pod", "data"), comm=comm)
                        step = make_train_step(loss, opt, sched,
                                               dist_update=update_fn)
                    with jax.set_mesh(mesh):
                        p2, s2, m2 = jax.jit(step)(params, init_fn(params),
                                                   0, batch)
                        p2, s2, m2 = jax.jit(step)(p2, s2, 1, batch)
                    tag = f"{bucket_bytes}/hier={hier}/overlap={overlap}"
                    np.testing.assert_allclose(float(m1["loss"]),
                                               float(m2["loss"]),
                                               rtol=1e-5, err_msg=tag)
                    for k in params:
                        np.testing.assert_allclose(
                            np.asarray(p1[k]), np.asarray(p2[k]),
                            rtol=1e-5, atol=1e-6, err_msg=f"{tag}/{k}")
        print("OK")
    """)


def test_phase_pipeline_bit_exact_vs_seed_builders():
    """The refactor contract: the UpdatePlan phase pipeline is BIT-equal to
    the pre-refactor builders for every existing mode.  The seed
    implementations (per-tensor schedule, bucketed monolithic update,
    bucketed apply+broadcast tail) are copied verbatim below and both
    stacks run two momentum steps from the same start; params and state
    leaves must match with assert_array_equal — no tolerance.  The seed
    packs every bucket into a 1-D buffer; the pipeline keeps a row-blocked
    bucket's (G, rows/G, cols) state, compared after reshape(G, -1)."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import lax
        from jax.sharding import AxisType, NamedSharding, PartitionSpec as P
        from repro.comm import CommConfig
        from repro.comm import bucketer
        from repro.comm.bucketer import plan_buckets
        from repro.comm.schedule import group_axes, make_schedule
        from repro.core.collectives import flatten_pad, strip_broadcast, \\
            strip_reduce
        from repro.optim import MomentumSGD
        from repro.optim.dist import _state_spec, make_distributed_update, \\
            make_overlapped_update, owner_perm

        # ---- seed builders, verbatim from the pre-refactor module ----
        def pack_bucket(flat_leaves, bucket):
            parts = [flat_leaves[s.index].reshape(-1) for s in bucket.slots]
            pad = bucket.padded_size - bucket.size
            if pad:
                parts.append(jnp.zeros((pad,), parts[0].dtype))
            return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

        def unpack_buckets(buffers, plan):
            out = [None] * plan.n_leaves
            for buf, bucket in zip(buffers, plan.buckets):
                for s in bucket.slots:
                    out[s.index] = lax.slice(
                        buf, (s.offset,), (s.offset + s.size,)).reshape(
                            s.shape)
            return out

        def seed_bucketed_init(optimizer, mesh, axes, axis_arg, G, comm):
            perm = owner_perm(comm.hierarchical,
                              [mesh.shape[a] for a in axes])
            def _strip_init(params):
                plan = plan_buckets(params, G, comm.bucket_bytes)
                flat = jax.tree.leaves(params)
                strips = [pack_bucket(flat, b).reshape(G, -1)
                          for b in plan.buckets]
                if perm is not None:
                    strips = [s[perm] for s in strips]
                return optimizer.init(strips)
            def init_fn(params):
                with jax.set_mesh(mesh):
                    state = jax.jit(_strip_init)(params)
                sh = jax.tree.map(
                    lambda s: NamedSharding(mesh, _state_spec(s, axis_arg)),
                    state)
                return jax.tree.map(jax.device_put, state, sh)
            return init_fn

        def seed_apply(optimizer, sched, plan, G, params, g_strips,
                       opt_state, lr):
            flat_params, treedef = jax.tree.flatten(params)
            i = sched.owner_index()
            p_strips = []
            for b in plan.buckets:
                pbuf = pack_bucket(flat_params, b)
                n = b.padded_size // G
                p_strips.append(lax.dynamic_slice(pbuf, (i * n,), (n,)))
            s_local = jax.tree.map(
                lambda s: s[0] if s.ndim >= 2 else s, opt_state)
            new_p_strips, new_state = optimizer.update(g_strips, s_local,
                                                       p_strips, lr)
            bufs = [sched.broadcast(ps)
                    for ps in jax.tree.leaves(new_p_strips)]
            new_params = jax.tree.unflatten(treedef,
                                            unpack_buckets(bufs, plan))
            new_state = jax.tree.map(
                lambda s: s[None] if s.ndim >= 1 else s, new_state)
            return new_params, new_state

        def seed_bucketed(optimizer, mesh, data_axes, comm):
            axes, axis_arg, G = group_axes(mesh, data_axes)
            init_fn = seed_bucketed_init(optimizer, mesh, axes, axis_arg,
                                         G, comm)
            def _update(params, grads, opt_state, lr):
                plan = plan_buckets(params, G, comm.bucket_bytes)
                sched = make_schedule(axis_arg, comm.hierarchical,
                                      comm.backend, comm.cross_backend)
                flat_grads = jax.tree.leaves(grads)
                g_strips = [sched.reduce(pack_bucket(flat_grads, b),
                                         comm.wire_dtype) / G
                            for b in plan.buckets]
                return seed_apply(optimizer, sched, plan, G, params,
                                  g_strips, opt_state, lr)
            def update_fn(params, grads, opt_state, lr):
                pspec = jax.tree.map(lambda _: P(), params)
                sspec = jax.tree.map(
                    lambda s: _state_spec(s, axis_arg), opt_state)
                fn = jax.shard_map(_update, mesh=mesh,
                                   in_specs=(pspec, pspec, sspec, P()),
                                   out_specs=(pspec, sspec),
                                   check_vma=False)
                return fn(params, grads, opt_state, lr)
            return init_fn, update_fn

        def seed_per_tensor(optimizer, mesh, data_axes):
            axes, axis_arg, G = group_axes(mesh, data_axes)
            def _strip_init(params):
                def per_tensor(p):
                    return flatten_pad(p, G).reshape(G, -1)
                return optimizer.init(jax.tree.map(per_tensor, params))
            def init_fn(params):
                with jax.set_mesh(mesh):
                    state = jax.jit(_strip_init)(params)
                sh = jax.tree.map(
                    lambda s: NamedSharding(mesh, _state_spec(s, axis_arg)),
                    state)
                return jax.tree.map(jax.device_put, state, sh)
            def _update(params, grads, opt_state, lr):
                flat_params, treedef = jax.tree.flatten(params)
                flat_grads = jax.tree.leaves(grads)
                g_strips = [strip_reduce(g, axis_arg) for g in flat_grads]
                i = make_schedule(axis_arg).owner_index()
                p_strips = []
                for p in flat_params:
                    flat = flatten_pad(p, G)
                    n = flat.size // G
                    p_strips.append(lax.dynamic_slice(flat, (i * n,), (n,)))
                g_tree = jax.tree.unflatten(treedef, g_strips)
                p_tree = jax.tree.unflatten(treedef, p_strips)
                s_local = jax.tree.map(
                    lambda s: s[0] if s.ndim >= 2 else s, opt_state)
                new_p_strips, new_state = optimizer.update(
                    g_tree, s_local, p_tree, lr)
                new_flat = [strip_broadcast(ps, axis_arg, p.shape)
                            for p, ps in zip(flat_params,
                                             jax.tree.leaves(new_p_strips))]
                new_params = jax.tree.unflatten(treedef, new_flat)
                new_state = jax.tree.map(
                    lambda s: s[None] if s.ndim >= 1 else s, new_state)
                return new_params, new_state
            def update_fn(params, grads, opt_state, lr):
                pspec = jax.tree.map(lambda _: P(), params)
                sspec = jax.tree.map(
                    lambda s: _state_spec(s, axis_arg), opt_state)
                fn = jax.shard_map(_update, mesh=mesh,
                                   in_specs=(pspec, pspec, sspec, P()),
                                   out_specs=(pspec, sspec),
                                   check_vma=False)
                return fn(params, grads, opt_state, lr)
            return init_fn, update_fn

        # ---- the matrix ----
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(AxisType.Auto,) * 3)
        opt = MomentumSGD(momentum=0.9, weight_decay=0.01)
        params = {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4) / 7,
                  "b": jnp.ones((5,), jnp.float32),
                  "c": jnp.cos(jnp.arange(40, dtype=jnp.float32)),
                  # 32 rows: 8 a member at G = 4, row-blocked alone
                  "r": jnp.sin(jnp.arange(128, dtype=jnp.float32)
                               ).reshape(32, 4)}
        g1 = jax.tree.map(jnp.cos, params)
        g2 = jax.tree.map(jnp.sin, params)

        def two_steps(init_fn, update_fn):
            with jax.set_mesh(mesh):
                st = init_fn(params)
                p, st = jax.jit(update_fn)(params, g1, st, 0.05)
                p, st = jax.jit(update_fn)(p, g2, st, 0.05)
            return p, st

        def check(tag, seed_pair, new_pair):
            ps, ss = two_steps(*seed_pair)
            pn, sn = two_steps(*new_pair)
            for k in params:
                np.testing.assert_array_equal(
                    np.asarray(ps[k]), np.asarray(pn[k]),
                    err_msg=f"{tag}/params/{k}")
            # seed per-tensor state is tree-shaped, the pipeline's is a
            # strip list — leaves match positionally
            for a, b in zip(jax.tree.leaves(ss), jax.tree.leaves(sn)):
                a, b = np.asarray(a), np.asarray(b)
                if b.ndim >= 2:
                    b = b.reshape(b.shape[0], -1)
                np.testing.assert_array_equal(a, b, err_msg=f"{tag}/state")

        check("per-tensor",
              seed_per_tensor(opt, mesh, ("pod", "data")),
              make_distributed_update(opt, mesh, data_axes=("pod", "data"),
                                      comm=None))
        for comm in (CommConfig(bucket_bytes=64),
                     CommConfig(bucket_bytes=64, hierarchical=True),
                     CommConfig(bucket_bytes=64, backend="pallas-ring"),
                     CommConfig(bucket_bytes=1 << 20, hierarchical=True,
                                reduce_dtype="bfloat16")):
            tag = (f"bkt{comm.bucket_bytes}/hier={comm.hierarchical}"
                   f"/{comm.backend}/{comm.reduce_dtype}")
            check(tag,
                  seed_bucketed(opt, mesh, ("pod", "data"), comm),
                  make_distributed_update(opt, mesh,
                                          data_axes=("pod", "data"),
                                          comm=comm))

        # overlapped tail (apply + broadcast on pre-reduced strips): seed
        # _apply_strip_update vs the pipeline's local_update, same inputs
        comm = CommConfig(bucket_bytes=64, hierarchical=True, overlap=True)
        axes, axis_arg, G = group_axes(mesh, ("pod", "data"))
        init_new, local_new = make_overlapped_update(
            opt, mesh, data_axes=("pod", "data"), comm=comm)
        init_seed = seed_bucketed_init(opt, mesh, axes, axis_arg, G, comm)

        def driver(local_update, pack):
            # each stack's strips in its own layout: the seed's 1-D, the
            # pipeline's row blocks
            def _inner(params, grads, opt_state, lr):
                plan = plan_buckets(params, G, comm.bucket_bytes)
                sched = make_schedule(axis_arg, comm.hierarchical,
                                      comm.backend, comm.cross_backend)
                flat_grads = jax.tree.leaves(grads)
                g_strips = [sched.reduce(pack(flat_grads, b),
                                         comm.wire_dtype) / G
                            for b in plan.buckets]
                return local_update(params, g_strips, opt_state, lr)
            def update_fn(params, grads, opt_state, lr):
                pspec = jax.tree.map(lambda _: P(), params)
                sspec = jax.tree.map(
                    lambda s: _state_spec(s, axis_arg), opt_state)
                fn = jax.shard_map(_inner, mesh=mesh,
                                   in_specs=(pspec, pspec, sspec, P()),
                                   out_specs=(pspec, sspec),
                                   check_vma=False)
                return fn(params, grads, opt_state, lr)
            return update_fn

        def seed_local(params, g_strips, opt_state, lr):
            plan = plan_buckets(params, G, comm.bucket_bytes)
            sched = make_schedule(axis_arg, comm.hierarchical,
                                  comm.backend, comm.cross_backend)
            return seed_apply(opt, sched, plan, G, params, g_strips,
                              opt_state, lr)

        check("overlap-tail",
              (init_seed, driver(seed_local, pack_bucket)),
              (init_new, driver(local_new, bucketer.pack_bucket)))
        print("OK")
    """)


def test_gossip_backend_pair_exchange_rotation():
    """comm.backends.gossip semantics at the primitive level: at step t
    member i's part_reduce strip is (own chunk i + chunk i of partner
    (i - s) % G) * G/2 with the GossipGraD shift s = 1 + t % (G-1) — so
    the schedule's /G yields the PAIR mean, every member is in exactly one
    exchange per step, and the rotation sweeps all G-1 partners before
    repeating."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, PartitionSpec as P
        from repro.comm.backends import get_backend
        from repro.comm.schedule import bind_step

        G, n = 8, 16
        mesh = jax.make_mesh((G,), ("data",), axis_types=(AxisType.Auto,))
        x = np.arange(G * n, dtype=np.float32).reshape(G, n) / 3.0
        chunks = x.reshape(G, G, n // G)      # [member, chunk, elems]

        for step in range(2 * (G - 1) + 1):
            b = bind_step(get_backend("gossip"), jnp.asarray(step))
            def f(row):
                return b.part_reduce(row[0], "data")[None]
            with jax.set_mesh(mesh):
                got = jax.jit(jax.shard_map(
                    f, mesh=mesh, in_specs=P("data"),
                    out_specs=P("data"), check_vma=False))(jnp.asarray(x))
            s = 1 + step % (G - 1)
            want = np.stack([(chunks[i, i] + chunks[(i - s) % G, i])
                             * (G / 2.0) for i in range(G)])
            np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                                       err_msg=f"step={step}")
            # symmetry: i's partner (i-s) has i as ITS partner at the same
            # step iff shifts cancel mod G — verified implicitly by the
            # ppermute pair construction; check every member appears once
            partners = {(i, (i - s) % G) for i in range(G)}
            assert len({p for p, _ in partners}) == G
        print("OK")
    """)


def test_gossip_g2_matches_zero1_bitwise():
    """At G=2 the rotation is degenerate (the only partner is the other
    member), so gossip IS full synchronous data parallelism: the gossip
    update must be bitwise identical to zero1, params and state."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.comm import CommConfig
        from repro.optim import MomentumSGD
        from repro.optim.dist import make_distributed_update
        mesh = jax.make_mesh((2,), ("data",), axis_types=(AxisType.Auto,))
        opt = MomentumSGD(momentum=0.9)
        params = {"w": jnp.linspace(-1, 1, 37, dtype=jnp.float32),
                  "b": jnp.cos(jnp.arange(11, dtype=jnp.float32))}
        grads = [jax.tree.map(lambda p: jnp.sin(p + t), params)
                 for t in range(3)]

        def run(backend):
            comm = CommConfig(bucket_bytes=64, backend=backend)
            init_fn, update_fn = make_distributed_update(
                opt, mesh, comm=comm)
            with jax.set_mesh(mesh):
                p, st = params, init_fn(params)
                for t, g in enumerate(grads):
                    p, st = jax.jit(update_fn)(p, g, st, 0.05, t)
            return p, st

        pz, sz = run("lax")
        pg, sg = run("gossip")
        for k in params:
            np.testing.assert_array_equal(np.asarray(pz[k]),
                                          np.asarray(pg[k]), err_msg=k)
        for a, b in zip(jax.tree.leaves(sz), jax.tree.leaves(sg)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("OK")
    """, devices=2)


def test_stale_sync_applies_previous_steps_gradient():
    """make_stale_sync_update semantics: step 0 applies its OWN reduce
    (empty carry), step t>0 applies step t-1's — so feeding gradients
    [g0, g1, g2] must land exactly where the serial optimizer lands on
    [g0, g0, g1], and the carried buffer always holds the LAST reduce."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.comm import CommConfig
        from repro.optim import MomentumSGD
        from repro.optim.dist import make_stale_sync_update
        mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
        opt = MomentumSGD(momentum=0.9, weight_decay=0.01)
        params = {"w": jnp.arange(24, dtype=jnp.float32).reshape(4, 6) / 11,
                  "b": jnp.ones((7,), jnp.float32)}
        gs = [jax.tree.map(lambda p: jnp.cos(p + t), params)
              for t in range(3)]

        init_fn, update_fn = make_stale_sync_update(
            opt, mesh, comm=CommConfig(bucket_bytes=64))
        with jax.set_mesh(mesh):
            p, st = params, init_fn(params)
            assert int(st["synced"]) == 0
            for t, g in enumerate(gs):
                p, st = jax.jit(update_fn)(p, g, st, 0.05, t)
                assert int(st["synced"]) == 1

        # serial reference on the staleness-shifted gradient sequence
        rp, rs = params, opt.init(params)
        for g in [gs[0], gs[0], gs[1]]:
            rp, rs = opt.update(g, rs, rp, 0.05)
        for k in params:
            np.testing.assert_allclose(np.asarray(p[k]), np.asarray(rp[k]),
                                       rtol=1e-5, atol=1e-7, err_msg=k)
        print("OK")
    """, devices=4)


# ---------------------------------------------------------------------------
# row-block strips: a single-leaf bucket keeps the leaf's (rows, cols) view
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape,dtype,group,want", [
    ((2048, 2048), "float32", 4, (2048, 2048)),
    ((3, 3, 512, 512), "float32", 4, (4608, 512)),
    ((32, 24), "float32", 4, (32, 24)),          # 8 rows a block
    ((16, 8), "float32", 4, None),               # 4 rows: under a tile
    ((12, 8), "float32", 1, None),               # 12 rows: not whole tiles
    ((440, 2048), "float32", 1, (440, 2048)),
    ((440, 2048), "float32", 4, None),           # 110 rows a block
    ((64, 128), "bfloat16", 4, (64, 128)),       # 16 rows: one bf16 tile
    ((32, 128), "bfloat16", 4, None),            # 8 rows: half a bf16 tile
    ((64, 128), "int8", 2, (64, 128)),           # 32 rows: one int8 tile
    ((2048,), "float32", 1, None),               # 1-D stays flat
])
def test_row_block_rule_on_plan_buckets(shape, dtype, group, want):
    import jax
    import jax.numpy as jnp
    from repro.comm.bucketer import plan_buckets
    leaf = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))
    size = int(np.prod(shape))
    # alone in its bucket: row-blocked by the rule, else the 1-D buffer
    b, = plan_buckets({"w": leaf}, group, 4 * 2**20).buckets
    assert b.row_blocked == (want is not None)
    assert b.shape == (want if want else (b.padded_size,))
    assert b.strip_shape(group)[0] * group == b.shape[0]
    if want:
        assert b.padded_size == size
    # sharing a bucket with another leaf: always the 1-D buffer
    two = {"a": jax.ShapeDtypeStruct((8,), jnp.dtype(dtype)), "w": leaf}
    b, = plan_buckets(two, group, 1 << 30).buckets
    assert not b.row_blocked and b.shape == (b.padded_size,)


_ROW_BLOCK_EQUIV = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro.comm import CommConfig
    from repro.comm.bucketer import plan_buckets
    from repro.optim import MomentumSGD
    from repro.optim.dist import make_distributed_update, \\
        make_overlapped_update, make_stale_sync_update, make_topk_ef_update
    from repro.optim.schedule import constant
    from repro.train import make_overlapped_train_step

    G = {G}
    rng = np.random.default_rng(G)
    shapes = {{"a": (32, 24),          # row-blocked: 32 / G rows of 8
               "b": (16,), "c": (40,),
               "k": (3, 3, 32, 16),    # row-blocked: 288 / G rows
               "u": (12, 8)}}          # 12 rows never split into tiles
    params = {{k: jnp.asarray(rng.normal(size=s), jnp.float32)
               for k, s in shapes.items()}}
    x = jnp.asarray(rng.normal(size=(8 * G, 4)), jnp.float32)
    grads = [jax.tree.map(lambda p, t=t: jnp.cos(p * (t + 1)), params)
             for t in range(2)]
    opt = MomentumSGD(momentum=0.9, weight_decay=0.01)
    assert [b.row_blocked for b in plan_buckets(params, G, 64).buckets] == [
        True, False, False, True, False]

    def flat(tree):
        # every leaf 1-D: every bucket takes the flat path
        return {{k: v.reshape(-1) for k, v in tree.items()}}

    def monolithic(make, comm):
        def run(mesh, axes, tree, gs):
            init_fn, update_fn = make(opt, mesh, data_axes=axes, comm=comm)
            st = init_fn(tree)
            for t, g in enumerate(gs):
                tree, st = jax.jit(update_fn)(tree, g, st, 0.05, t)
            return tree, st
        return run

    def loss(p, batch):
        # sees each leaf in its own shape, whatever shape the tree holds
        return sum(jnp.sum(jnp.sin(v.reshape(shapes[k])).reshape(-1)[:4]
                           * batch["x"].sum(0)) for k, v in p.items())

    def overlapped(comm):
        def run(mesh, axes, tree, gs):
            init_fn, local = make_overlapped_update(opt, mesh,
                                                    data_axes=axes, comm=comm)
            step = jax.jit(make_overlapped_train_step(
                loss, constant(0.05), mesh, axes, comm, local, grad_clip=0))
            st = init_fn(tree)
            for t in range(len(gs)):
                tree, st, _ = step(tree, st, t, {{"x": x}})
            return tree, st
        return run

    cases = [
        ("zero1", monolithic(make_distributed_update,
                             CommConfig(bucket_bytes=64))),
        ("per-tensor", monolithic(make_distributed_update, None)),
        ("pallas-ring", monolithic(make_distributed_update, CommConfig(
            bucket_bytes=64, backend="pallas-ring"))),
        ("int8", monolithic(make_distributed_update, CommConfig(
            bucket_bytes=64, wire_format="int8"))),
        ("stale-sync", monolithic(make_stale_sync_update,
                                  CommConfig(bucket_bytes=64))),
        ("topk", monolithic(make_topk_ef_update, CommConfig(
            bucket_bytes=64, wire_format="topk"))),
        ("overlap", overlapped(CommConfig(bucket_bytes=64, overlap=True)))]
    meshes = {{name: ("data",) for name, _ in cases}}
    if G == 4:
        for backend in ("lax", "pallas-ring"):
            tag = f"hierarchical/{{backend}}"
            cases.append((tag, monolithic(
                make_distributed_update, CommConfig(
                    bucket_bytes=64, hierarchical=True, backend=backend))))
            meshes[tag] = ("pod", "data")
    for tag, run in cases:
        axes = meshes[tag]
        sizes = (G,) if len(axes) == 1 else (2, G // 2)
        mesh = jax.make_mesh(sizes, axes,
                             axis_types=(AxisType.Auto,) * len(axes))
        with jax.set_mesh(mesh):
            pr, sr = run(mesh, axes, params, grads)
            pf, sf = run(mesh, axes, flat(params), [flat(g) for g in grads])
        for k in params:
            np.testing.assert_array_equal(
                np.asarray(pr[k]).reshape(-1), np.asarray(pf[k]),
                err_msg=f"{{tag}}/params/{{k}}")
        for a, b in zip(jax.tree.leaves(sr), jax.tree.leaves(sf)):
            a, b = np.asarray(a), np.asarray(b)
            if b.ndim >= 2:
                # a row-blocked (G, rows/G, cols) strip holds the flat
                # (G, n/G) strip's elements in the same order
                a = a.reshape(b.shape[0], -1)
            np.testing.assert_array_equal(a, b, err_msg=f"{{tag}}/state")
        print(tag, "OK")
    print("OK")
"""


@pytest.mark.parametrize("G", [1, 2, 4])
def test_row_blocked_update_bitwise_equals_flat(G):
    """Two momentum steps of every zero1 composition give params bitwise
    equal to the flat path's, and strip state bitwise equal after
    ``reshape(G, -1)``: the flat path is the same tree with every leaf
    1-D, so no bucket is row-blocked there."""
    out = run_py(_ROW_BLOCK_EQUIV.format(G=G), devices=G)
    assert out.rstrip().endswith("OK"), out
