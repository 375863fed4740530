"""Cluster subsystem tests: ClusterSpec env resolution, elastic failure
detection, world-size replan of the zero1 strip state, and the real
multi-process launcher (2 processes over ``jax.distributed`` + gloo).

Process-spawning tests go through ``python -m repro.launch.cluster`` like a
user would; the forced-device-count tests run in subprocesses so the rest
of the suite keeps the single real CPU device (same isolation policy as
tests/test_distributed.py)."""
import json
import os
import re
import subprocess
import sys
import tempfile
import textwrap
import time

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8, timeout: int = 300) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def run_cluster_cli(argv, timeout: int = 420):
    """Invoke the supervisor exactly as a user would."""
    env = dict(os.environ, PYTHONPATH=SRC)
    return subprocess.run(
        [sys.executable, "-m", "repro.launch.cluster"] + argv,
        env=env, capture_output=True, text=True, timeout=timeout)


# ---------------------------------------------------------------------------
# ClusterSpec
# ---------------------------------------------------------------------------

def test_cluster_spec_env_round_trip():
    from repro.cluster import ClusterSpec
    spec = ClusterSpec(coordinator="localhost:12345", num_processes=4,
                       process_id=2, local_devices=3)
    assert ClusterSpec.from_env(spec.env()) == spec
    # missing vars keep single-process defaults
    assert ClusterSpec.from_env({}).num_processes == 1
    assert not ClusterSpec.from_env({}).is_multiprocess


def test_cluster_spec_validation():
    from repro.cluster import ClusterSpec
    with pytest.raises(ValueError):
        ClusterSpec(num_processes=0)
    with pytest.raises(ValueError):
        ClusterSpec(num_processes=2, process_id=2)
    with pytest.raises(ValueError):
        ClusterSpec(coordinator="no-port")
    with pytest.raises(ValueError):
        ClusterSpec(local_devices=0)


def test_in_worker_detection():
    from repro.cluster.spec import ENV_PROCESS_ID, in_worker
    assert not in_worker({})
    assert in_worker({ENV_PROCESS_ID: "0"})


# ---------------------------------------------------------------------------
# elastic failure detection (no real processes: duck-typed handles)
# ---------------------------------------------------------------------------

class _FakeProc:
    def __init__(self, returncode=None):
        self.returncode = returncode

    def poll(self):
        return self.returncode


def _handle(pid, returncode=None, hb=None, tmpdir="/tmp"):
    from repro.cluster.launcher import WorkerHandle
    hb_file = os.path.join(tmpdir, f"hb_{pid}")
    if hb is not None:
        with open(hb_file, "w") as f:
            f.write(str(hb))
    return WorkerHandle(proc=_FakeProc(returncode), process_id=pid,
                        hb_file=hb_file, log_file=None)


def test_failure_detects_nonzero_exit(tmp_path):
    from repro.cluster.elastic import _failure
    hs = [_handle(0, tmpdir=str(tmp_path)),
          _handle(1, returncode=-9, tmpdir=str(tmp_path))]
    fail = _failure(hs, time.monotonic(), heartbeat_timeout=60.0)
    assert fail is not None and fail["reason"] == "exit"
    assert fail["dead"] == [1]


def test_failure_ignores_clean_exit_and_fresh_group(tmp_path):
    from repro.cluster.elastic import _failure
    hs = [_handle(0, tmpdir=str(tmp_path)),
          _handle(1, returncode=0, tmpdir=str(tmp_path))]
    assert _failure(hs, time.monotonic(), heartbeat_timeout=60.0) is None


def test_failure_declares_hang_only_when_whole_group_stale(tmp_path):
    from repro.cluster.elastic import _failure
    # both alive, spawned long ago, no heartbeat ever written -> hang
    hs = [_handle(0, tmpdir=str(tmp_path)),
          _handle(1, tmpdir=str(tmp_path))]
    old = time.monotonic() - 1000.0
    fail = _failure(hs, old, heartbeat_timeout=60.0)
    assert fail is not None and fail["reason"] == "heartbeat"
    assert fail["dead"] == []
    # one member freshly beating -> healthy (sync SGD: a real hang is
    # always collective)
    hs2 = [_handle(0, hb=5, tmpdir=str(tmp_path)),
           _handle(1, tmpdir=str(tmp_path))]
    assert _failure(hs2, old, heartbeat_timeout=60.0) is None


# ---------------------------------------------------------------------------
# world-size replan of the strip state
# ---------------------------------------------------------------------------

def _value_strips(payload_vals, world):
    from repro.core.collectives import padded_size
    from repro.optim.dist import owner_perm
    p = padded_size(len(payload_vals), world["G"])
    flat = np.zeros(p, np.float32)
    flat[:len(payload_vals)] = payload_vals
    arr = flat.reshape(world["G"], -1)
    perm = owner_perm(world["hierarchical"], world["axes_sizes"])
    return arr[perm] if perm is not None else arr


def test_replan_strip_leaf_round_trips_across_worlds():
    from repro.checkpoint.replan import replan_strip_leaf, world_meta
    payload = np.random.default_rng(0).normal(size=10).astype(np.float32)
    worlds = [world_meta([8], False, 4), world_meta([2, 4], True, 4),
              world_meta([4, 2], True, 4), world_meta([4], False, 4),
              world_meta([2, 2], True, 4), world_meta([1], False, 4)]
    for old in worlds:
        for new in worlds:
            got = replan_strip_leaf(_value_strips(payload, old),
                                    len(payload), old, new)
            np.testing.assert_array_equal(got,
                                          _value_strips(payload, new))


def test_replan_strip_leaf_rejects_wrong_shape():
    from repro.checkpoint.replan import replan_strip_leaf, world_meta
    old, new = world_meta([4], False, 4), world_meta([2], False, 4)
    with pytest.raises(ValueError):
        replan_strip_leaf(np.zeros((2, 8), np.float32), 10, old, new)
    with pytest.raises(ValueError):   # padded size inconsistent w/ payload
        replan_strip_leaf(np.zeros((4, 9), np.float32), 10, old, new)


def test_replan_strip_state_rejects_bucket_bytes_change():
    from repro.checkpoint.replan import replan_strip_state, world_meta
    with pytest.raises(ValueError, match="bucket_bytes"):
        replan_strip_state({}, [], None, world_meta([4], False, 4),
                           world_meta([2], False, 8))


def test_replan_strip_state_full_state_matches_ginvariant_run():
    """Run the REAL bucketed update twice at G=4 (hierarchical 2x2), replan
    the resulting momentum strips to G=2 (flat), and compare against the
    state the same two updates produce when run at G=2 directly — the
    G-invariance of the §3.4 update makes them equal to float tolerance."""
    out = run_py("""
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType

        from repro.checkpoint.replan import replan_strip_state, world_meta
        from repro.comm.bucketer import CommConfig, plan_buckets
        from repro.optim import MomentumSGD
        from repro.optim.dist import make_distributed_update

        params = {"w": jnp.linspace(-1, 1, 37, dtype=jnp.float32),
                  "b": jnp.linspace(0, 2, 11, dtype=jnp.float32)}
        grads = jax.tree.map(lambda p: jnp.cos(p) + 0.1, params)
        comm = CommConfig(bucket_bytes=64, hierarchical=True)
        opt = MomentumSGD(momentum=0.9)

        def run_world(devs, axes, hier):
            mesh = jax.make_mesh(devs, axes,
                                 devices=jax.devices()[:int(np.prod(devs))],
                                 axis_types=(AxisType.Auto,) * len(devs))
            cc = CommConfig(bucket_bytes=64, hierarchical=hier)
            init, upd = make_distributed_update(opt, mesh, data_axes=axes,
                                                comm=cc)
            p, s = params, init(params)
            for _ in range(2):
                p, s = upd(p, grads, s, 0.05)
            return p, s

        p4, s4 = run_world((2, 2), ("pod", "data"), True)
        p2, s2 = run_world((2,), ("data",), False)
        np.testing.assert_allclose(np.asarray(p4["w"]), np.asarray(p2["w"]),
                                   rtol=2e-6, atol=2e-6)

        old_w = world_meta([2, 2], True, 64)
        new_w = world_meta([2], False, 64)
        plan = plan_buckets(params, 2, 64)
        old_leaves = [np.asarray(x) for x in jax.tree.leaves(s4)]
        replanned = replan_strip_state(s2, old_leaves, plan, old_w, new_w)
        for got, want in zip(jax.tree.leaves(replanned),
                             jax.tree.leaves(s2)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-6, atol=2e-6)
        print("OK")
    """, devices=4)
    assert "OK" in out


def test_row_blocked_strip_state_replans_and_restores_as_flat(tmp_path):
    """A row-blocked bucket's strip state saved at G=2, replanned to G=4 and
    restored, and a zero1 checkpoint restored into the overlapped mode,
    both give the flat path's values exactly — the flat path being the same
    tree with every leaf 1-D, so that no bucket is row-blocked — and the
    next step from them the flat path's params."""
    out = run_py(f"""
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType
        from repro.checkpoint import ckpt as ckpt_lib
        from repro.checkpoint.replan import replan_strip_state, world_meta
        from repro.comm.bucketer import CommConfig, plan_buckets
        from repro.optim import MomentumSGD
        from repro.optim.dist import make_distributed_update, \\
            make_overlapped_update
        from repro.optim.schedule import constant
        from repro.train import make_overlapped_train_step

        # a: row-blocked at G=2 and G=4; h: at G=2 only (4 rows a block
        # at G=4); b: 1-D
        shapes = {{"a": (32, 24), "b": (16,), "h": (16, 8)}}
        rng = np.random.default_rng(0)
        params = {{k: jnp.asarray(rng.normal(size=s), jnp.float32)
                   for k, s in shapes.items()}}
        grads = jax.tree.map(jnp.cos, params)
        x = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)
        opt = MomentumSGD(momentum=0.9)
        comm = CommConfig(bucket_bytes=64)
        assert [b.row_blocked for b in plan_buckets(params, 2, 64).buckets
                ] == [True, False, True]
        assert [b.row_blocked for b in plan_buckets(params, 4, 64).buckets
                ] == [True, False, False]

        def flat(tree):
            return {{k: v.reshape(-1) for k, v in tree.items()}}

        def mesh_of(G):
            return jax.make_mesh((G,), ("data",), devices=jax.devices()[:G],
                                 axis_types=(AxisType.Auto,))

        def loss(p, batch):
            return sum(jnp.sum(jnp.sin(v.reshape(shapes[k])).reshape(-1)[:4]
                               * batch["x"].sum(0)) for k, v in p.items())

        def saved_at_g2(tree, d):
            mesh = mesh_of(2)
            init, upd = make_distributed_update(opt, mesh, comm=comm)
            with jax.set_mesh(mesh):
                st = init(tree)
                for _ in range(2):
                    tree, st = jax.jit(upd)(tree, flat(grads) if tree[
                        "a"].ndim == 1 else grads, st, 0.05)
            ckpt_lib.save(d, 2, params=tree, opt_state=st)
            return tree

        def replanned_at_g4(tree, d):
            tree = ckpt_lib.restore(d, 2, params=tree)[0]["params"]
            mesh = mesh_of(4)
            init, upd = make_distributed_update(opt, mesh, comm=comm)
            with jax.set_mesh(mesh):
                tpl = jax.eval_shape(init, tree)
                st = replan_strip_state(
                    tpl, ckpt_lib.restore_loose(d, 2, "opt_state", tpl),
                    plan_buckets(tree, 4, 64), world_meta([2], False, 64),
                    world_meta([4], False, 64))
                g = flat(grads) if tree["a"].ndim == 1 else grads
                p, _ = jax.jit(upd)(tree, g, st, 0.05)
            return st, p

        def overlapped_at_g2(tree, d):
            mesh = mesh_of(2)
            c = CommConfig(bucket_bytes=64, overlap=True)
            init, local = make_overlapped_update(opt, mesh, comm=c)
            step = make_overlapped_train_step(loss, constant(0.05), mesh,
                                              ("data",), c, local,
                                              grad_clip=0)
            with jax.set_mesh(mesh):
                tpl = jax.eval_shape(init, tree)
                trees, _ = ckpt_lib.restore(d, 2, params=tree, opt_state=tpl)
                p, _, _ = jax.jit(step)(trees["params"], trees["opt_state"],
                                        2, {{"x": x}})
            return trees["opt_state"], p

        d_rb, d_fl = {str(tmp_path / "rb")!r}, {str(tmp_path / "flat")!r}
        p_rb, p_fl = saved_at_g2(params, d_rb), saved_at_g2(flat(params), d_fl)
        for tag, fn in (("replan G=2 -> 4", replanned_at_g4),
                        ("zero1 -> overlapped", overlapped_at_g2)):
            s_rb, q_rb = fn(p_rb, d_rb)
            s_fl, q_fl = fn(p_fl, d_fl)
            for a, b in zip(jax.tree.leaves(s_rb), jax.tree.leaves(s_fl)):
                a, b = np.asarray(a), np.asarray(b)
                np.testing.assert_array_equal(
                    a.reshape(b.shape[0], -1) if b.ndim >= 2 else a, b,
                    err_msg=tag)
            for k in shapes:
                np.testing.assert_array_equal(
                    np.asarray(q_rb[k]).reshape(-1), np.asarray(q_fl[k]),
                    err_msg=f"{{tag}}/{{k}}")
            print(tag, "OK")
        print("OK")
    """, devices=4)
    assert out.rstrip().endswith("OK"), out


# ---------------------------------------------------------------------------
# make_host_mesh device-drop fix
# ---------------------------------------------------------------------------

def test_make_host_mesh_warns_and_keeps_all_devices():
    out = run_py("""
        import warnings
        import jax
        from repro.launch.mesh import make_host_mesh, mesh_devices
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            mesh = make_host_mesh(model_ways=4)   # 4 does not divide 6
        assert len(w) == 1, [str(x.message) for x in w]
        msg = str(w[0].message)
        assert "drop 2" in msg and "model_ways=3" in msg, msg
        assert mesh_devices(mesh) == 6, dict(mesh.shape)
        assert dict(mesh.shape) == {"data": 2, "model": 3}, dict(mesh.shape)
        print("OK")
    """, devices=6)
    assert "OK" in out


def test_divisible_factorization():
    from repro.launch.mesh import _divisible_factorization
    assert _divisible_factorization(6, 4, 1) == (3, 1)
    assert _divisible_factorization(8, 4, 2) == (4, 2)
    assert _divisible_factorization(7, 4, 2) == (1, 7) or \
        _divisible_factorization(7, 4, 2)[0] * \
        _divisible_factorization(7, 4, 2)[1] in (1, 7)
    assert _divisible_factorization(1, 1, 1) == (1, 1)


# ---------------------------------------------------------------------------
# checkpoint restore onto a different world size (through compile_run)
# ---------------------------------------------------------------------------

_STAGE = """
    import jax
    from repro.api import MeshSpec, RunSpec, compile_run
    # constant schedule: the LR at step k must not depend on spec.steps,
    # or the save-at-3 run and the 6-step reference would train the first
    # three steps under different LRs
    spec = RunSpec(arch="vgg-a", smoke=True, parallel="zero1",
                   mesh=MeshSpec(pods={pods}), steps={steps}, batch=8,
                   schedule="constant",
                   ckpt_dir={ckpt_dir!r}, ckpt_every={ckpt_every},
                   log_every=100)
    run = compile_run(spec)
    hist = run.fit({fit_args})
    run.close()
    print("FINAL", hist[-1]["loss"] if hist else "none")
"""


def _final(out: str) -> float:
    m = re.search(r"FINAL ([\d.eE+-]+)", out)
    assert m, out
    return float(m.group(1))


@pytest.mark.parametrize("resume_devices,resume_pods", [(4, 1), (2, 1)])
def test_restore_across_world_sizes(tmp_path, resume_devices, resume_pods):
    """Save at G=8 (hierarchical pods=2 x data=4), restore at G=4 and G=2
    (flat): the strip state is re-planned and the trajectory continues —
    final loss matches an uninterrupted run at the RESUME world size."""
    ckpt = str(tmp_path / "ckpt")
    run_py(_STAGE.format(pods=2, steps=3, ckpt_dir=ckpt, ckpt_every=3,
                         fit_args=""), devices=8)
    resumed = _final(run_py(
        _STAGE.format(pods=resume_pods, steps=6, ckpt_dir=ckpt,
                      ckpt_every=0, fit_args=""),
        devices=resume_devices))
    ref = _final(run_py(
        _STAGE.format(pods=resume_pods, steps=6, ckpt_dir=None,
                      ckpt_every=0, fit_args="start_step=0"),
        devices=resume_devices))
    assert abs(resumed - ref) < 5e-3, (resumed, ref)


def test_restore_without_meta_still_fails_cleanly(tmp_path):
    """A shape-mismatched checkpoint with NO zero1 meta must raise a real
    error, not replan garbage."""
    out = run_py(f"""
        import numpy as np
        import jax
        from repro.api import MeshSpec, RunSpec, compile_run
        from repro.checkpoint import ckpt as ckpt_lib
        spec = RunSpec(arch="vgg-a", smoke=True, parallel="zero1",
                       mesh=MeshSpec(), steps=2, batch=8,
                       ckpt_dir={str(tmp_path)!r}, log_every=100)
        run = compile_run(spec)
        # forge a checkpoint with wrong strip shapes and no meta
        bad_state = jax.tree.map(
            lambda s: np.zeros((7,) + tuple(s.shape[1:]), np.float32)
            if getattr(s, 'ndim', 0) >= 2 else np.asarray(s),
            run.opt_state)
        ckpt_lib.save({str(tmp_path)!r}, 1, params=run.params,
                      opt_state=bad_state)
        try:
            run.restore(1)
        except ValueError as e:
            assert "meta" in str(e) or "shape" in str(e), e
            print("RAISED")
    """, devices=2)
    assert "RAISED" in out


# ---------------------------------------------------------------------------
# the real thing: multi-process jax.distributed via the launcher CLI
# ---------------------------------------------------------------------------

def test_two_process_smoke_matches_single_process():
    """2 real processes over gloo, --verify: the launcher itself asserts
    |cluster final loss - single-process final loss| <= tol and exits
    nonzero on mismatch."""
    with tempfile.TemporaryDirectory() as td:
        out = run_cluster_cli(
            ["--processes", "2", "--arch", "vgg-a", "--smoke",
             "--steps", "4", "--batch", "8", "--run-dir", td, "--verify"])
        assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
        assert "verify:" in out.stdout and "OK" in out.stdout, out.stdout
        result = json.load(open(os.path.join(td, "result.json")))
        assert result["world"] == 2 and result["final_loss"] is not None


def test_chaos_kill_one_worker_recovers_and_matches():
    """The chaos harness: SIGKILL worker 1 mid-run; the supervisor must
    detect it, re-form at world=1, resume from the latest checkpoint with
    a replanned G=2 -> G=1 state, and land on the SAME final loss as an
    uninterrupted single-process run of the full schedule."""
    with tempfile.TemporaryDirectory() as td:
        ckpt = os.path.join(td, "ckpt")
        out = run_cluster_cli(
            ["--processes", "2", "--arch", "vgg-a", "--smoke",
             "--steps", "16", "--batch", "8", "--schedule", "constant",
             "--ckpt-dir", ckpt, "--run-dir", td, "--ckpt-every", "2",
             "--chaos-kill-step", "3", "--heartbeat-timeout", "60"])
        assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-2000:]
        assert "attempt 1: world=1" in out.stdout, out.stdout
        assert "resuming from checkpoint" in out.stdout, out.stdout
        result = json.load(open(os.path.join(td, "result.json")))
        assert result["world"] == 1

        ref = _final(run_py(
            _STAGE.format(pods=1, steps=16, ckpt_dir=None, ckpt_every=0,
                          fit_args="start_step=0"), devices=1))
        assert abs(result["final_loss"] - ref) < 5e-3, (result, ref)


# ---------------------------------------------------------------------------
# satellites: linear-scale-warmup schedule, cross-host balance regimes
# ---------------------------------------------------------------------------

def test_linear_scale_warmup_shape():
    from repro.optim import linear_scale_warmup
    sched = linear_scale_warmup(1e-3, 8, 10, 100)
    assert float(sched(0)) == pytest.approx(1e-3)
    assert float(sched(5)) == pytest.approx((1e-3 + 8e-3) / 2)
    assert float(sched(10)) == pytest.approx(8e-3)
    # decays after warmup, floored at final_frac * peak
    assert float(sched(100)) == pytest.approx(0.1 * 8e-3, rel=1e-3)
    assert float(sched(55)) < 8e-3


def test_linear_scale_warmup_in_runspec():
    from repro.api import SCHEDULES, RunSpec
    assert "linear-scale-warmup" in SCHEDULES
    RunSpec(arch="vgg-a", schedule="linear-scale-warmup")   # validates
    with pytest.raises(ValueError):
        RunSpec(arch="vgg-a", schedule="nope")


def test_cross_host_hw_regimes():
    from repro.configs import XEON_E5_2698V3_FDR as FDR
    from repro.core.balance import CROSS_HOST_REGIMES, cross_host_hw
    eth = cross_host_hw(FDR, "ethernet-10gbe")
    assert eth.link_bw == pytest.approx(10e9 / 8)
    assert eth.sw_latency == pytest.approx(50e-6)
    ib = cross_host_hw(FDR, "infiniband-fdr")
    assert ib.link_bw == pytest.approx(56e9 / 8)
    assert set(CROSS_HOST_REGIMES) == {"infiniband-fdr", "ethernet-10gbe"}
    with pytest.raises(ValueError):
        cross_host_hw(FDR, "carrier-pigeon")


def test_comm_config_cross_backend_validation():
    from repro.comm import CommConfig
    CommConfig(cross_backend="pallas-ring")   # valid
    with pytest.raises(ValueError, match="cross_backend"):
        CommConfig(cross_backend="smoke-signals")


# ---------------------------------------------------------------------------
# mode interop: a zero1 checkpoint resumes under stale-sync
# ---------------------------------------------------------------------------

def test_zero1_ckpt_resumes_under_stale_sync_same_world(tmp_path):
    """The inner strip state of stale-sync is BIT-identical to zero1's, so
    a zero1 checkpoint restores into a stale-sync run with the staleness
    buffer re-initialized — and the first post-resume step is then exactly
    synchronous (empty carry), so training one step past the checkpoint
    must land on the SAME params as an uninterrupted zero1 run."""
    ckpt = str(tmp_path / "ckpt")
    out = run_py(f"""
        import numpy as np, jax
        from repro.api import RunSpec, compile_run
        quiet = lambda *_: None
        base = RunSpec(arch="vgg-a", smoke=True, steps=3, batch=8,
                       schedule="constant", parallel="zero1",
                       ckpt_dir={ckpt!r}, ckpt_every=3, log_every=100)
        rz = compile_run(base)
        rz.fit(log_fn=quiet); rz.close()

        # resume the zero1 checkpoint under stale-sync, train ONE step
        logs = []
        rs = compile_run(base.replace(parallel="stale-sync", steps=4,
                                      ckpt_every=0))
        rs.fit(log_fn=logs.append)
        assert any("resuming from checkpoint step 3" in str(ln)
                   for ln in logs), logs
        assert set(rs.opt_state) == {{"stale", "synced", "zero1"}}
        rs.close()

        # uninterrupted zero1 for the same 4 steps
        ref = compile_run(base.replace(steps=4, ckpt_dir=None,
                                       ckpt_every=0))
        ref.fit(log_fn=quiet); ref.close()
        for a, b in zip(jax.tree.leaves(rs.params),
                        jax.tree.leaves(ref.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)
        print("OK")
    """)
    assert "OK" in out


@pytest.mark.parametrize("resume_devices,resume_pods", [(4, 1), (2, 1)])
def test_zero1_ckpt_resumes_under_stale_sync_across_worlds(
        tmp_path, resume_devices, resume_pods):
    """Cross-world interop: a hierarchical G=8 zero1 checkpoint restores
    into a FLAT smaller-world stale-sync run — the inner strips are
    re-planned (owner layout included), the staleness buffer re-initialized
    at the new world's bucket geometry.  One synchronous post-resume step
    must match uninterrupted zero1 at the RESUME world size (the §3.4
    update is G-invariant to float tolerance)."""
    ckpt = str(tmp_path / "ckpt")
    run_py(_STAGE.format(pods=2, steps=3, ckpt_dir=ckpt, ckpt_every=3,
                         fit_args=""), devices=8)
    out = run_py(f"""
        import numpy as np, jax
        from repro.api import MeshSpec, RunSpec, compile_run
        quiet = lambda *_: None
        base = RunSpec(arch="vgg-a", smoke=True, steps=4, batch=8,
                       schedule="constant", mesh=MeshSpec(pods={resume_pods}),
                       log_every=100)
        logs = []
        rs = compile_run(base.replace(parallel="stale-sync",
                                      ckpt_dir={ckpt!r}))
        rs.fit(log_fn=logs.append)
        assert any("resuming from checkpoint step 3" in str(ln)
                   for ln in logs), logs
        rs.close()
        ref = compile_run(base.replace(parallel="zero1"))
        ref.fit(log_fn=quiet); ref.close()
        for a, b in zip(jax.tree.leaves(rs.params),
                        jax.tree.leaves(ref.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)
        print("OK")
    """, devices=resume_devices)
    assert "OK" in out
