"""Compressed gradient wire formats (``CommConfig.wire_format``).

Covers the whole vertical: the int8/topk Pallas kernels against their jnp
oracles (with error bounds across message sizes and G in {1, 2, 4, 8}),
CommConfig/RunSpec validation against the MODE_CAPS capability table, the
bytes-on-wire balance models, the topk error-feedback residual through
checkpoint save/restore and cross-world replan, the persisted comm=auto
plan cache, and its invalidation by the elastic supervisor on a world-size
change (fake-proc harness — no real processes).

Forced-device-count tests run in subprocesses so the rest of the suite
keeps the single real CPU device (same isolation policy as
tests/test_distributed.py)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ref as kref
from repro.kernels import ring as kring

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

RNG = np.random.default_rng(42)


def _arr(*shape):
    return jnp.asarray(RNG.normal(size=shape).astype(np.float32))


def run_py(code: str, devices: int = 8, timeout: int = 300) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


# ---------------------------------------------------------------------------
# int8 quantize / ring-hop kernels vs oracles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 5, 128, 1000, 4096])
def test_int8_quantize_matches_oracle_exactly(n):
    x = _arr(n)
    q, s = kring.int8_quantize(kring.to_tiles(x), interpret=True)
    q = kring.from_tiles(q, n)
    qr, sr = kref.int8_quantize_ref(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-7)
    assert np.abs(np.asarray(q)).max() <= 127


def test_int8_quantize_all_zero_message_is_well_defined():
    q, s = kring.int8_quantize(kring.to_tiles(jnp.zeros((64,), jnp.float32)),
                               interpret=True)
    assert float(s[0]) == 1.0     # scale 1.0 so dequantize is a no-op
    assert not np.asarray(q).any()


@pytest.mark.parametrize("n", [7, 640, 4096])
def test_int8_roundtrip_error_bounded_by_half_scale(n):
    x = _arr(n) * 10.0
    q, s = kref.int8_quantize_ref(x)
    back = np.asarray(kref.int8_dequantize_ref(q, s))
    # round-to-nearest: per-element error <= scale/2
    bound = float(s[0]) / 2 + 1e-6
    assert np.abs(back - np.asarray(x)).max() <= bound


@pytest.mark.parametrize("G", [1, 2, 4, 8])
@pytest.mark.parametrize("n", [3, 257])
def test_ring_hop_int8_matches_oracle(G, n):
    chunks = _arr(G, n)
    q, s = kref.int8_quantize_ref(_arr(n))
    for c in range(G):
        qk, sk = kring.ring_hop_int8(kring.to_tiles(chunks),
                                     kring.to_tiles(q), s, jnp.int32(c),
                                     interpret=True)
        qr, sr = kref.ring_hop_int8_ref(chunks, q, s, c)
        np.testing.assert_array_equal(np.asarray(kring.from_tiles(qk, n)),
                                      np.asarray(qr))
        np.testing.assert_allclose(np.asarray(sk), np.asarray(sr),
                                   rtol=1e-6)


@pytest.mark.parametrize("G", [2, 4, 8])
@pytest.mark.parametrize("n", [8, 640, 4096])
def test_int8_ring_error_is_additive_across_hops(G, n):
    """Per-hop f32 accumulation keeps the total quantization error bounded
    by the SUM of the per-hop half-scales (one rounding per hop), not a
    product — the property the fused hop kernel exists to preserve."""
    chunks = _arr(G, n)
    exact = np.asarray(chunks.astype(jnp.float32).sum(0))
    q, s = kref.int8_quantize_ref(chunks[0])
    bound = float(s[0]) / 2
    for j in range(1, G):
        q, s = kref.ring_hop_int8_ref(chunks, q, s, jnp.int32(j))
        bound += float(s[0]) / 2
    got = np.asarray(kref.int8_dequantize_ref(q, s))
    assert np.abs(got - exact).max() <= bound + 1e-6


# ---------------------------------------------------------------------------
# topk select / scatter / ring-hop kernels vs oracles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("G", [1, 2, 4, 8])
@pytest.mark.parametrize("n,k", [(8, 2), (40, 5), (257, 32)])
def test_ring_hop_topk_matches_oracle(G, n, k):
    chunks = _arr(G, n)
    vals, idx = kref.topk_select_ref(_arr(n), k)
    for c in range(G):
        got = kring.from_tiles(
            kring.ring_hop_topk(kring.to_tiles(chunks), vals, idx,
                                jnp.int32(c), interpret=True), n)
        want = kref.ring_hop_topk_ref(chunks, vals, idx, c)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


def test_topk_select_scatter_round_trips_at_full_density():
    x = _arr(129)
    vals, idx = kref.topk_select_ref(x, 129)
    np.testing.assert_allclose(
        np.asarray(kref.topk_scatter_ref(vals, idx, 129)), np.asarray(x),
        rtol=1e-7)
    assert idx.dtype == jnp.int32


def test_topk_mask_keeps_largest_magnitudes_in_place():
    x = _arr(200)
    k = 20
    kept = np.asarray(kref.topk_mask_ref(x, k))
    xn = np.asarray(x)
    nz = np.flatnonzero(kept)
    assert len(nz) == k
    np.testing.assert_array_equal(kept[nz], xn[nz])   # in place, unscaled
    dropped = np.setdiff1d(np.arange(200), nz)
    assert np.abs(xn[nz]).min() >= np.abs(xn[dropped]).max()
    # residual + kept reconstructs the input exactly
    np.testing.assert_array_equal(kept + (xn - kept), xn)


def test_topk_chunk_k_floor_and_ceiling():
    from repro.comm.backends.pallas_ring import topk_chunk_k
    assert topk_chunk_k(100, 0.05) == 5
    assert topk_chunk_k(10, 0.25) == 3          # ceil(2.5)
    assert topk_chunk_k(10, 0.01) == 1          # never empty
    assert topk_chunk_k(10, 0.01, floor=4) == 4
    assert topk_chunk_k(3, 1.0) == 3            # never more than n
    assert topk_chunk_k(3, 1.0, floor=8) == 3


# ---------------------------------------------------------------------------
# CommConfig / RunSpec validation against MODE_CAPS
# ---------------------------------------------------------------------------

def test_comm_config_unknown_wire_format_names_supported_set():
    from repro.comm.bucketer import WIRE_FORMATS, CommConfig
    with pytest.raises(ValueError) as ei:
        CommConfig(wire_format="fp4")
    msg = str(ei.value)
    assert "fp4" in msg
    for fmt in WIRE_FORMATS:
        assert fmt in msg, msg


def test_comm_config_unknown_reduce_dtype_names_supported_set():
    from repro.comm import CommConfig
    with pytest.raises(ValueError) as ei:
        CommConfig(reduce_dtype="float8")
    msg = str(ei.value)
    assert "float8" in msg and "float32" in msg and "bfloat16" in msg


def test_comm_config_wire_format_derivation_and_properties():
    from repro.comm import CommConfig
    assert CommConfig().wire_format == "fp32"
    assert CommConfig(reduce_dtype="bfloat16").wire_format == "bf16"
    assert CommConfig(reduce_dtype="bfloat16").wire_dtype == jnp.bfloat16
    int8 = CommConfig(wire_format="int8")
    assert int8.compressed and int8.wire_dtype == jnp.float32
    assert not CommConfig().compressed
    with pytest.raises(ValueError, match="conflicting"):
        CommConfig(reduce_dtype="bfloat16", wire_format="int8")
    for bad in (0.0, -0.1, 1.5):
        with pytest.raises(ValueError, match="topk_ratio"):
            CommConfig(wire_format="topk", topk_ratio=bad)


def test_runspec_mode_caps_gate_wire_formats():
    from repro.api import RunSpec
    from repro.api.spec import MODE_CAPS
    from repro.comm import CommConfig
    topk = CommConfig(wire_format="topk")
    RunSpec(arch="vgg-a", parallel="zero1", comm=topk)          # valid
    # stale-sync takes the stateless int8 wire but not the EF-stateful topk
    RunSpec(arch="vgg-a", parallel="stale-sync",
            comm=CommConfig(wire_format="int8"))
    with pytest.raises(ValueError, match="not valid under parallel="):
        RunSpec(arch="vgg-a", parallel="stale-sync", comm=topk)
    # gossip moves no ring message at all: dense formats only
    for fmt in ("int8", "topk"):
        with pytest.raises(ValueError, match="not valid under parallel="):
            RunSpec(arch="vgg-a", parallel="gossip",
                    comm=CommConfig(backend="gossip", wire_format=fmt))
    RunSpec(arch="vgg-a", parallel="gossip",
            comm=CommConfig(backend="gossip", reduce_dtype="bfloat16"))
    assert MODE_CAPS["zero1"].wire_formats == ("fp32", "bf16", "int8",
                                               "topk")


def test_runspec_rejects_topk_under_overlap():
    from repro.api import RunSpec
    from repro.comm import CommConfig
    with pytest.raises(ValueError, match="overlap"):
        RunSpec(arch="vgg-a", parallel="zero1",
                comm=CommConfig(wire_format="topk", overlap=True))
    # int8 is stateless, so it overlaps fine
    RunSpec(arch="vgg-a", parallel="zero1",
            comm=CommConfig(wire_format="int8", overlap=True))


def test_train_cli_rejects_wire_format_outside_caps():
    import argparse

    from repro.launch.train import add_run_args, check_run_args
    for argv in (["--parallel", "gossip", "--wire-format", "int8"],
                 ["--parallel", "stale-sync", "--wire-format", "topk"],
                 ["--parallel", "zero1", "--wire-format", "topk",
                  "--overlap"]):
        ap = argparse.ArgumentParser()
        add_run_args(ap)
        with pytest.raises(SystemExit):
            check_run_args(ap, ap.parse_args(["--arch", "vgg-a"] + argv))


def test_spec_from_args_threads_wire_format_and_ratio():
    import argparse

    from repro.launch.train import add_run_args, check_run_args, \
        spec_from_args
    ap = argparse.ArgumentParser()
    add_run_args(ap)
    args = ap.parse_args(["--arch", "vgg-a", "--parallel", "zero1",
                          "--wire-format", "topk", "--topk-ratio", "0.25"])
    check_run_args(ap, args)
    spec = spec_from_args(args)
    assert spec.comm.wire_format == "topk"
    assert spec.comm.topk_ratio == 0.25


# ---------------------------------------------------------------------------
# bytes-on-wire balance models
# ---------------------------------------------------------------------------

def test_wire_reduce_factor_table():
    from repro.core.balance import wire_reduce_factor
    assert wire_reduce_factor("fp32") == 1.0
    assert wire_reduce_factor("bf16") == 0.5
    assert wire_reduce_factor("int8") == 0.25
    assert wire_reduce_factor("topk", 0.05) == pytest.approx(0.1)
    assert wire_reduce_factor("topk", 0.25) == pytest.approx(0.5)
    with pytest.raises(ValueError, match="fp4"):
        wire_reduce_factor("fp4")


def test_compressed_allreduce_time_reduces_to_dense_at_fp32():
    from repro.core.balance import bucketed_allreduce_time, \
        compressed_allreduce_time
    from repro.telemetry.autotune import measured_hw
    hw = measured_hw(1e-5, 1e9)
    kw = dict(total_bytes=64 * 2**20, n_tensors=20, bucket_bytes=4 * 2**20,
              G=8, hw=hw)
    assert compressed_allreduce_time(wire_format="fp32", **kw) == \
        pytest.approx(bucketed_allreduce_time(**kw))
    # every compressed format is strictly cheaper than the dense wire
    dense = compressed_allreduce_time(wire_format="fp32", **kw)
    for fmt in ("bf16", "int8", "topk"):
        assert compressed_allreduce_time(wire_format=fmt, **kw) < dense


def test_optimal_bucket_grows_with_compression():
    """b* = sqrt(B*SWlat*BW*G * 2/(1+f)): a compressed reduce wire shrinks
    the bandwidth term, so the latency term amortizes over a LARGER
    bucket — int8 (f=1/4) by exactly sqrt(2/1.25 / 1) vs fp32."""
    import math

    from repro.core.balance import optimal_bucket_bytes
    from repro.telemetry.autotune import measured_hw
    hw = measured_hw(1e-5, 1e9)
    B = 256 * 2**20
    b_fp32 = optimal_bucket_bytes(B, 8, hw)
    b_int8 = optimal_bucket_bytes(B, 8, hw, wire_format="int8")
    assert b_int8 == pytest.approx(b_fp32 * math.sqrt(2.0 / 1.25))
    assert b_fp32 < b_int8 < B


def test_int8_wire_reduce_bytes_cut_by_at_least_3p5x():
    """The BENCH_comm gate's model: int8 cuts reduce-side wire bytes >= 3.5x
    vs fp32 (4x payload minus the per-message scale overhead)."""
    from repro.core.balance import wire_reduce_bytes
    total = 4 * 10_000_000            # 10M fp32 gradient elements
    dense = wire_reduce_bytes(total, G=8, n_coll=12, wire_format="fp32")
    i8 = wire_reduce_bytes(total, G=8, n_coll=12, wire_format="int8")
    assert dense == total
    assert i8 > total / 4             # scale overhead is accounted
    assert dense / i8 > 3.5


# ---------------------------------------------------------------------------
# the persisted comm=auto plan cache
# ---------------------------------------------------------------------------

def test_autotune_cache_save_load_round_trip(tmp_path):
    from repro.telemetry.autotune import _load_cached_plan, \
        _save_cached_plan
    path = str(tmp_path / "cache.json")
    key = {"G": 4, "axes": ["data"], "total_bytes": 100,
           "backends": ["lax"], "wire_formats": ["fp32", "int8"]}
    plan = {"bucket_bytes": 65536, "chosen_backend": "lax",
            "chosen_wire_format": "int8"}
    assert _load_cached_plan(path, key) is None          # absent
    _save_cached_plan(path, key, plan)
    assert _load_cached_plan(path, key) == plan
    assert _load_cached_plan(path, dict(key, G=2)) is None   # other topology
    with open(path, "w") as f:
        f.write("{not json")
    assert _load_cached_plan(path, key) is None          # corrupt


def test_autotune_comm_cache_hit_skips_probing(tmp_path, monkeypatch):
    """Second launch with the same key must return the persisted plan
    WITHOUT timing a single collective (probing is made to raise)."""
    from jax.sharding import AxisType

    from repro.comm import CommConfig
    from repro.telemetry import autotune
    def quiet(*a, **k):
        pass
    params = {"w": jnp.zeros((4096,), jnp.float32),
              "b": jnp.zeros((128,), jnp.float32)}
    mesh = jax.make_mesh((1,), ("data",), devices=jax.devices()[:1],
                         axis_types=(AxisType.Auto,))
    path = str(tmp_path / "autotune_cache.json")
    first = autotune.autotune_comm(params, mesh, ("data",), CommConfig(),
                                   backends=["lax"], reps=1, log=quiet,
                                   wire_formats=("fp32", "bf16", "int8"),
                                   cache_path=path)
    saved = json.load(open(path))
    assert saved["plan"]["chosen_backend"] == first.backend
    assert saved["plan"]["chosen_wire_format"] == first.wire_format
    assert saved["plan"]["bucket_bytes"] == first.bucket_bytes

    def boom(*a, **k):
        raise RuntimeError("probe ran despite a cached plan")

    monkeypatch.setattr(autotune, "_time_backend", boom)
    second = autotune.autotune_comm(params, mesh, ("data",), CommConfig(),
                                    backends=["lax"], reps=1, log=quiet,
                                    wire_formats=("fp32", "bf16", "int8"),
                                    cache_path=path)
    assert second == first
    # a different candidate set is a different key: must re-probe (and
    # here, hit the tripwire) — stale plans never leak across configs
    with pytest.raises(RuntimeError, match="probe ran"):
        autotune.autotune_comm(params, mesh, ("data",), CommConfig(),
                               backends=["lax"], reps=1, log=quiet,
                               wire_formats=("fp32",), cache_path=path)


def test_autotune_joint_choice_picks_int8_never_topk():
    """With a real fitted model the predicted wire time orders strictly by
    the reduce factor at equal latency count, so the joint (backend,
    format) winner is int8; topk is filtered from auto entirely (lossy AND
    stateful — explicit opt-in only)."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import AxisType
        from repro.comm import CommConfig
        from repro.telemetry.autotune import autotune_comm
        quiet = lambda *a, **k: None
        params = {"w": jnp.zeros((4096,), jnp.float32),
                  "b": jnp.zeros((512,), jnp.float32)}
        mesh = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4],
                             axis_types=(AxisType.Auto,))
        comm = autotune_comm(params, mesh, ("data",), CommConfig(),
                             backends=["lax"], reps=1, log=quiet,
                             wire_formats=("fp32", "bf16", "int8", "topk"))
        assert comm.wire_format == "int8", comm.wire_format
        assert comm.backend == "lax"
        print("OK")
    """, devices=4)
    assert "OK" in out


# ---------------------------------------------------------------------------
# elastic supervisor: world-size change invalidates the plan cache
# (fake-proc harness — duck-typed handles, no real processes)
# ---------------------------------------------------------------------------

class _FakeProc:
    def __init__(self, returncode):
        self.returncode = returncode

    def poll(self):
        return self.returncode


def _fake_handle(pid, returncode, tmpdir):
    from repro.cluster.launcher import WorkerHandle
    return WorkerHandle(proc=_FakeProc(returncode), process_id=pid,
                        hb_file=os.path.join(tmpdir, f"hb_{pid}"),
                        log_file=None)


def _elastic_fixture(tmp_path, monkeypatch, first_attempt_rcs, later_world_rc=0):
    """Monkeypatched spawn_workers: attempt 0 returns handles with the given
    returncodes; later attempts return a healthy group.  Pre-writes the
    autotune cache and worker 0's result.json."""
    from repro.cluster import elastic
    from repro.cluster.launcher import autotune_cache_path, result_path
    run_dir = str(tmp_path)
    cache = autotune_cache_path(run_dir)
    with open(cache, "w") as f:
        json.dump({"key": {"G": 2}, "plan": {"bucket_bytes": 1}}, f)
    with open(result_path(run_dir), "w") as f:
        json.dump({"final_loss": 1.0}, f)
    calls = []

    def fake_spawn(world, argv, rd, attempt=0, local_devices=1):
        calls.append((attempt, world))
        if attempt == 0:
            return [_fake_handle(i, rc, run_dir)
                    for i, rc in enumerate(first_attempt_rcs)]
        return [_fake_handle(i, later_world_rc, run_dir)
                for i in range(world)]

    monkeypatch.setattr(elastic, "spawn_workers", fake_spawn)
    return elastic, run_dir, cache, calls


def test_elastic_shrink_invalidates_autotune_cache(tmp_path, monkeypatch):
    elastic, run_dir, cache, calls = _elastic_fixture(
        tmp_path, monkeypatch, first_attempt_rcs=[0, -9])
    logs = []
    res = elastic.run_elastic(["worker"], run_dir, num_processes=2,
                              poll_interval=0.01, log=logs.append)
    assert res.final_world == 1 and res.attempts == 2
    assert calls == [(0, 2), (1, 1)]
    assert not os.path.exists(cache), \
        "stale autotune plan survived a world-size change"
    assert any("invalidated" in str(ln) for ln in logs), logs


def test_elastic_grow_back_same_world_keeps_cache(tmp_path, monkeypatch):
    """grow_back relaunches at FULL strength: the world size is unchanged,
    so the cached plan is still valid and must survive."""
    elastic, run_dir, cache, calls = _elastic_fixture(
        tmp_path, monkeypatch, first_attempt_rcs=[0, -9])
    res = elastic.run_elastic(["worker"], run_dir, num_processes=2,
                              poll_interval=0.01, grow_back=True,
                              log=lambda *_: None)
    assert res.final_world == 2 and res.attempts == 2
    assert calls == [(0, 2), (1, 2)]
    assert os.path.exists(cache), \
        "same-topology relaunch must not re-probe"


# ---------------------------------------------------------------------------
# topk error-feedback residual through checkpoint save/restore and replan
# ---------------------------------------------------------------------------

_TOPK_COMM = ('CommConfig(backend="pallas-ring", wire_format="topk", '
              'topk_ratio=0.25)')


def test_topk_ef_ckpt_resumes_same_world_exact(tmp_path):
    """Same-world resume restores the residual strictly (it is part of the
    saved opt_state), so one post-resume step lands on the SAME params as
    an uninterrupted run — the EF state round-trips losslessly."""
    ckpt = str(tmp_path / "ckpt")
    out = run_py(f"""
        import numpy as np, jax
        from repro.api import RunSpec, compile_run
        from repro.comm import CommConfig
        quiet = lambda *_: None
        base = RunSpec(arch="vgg-a", smoke=True, steps=3, batch=8,
                       schedule="constant", parallel="zero1",
                       comm={_TOPK_COMM},
                       ckpt_dir={ckpt!r}, ckpt_every=3, log_every=100)
        r1 = compile_run(base)
        r1.fit(log_fn=quiet)
        assert set(r1.opt_state) == {{"residual", "zero1"}}
        res = [np.asarray(x)
               for x in jax.tree.leaves(r1.opt_state["residual"])]
        assert any(np.abs(r).max() > 0 for r in res)   # EF mass carried
        r1.close()

        logs = []
        r2 = compile_run(base.replace(steps=4, ckpt_every=0))
        r2.fit(log_fn=logs.append)
        assert any("resuming from checkpoint step 3" in str(ln)
                   for ln in logs), logs
        r2.close()

        ref = compile_run(base.replace(steps=4, ckpt_dir=None,
                                       ckpt_every=0))
        ref.fit(log_fn=quiet); ref.close()
        for a, b in zip(jax.tree.leaves(r2.params),
                        jax.tree.leaves(ref.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)
        print("OK")
    """)
    assert "OK" in out


def test_topk_ef_ckpt_replans_across_worlds_rezeroing_residual(tmp_path):
    """Cross-world restore: the inner zero1 strips are re-planned to the
    new group size, but the residual is member-LOCAL unsent mass with no
    owner in the new world — it must come back ZERO at the new geometry."""
    ckpt = str(tmp_path / "ckpt")
    run_py(f"""
        from repro.api import RunSpec, compile_run
        from repro.comm import CommConfig
        spec = RunSpec(arch="vgg-a", smoke=True, steps=3, batch=8,
                       schedule="constant", parallel="zero1",
                       comm={_TOPK_COMM},
                       ckpt_dir={ckpt!r}, ckpt_every=3, log_every=100)
        run = compile_run(spec)
        run.fit(log_fn=lambda *_: None)
        run.close()
    """, devices=4)
    out = run_py(f"""
        import numpy as np, jax
        from repro.api import RunSpec, compile_run
        from repro.comm import CommConfig
        spec = RunSpec(arch="vgg-a", smoke=True, steps=4, batch=8,
                       schedule="constant", parallel="zero1",
                       comm={_TOPK_COMM},
                       ckpt_dir={ckpt!r}, log_every=100)
        run = compile_run(spec)
        run.restore(3)
        assert set(run.opt_state) == {{"residual", "zero1"}}
        for r in jax.tree.leaves(run.opt_state["residual"]):
            arr = np.asarray(r)
            assert arr.shape[0] == 2, arr.shape   # new world's G rows
            assert not arr.any()                  # re-zeroed, not replanned
        run.close()
        print("OK")
    """, devices=2)
    assert "OK" in out


def test_bare_zero1_ckpt_restores_into_topk_run(tmp_path):
    """Mode interop: a plain zero1 checkpoint (no residual saved) restores
    into a topk run — the inner strips load strictly, the EF wrapper
    re-initializes its residual to zero."""
    ckpt = str(tmp_path / "ckpt")
    out = run_py(f"""
        import numpy as np, jax
        from repro.api import RunSpec, compile_run
        from repro.comm import CommConfig
        quiet = lambda *_: None
        base = RunSpec(arch="vgg-a", smoke=True, steps=3, batch=8,
                       schedule="constant", parallel="zero1",
                       ckpt_dir={ckpt!r}, ckpt_every=3, log_every=100)
        rz = compile_run(base)
        rz.fit(log_fn=quiet); rz.close()

        rt = compile_run(base.replace(comm={_TOPK_COMM}, ckpt_every=0))
        rt.restore(3)
        assert set(rt.opt_state) == {{"residual", "zero1"}}
        for r in jax.tree.leaves(rt.opt_state["residual"]):
            assert not np.asarray(r).any()
        for a, b in zip(jax.tree.leaves(rt.opt_state["zero1"]),
                        jax.tree.leaves(rz.opt_state)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)
        rt.close()
        print("OK")
    """, devices=4)
    assert "OK" in out


# ---------------------------------------------------------------------------
# end-to-end acceptance: int8 on the Pallas ring converges with fp32
# ---------------------------------------------------------------------------

def test_int8_pallas_ring_smoke_within_1pct_of_fp32():
    out = run_py("""
        from repro.api import RunSpec, compile_run
        from repro.comm import CommConfig
        quiet = lambda *_: None
        def final(fmt):
            spec = RunSpec(arch="vgg-a", smoke=True, steps=4, batch=8,
                           schedule="constant", parallel="zero1",
                           comm=CommConfig(backend="pallas-ring",
                                           wire_format=fmt),
                           log_every=100)
            run = compile_run(spec)
            hist = run.fit(log_fn=quiet)
            run.close()
            return hist[-1]["loss"]
        fp32 = final("fp32")
        int8 = final("int8")
        gap = abs(int8 - fp32) / abs(fp32)
        assert gap <= 0.01, (fp32, int8, gap)
        print("OK", gap)
    """, devices=4)
    assert "OK" in out
