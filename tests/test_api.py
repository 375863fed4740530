"""Tests for the declarative run-assembly layer (repro.api).

The in-process tests run on the single real CPU device (meshes degrade to
(1, 1)); the multi-device equivalence tests reuse the subprocess machinery
of test_distributed.py so the rest of the suite keeps one device."""
import dataclasses

import pytest

from test_distributed import run_py


# ---------------------------------------------------------------------------
# RunSpec validation + family registry (no jax compute)
# ---------------------------------------------------------------------------
def test_runspec_validates_fields():
    from repro.api import RunSpec
    with pytest.raises(ValueError):
        RunSpec(arch="vgg-a", parallel="async")
    with pytest.raises(ValueError):
        RunSpec(arch="vgg-a", optimizer="lars")
    with pytest.raises(ValueError):
        RunSpec(arch="vgg-a", schedule="linear")
    with pytest.raises(ValueError):
        RunSpec(arch="vgg-a", steps=0)
    with pytest.raises(ValueError):
        # comm knobs only drive the explicit bucketed zero1 path; setting
        # them on dp/serial would be silently ignored, so it's rejected
        from repro.comm import CommConfig
        RunSpec(arch="vgg-a", parallel="dp", comm=CommConfig())
    spec = RunSpec(arch="vgg-a")
    assert dataclasses.replace(spec, parallel="zero1").parallel == "zero1"


def test_mode_caps_table_drives_validation():
    """Satellite: the MODE_CAPS capability table replaces the comm->zero1
    special-case.  Every parallel mode has an entry, and each comm knob is
    accepted or rejected per the table, not per hard-coded mode names."""
    from repro.api import MODE_CAPS, PARALLEL_MODES, ModeCaps, RunSpec
    from repro.comm import CommConfig

    assert set(PARALLEL_MODES) == set(MODE_CAPS)
    assert {"serial", "dp", "zero1", "zero1-gspmd",
            "stale-sync", "gossip"} <= set(MODE_CAPS)
    assert isinstance(MODE_CAPS["zero1"], ModeCaps)

    # commful modes accept comm; comm-less modes reject it
    for mode in ("zero1", "stale-sync", "gossip"):
        assert MODE_CAPS[mode].comm
    RunSpec(arch="vgg-a", parallel="stale-sync",
            comm=CommConfig(bucket_bytes=1 << 14))
    with pytest.raises(ValueError):
        RunSpec(arch="vgg-a", parallel="serial", comm=CommConfig())

    # overlap is a zero1-only capability: stale-sync re-schedules the
    # reduce across steps itself, so the backward-pass hooks don't apply
    with pytest.raises(ValueError):
        RunSpec(arch="vgg-a", parallel="stale-sync",
                comm=CommConfig(overlap=True))
    with pytest.raises(ValueError):
        RunSpec(arch="vgg-a", parallel="gossip",
                comm=CommConfig(overlap=True, backend="gossip"))

    # the gossip backend is selected by parallel="gossip", not as a zero1
    # backend swap (it changes the consistency model, not just the wire)
    with pytest.raises(ValueError):
        RunSpec(arch="vgg-a", parallel="zero1",
                comm=CommConfig(backend="gossip"))
    RunSpec(arch="vgg-a", parallel="gossip",
            comm=CommConfig(backend="gossip"))
    # stale-sync runs the synchronous wire: lax or the Pallas ring
    RunSpec(arch="vgg-a", parallel="stale-sync",
            comm=CommConfig(backend="pallas-ring"))
    with pytest.raises(ValueError):
        RunSpec(arch="vgg-a", parallel="stale-sync",
                comm=CommConfig(backend="gossip"))


def test_mode_caps_drive_cli_mapping():
    """launch.train derives its argument checks and backend defaults from
    MODE_CAPS: --parallel gossip flips the default --comm-backend to
    gossip and stays flat even under --pods 2."""
    import argparse

    from repro.launch.train import add_run_args, check_run_args, \
        spec_from_args

    ap = add_run_args(argparse.ArgumentParser())

    def parse(*argv):
        return ap.parse_args(list(argv))

    args = parse("--arch", "vgg-a", "--smoke", "--parallel", "gossip",
                 "--pods", "2", "--bucket-mb", "4")
    check_run_args(ap, args)
    spec = spec_from_args(args)
    assert spec.comm.backend == "gossip"
    assert not spec.comm.hierarchical

    # no comm flags -> comm stays None; assemble picks the mode default
    assert spec_from_args(parse("--arch", "vgg-a", "--smoke",
                                "--parallel", "gossip")).comm is None

    args = parse("--arch", "vgg-a", "--smoke", "--parallel", "stale-sync",
                 "--bucket-mb", "4")
    check_run_args(ap, args)
    assert spec_from_args(args).comm.bucket_bytes == 4 * 2 ** 20

    with pytest.raises(SystemExit):
        check_run_args(ap, parse("--arch", "vgg-a", "--smoke",
                                 "--parallel", "stale-sync", "--overlap"))
    with pytest.raises(SystemExit):
        check_run_args(ap, parse("--arch", "vgg-a", "--smoke",
                                 "--parallel", "zero1",
                                 "--comm-backend", "gossip"))
    with pytest.raises(SystemExit):
        check_run_args(ap, parse("--arch", "vgg-a", "--smoke",
                                 "--parallel", "serial", "--bucket-mb", "4"))


def test_meshspec_axes():
    from repro.api import MeshSpec
    assert MeshSpec().axis_names == ("data", "model")
    assert MeshSpec(pods=2).axis_names == ("pod", "data", "model")
    assert MeshSpec(pods=2).data_axes == ("pod", "data")
    assert MeshSpec().data_axes == ("data",)


def test_family_registry_resolves_all_config_types():
    from repro.api import adapter_for, families
    from repro.configs import get_config
    assert set(families()) == {"cnn", "dnn", "transformer"}
    assert adapter_for(get_config("vgg-a")).family == "cnn"
    assert adapter_for(get_config("cd-dnn")).family == "dnn"
    assert adapter_for(get_config("llama3-8b")).family == "transformer"
    with pytest.raises(TypeError):
        adapter_for(object())


def test_register_family_override_wins():
    from repro.api import adapter_for, register_family
    from repro.api.families import CNN_FAMILY
    from repro.configs import get_config
    cfg = get_config("vgg-a")
    custom = dataclasses.replace(CNN_FAMILY, family="cnn-custom")
    register_family(custom)
    try:
        assert adapter_for(cfg).family == "cnn-custom"
    finally:
        register_family(CNN_FAMILY)
    assert adapter_for(cfg).family == "cnn"


def test_smoke_and_stream_delegate_to_adapters():
    """configs.smoke_variant / data.stream_for route through the registry
    (the isinstance ladders are gone) and keep their old behavior."""
    import numpy as np

    from repro.configs import get_config, smoke_variant
    from repro.data import stream_for
    cnn_smoke = smoke_variant(get_config("vgg-a"))
    assert cnn_smoke.name == "vgg-a-smoke" and cnn_smoke.image_size == 32
    dnn_smoke = smoke_variant(get_config("cd-dnn"))
    assert dnn_smoke.hidden_dim == 64
    lm_smoke = smoke_variant(get_config("llama3-8b"))
    assert lm_smoke.d_model <= 256
    b = next(stream_for(cnn_smoke, 4, 0))
    assert b["images"].shape == (4, 32, 32, 3)
    b = next(stream_for(lm_smoke, 2, 16))
    assert b["tokens"].shape == (2, 16)
    assert b["tokens"].dtype == np.int32


# ---------------------------------------------------------------------------
# throughput accounting (satellite: CNN/DNN runs reported 0 tok/s)
# ---------------------------------------------------------------------------
def test_trainer_counts_samples_for_vision_batches():
    import numpy as np

    from repro.train.trainer import _batch_items
    n, unit = _batch_items({"tokens": np.zeros((4, 16))})
    assert (n, unit) == (64, "tok")
    n, unit = _batch_items({"images": np.zeros((8, 32, 32, 3)),
                            "labels": np.zeros((8,))})
    assert (n, unit) == (8, "samples")
    n, unit = _batch_items({"frames": np.zeros((5, 40)),
                            "senones": np.zeros((5,))})
    assert (n, unit) == (5, "samples")
    n, unit = _batch_items({"codebook_labels": np.zeros((2, 8, 4)),
                            "frame_embeds": np.zeros((2, 8, 16))})
    assert (n, unit) == (64, "tok")


# ---------------------------------------------------------------------------
# compile matrix: every arch x every parallel mode assembles
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("parallel", ["serial", "dp", "zero1",
                                      "stale-sync", "gossip"])
def test_compile_run_matrix(parallel):
    import jax

    from repro.api import RunSpec, compile_run
    from repro.configs import ALL_ARCHS
    for arch in ALL_ARCHS:
        spec = RunSpec(arch=arch, smoke=True, parallel=parallel,
                       steps=2, batch=2, seq=32)
        run = compile_run(spec)
        assert callable(run.train_step), arch
        assert jax.tree.leaves(run.params), arch
        assert run.family.family in ("cnn", "dnn", "transformer")
        if parallel == "serial":
            assert run.mesh is None
        else:
            assert "data" in run.mesh.axis_names
        # opt_state materialized (zero1: strip-sharded fusion buffers)
        assert jax.tree.leaves(run.opt_state) is not None
        run.close()


def test_compile_run_one_train_step_per_family():
    """One real step through the compiled Run for each family (serial)."""
    from repro.api import RunSpec, compile_run
    for arch in ("vgg-a", "cd-dnn", "llama-100m"):
        run = compile_run(RunSpec(arch=arch, smoke=True, steps=2, batch=2,
                                  seq=32, log_every=1))
        metrics = run.step(next(run.data))
        assert float(metrics["loss"]) > 0, arch
        run.close()


# ---------------------------------------------------------------------------
# multi-device equivalence: RunSpec(zero1) == RunSpec(serial) to float tol
# ---------------------------------------------------------------------------
def test_api_zero1_matches_serial_vgg():
    """The compiled zero1 step (explicit bucketed §3.4 strips over an
    8-way data mesh) reproduces the serial run's params to float
    tolerance — the acceptance property for the api layer."""
    run_py("""
        import numpy as np, jax
        from repro.api import RunSpec, compile_run
        from repro.comm import CommConfig
        quiet = lambda *_: None
        base = RunSpec(arch="vgg-a", smoke=True, steps=3, batch=8, lr=5e-3,
                       schedule="constant", log_every=100, seed=0)
        rs = compile_run(base)
        hs = rs.fit(log_fn=quiet); rs.close()
        for comm in (None, CommConfig(bucket_bytes=1 << 14),
                     CommConfig(bucket_bytes=1 << 25)):
            rz = compile_run(base.replace(parallel="zero1", comm=comm))
            assert rz.mesh.shape["data"] == 8
            hz = rz.fit(log_fn=quiet); rz.close()
            np.testing.assert_allclose(hz[-1]["loss"], hs[-1]["loss"],
                                       rtol=1e-5)
            for a, b in zip(jax.tree.leaves(rs.params),
                            jax.tree.leaves(rz.params)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-4, atol=1e-6)
        print("OK")
    """)


def test_api_overlap_matches_serial_vgg():
    """CommConfig(overlap=True): the §3.1 backprop-overlapped zero1 run —
    bucket reduces issued inside the backward pass — reproduces the serial
    run to float tolerance, flat (8-way) and hierarchical (2 pods)."""
    run_py("""
        import numpy as np, jax
        from repro.api import RunSpec, MeshSpec, compile_run
        from repro.comm import CommConfig
        quiet = lambda *_: None
        base = RunSpec(arch="vgg-a", smoke=True, steps=3, batch=8, lr=5e-3,
                       schedule="constant", log_every=100, seed=0)
        rs = compile_run(base)
        hs = rs.fit(log_fn=quiet); rs.close()
        variants = [
            base.replace(parallel="zero1",
                         comm=CommConfig(bucket_bytes=1 << 14, overlap=True)),
            base.replace(parallel="zero1", mesh=MeshSpec(pods=2),
                         comm=CommConfig(bucket_bytes=1 << 14, overlap=True,
                                         hierarchical=True)),
        ]
        for spec in variants:
            rz = compile_run(spec)
            hz = rz.fit(log_fn=quiet); rz.close()
            np.testing.assert_allclose(hz[-1]["loss"], hs[-1]["loss"],
                                       rtol=1e-5)
            for a, b in zip(jax.tree.leaves(rs.params),
                            jax.tree.leaves(rz.params)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-4, atol=1e-6)
        print("OK")
    """)


def test_api_zero1_resume_roundtrip():
    """Kill-and-relaunch semantics under zero1: a run interrupted at step 4
    and recompiled from scratch resumes from the checkpoint (strip opt_state
    restored ONTO its data-axis shardings, data stream re-aligned) and lands
    exactly where the uninterrupted run does."""
    run_py("""
        import tempfile, numpy as np, jax
        from repro.api import RunSpec, compile_run
        from repro.comm import CommConfig
        quiet = lambda *_: None
        with tempfile.TemporaryDirectory() as d1, \\
                tempfile.TemporaryDirectory() as d2:
            base = RunSpec(arch="vgg-a", smoke=True, steps=6, batch=8,
                           lr=5e-3, schedule="constant", log_every=1,
                           parallel="zero1",
                           comm=CommConfig(bucket_bytes=1 << 14),
                           ckpt_every=2, ckpt_dir=d1)
            # "killed" run: only 4 of the 6 steps happen
            ra = compile_run(base.replace(steps=4))
            ra.fit(log_fn=quiet); ra.close()
            # relaunch with the SAME ckpt_dir: must resume at 4, not 0
            logs = []
            rb = compile_run(base)
            hb = rb.fit(log_fn=logs.append); rb.close()
            assert any("resuming from checkpoint step 4" in str(ln)
                       for ln in logs), logs
            assert hb[0]["step"] == 5, hb
            # uninterrupted reference over the same seeded stream
            rc = compile_run(base.replace(ckpt_dir=d2))
            hc = rc.fit(log_fn=quiet); rc.close()
            np.testing.assert_allclose(hb[-1]["loss"], hc[-1]["loss"],
                                       rtol=1e-6)
            for a, b in zip(jax.tree.leaves(rb.params),
                            jax.tree.leaves(rc.params)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-5, atol=1e-7)
            # restored zero1 strip state sits on the run's shardings, and a
            # finished run relaunched again trains zero further steps
            rd = compile_run(base)
            hd = rd.fit(log_fn=quiet)
            assert hd == []
            for s in jax.tree.leaves(rd.opt_state):
                if getattr(s, "ndim", 0) >= 2:
                    assert "data" in str(s.sharding.spec), s.sharding
            rd.close()
        print("OK")
    """)


def test_api_zero1_hierarchical_and_gspmd_match_serial_lm():
    """Transformer family: the pods=2 hierarchical zero1 run and the
    GSPMD zero1 run both reproduce serial training."""
    run_py("""
        import numpy as np, jax
        from repro.api import RunSpec, MeshSpec, compile_run
        from repro.comm import CommConfig
        quiet = lambda *_: None
        # momentum SGD: linear in the gradients, so float-level gradient
        # noise stays float-level in the params (AdamW's m/sqrt(v) turns
        # noise-level grads of unused vocab rows into +-lr sign flips)
        base = RunSpec(arch="llama3-8b", smoke=True, steps=2, batch=8,
                       seq=16, lr=1e-3, optimizer="sgd",
                       schedule="constant", log_every=100)
        rs = compile_run(base)
        hs = rs.fit(log_fn=quiet); rs.close()
        variants = [
            base.replace(parallel="zero1", mesh=MeshSpec(pods=2),
                         comm=CommConfig(bucket_bytes=1 << 16,
                                         hierarchical=True)),
            base.replace(parallel="zero1-gspmd"),
        ]
        for spec in variants:
            rv = compile_run(spec)
            hv = rv.fit(log_fn=quiet); rv.close()
            np.testing.assert_allclose(hv[-1]["loss"], hs[-1]["loss"],
                                       rtol=2e-3, err_msg=spec.parallel)
            for a, b in zip(jax.tree.leaves(rs.params),
                            jax.tree.leaves(rv.params)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-3, atol=1e-5,
                                           err_msg=spec.parallel)
        print("OK")
    """)


def test_api_pallas_ring_matches_serial_vgg():
    """CommConfig(backend="pallas-ring"): the compiled zero1 run through the
    explicit Pallas ring collectives reproduces the serial run to float
    tolerance — flat (8-way) and hierarchical (2 pods), with and without
    the §3.1 backprop overlap.  The acceptance property for the backend
    seam: swapping the wire implementation must not change training."""
    run_py("""
        import numpy as np, jax
        from repro.api import RunSpec, MeshSpec, compile_run
        from repro.comm import CommConfig
        quiet = lambda *_: None
        base = RunSpec(arch="vgg-a", smoke=True, steps=3, batch=8, lr=5e-3,
                       schedule="constant", log_every=100, seed=0)
        rs = compile_run(base)
        hs = rs.fit(log_fn=quiet); rs.close()
        ring = dict(bucket_bytes=1 << 16, backend="pallas-ring")
        variants = [
            base.replace(parallel="zero1", comm=CommConfig(**ring)),
            base.replace(parallel="zero1",
                         comm=CommConfig(overlap=True, **ring)),
            base.replace(parallel="zero1", mesh=MeshSpec(pods=2),
                         comm=CommConfig(hierarchical=True, **ring)),
            base.replace(parallel="zero1", mesh=MeshSpec(pods=2),
                         comm=CommConfig(hierarchical=True, overlap=True,
                                         **ring)),
        ]
        for spec in variants:
            rz = compile_run(spec)
            hz = rz.fit(log_fn=quiet); rz.close()
            tag = (f"hier={spec.comm.hierarchical}/"
                   f"overlap={spec.comm.overlap}")
            np.testing.assert_allclose(hz[-1]["loss"], hs[-1]["loss"],
                                       rtol=1e-5, err_msg=tag)
            for a, b in zip(jax.tree.leaves(rs.params),
                            jax.tree.leaves(rz.params)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-4, atol=1e-6, err_msg=tag)
        print("OK")
    """)


def test_api_stale_sync_and_gossip_converge_vs_serial():
    """The relaxed-consistency acceptance property, on both paper
    workloads: (a) gossip — every member computes the same global-batch
    gradient in this single-process emulation, so the pair mean equals the
    full mean and the run must TRACK serial to float tolerance; (b)
    stale-sync — a one-step-old gradient, so the trajectory lags but must
    still optimize (VGG-A: large loss drop) and stay glued to serial where
    the landscape is flat (cd-dnn)."""
    run_py("""
        import numpy as np
        from repro.api import RunSpec, compile_run
        quiet = lambda *_: None

        def fit(arch, mode, steps, lr):
            r = compile_run(RunSpec(arch=arch, smoke=True, parallel=mode,
                                    steps=steps, batch=8, lr=lr,
                                    schedule="constant", log_every=100,
                                    seed=0))
            h = r.fit(log_fn=quiet); r.close()
            return [float(x["loss"]) for x in h]

        # VGG-A: all three modes must actually train (from a fan-in init
        # at loss ~ln 16, halving the loss takes ~40 steps at 1e-2)
        serial = fit("vgg-a", "serial", 40, 1e-2)
        gossip = fit("vgg-a", "gossip", 40, 1e-2)
        stale = fit("vgg-a", "stale-sync", 40, 1e-2)
        np.testing.assert_allclose(gossip, serial, rtol=1e-4)
        assert serial[-1] < 0.5 * serial[0], serial
        assert stale[-1] < 0.5 * stale[0], stale
        # one-step staleness lags but stays the same order as serial
        assert stale[-1] < 2.0 * serial[-1], (stale[-1], serial[-1])

        # cd-dnn: both modes track the serial trajectory
        serial = fit("cd-dnn", "serial", 8, 5e-4)
        gossip = fit("cd-dnn", "gossip", 8, 5e-4)
        stale = fit("cd-dnn", "stale-sync", 8, 5e-4)
        np.testing.assert_allclose(gossip, serial, rtol=1e-4)
        np.testing.assert_allclose(stale, serial, rtol=5e-2, atol=5e-2)
        print("OK")
    """)
