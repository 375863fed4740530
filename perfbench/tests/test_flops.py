"""The FLOP counts each configuration file records, the peaks table, and
the configurations the program is given."""
import json
import os

import pytest

import flops
import harness
import peaks

CONFIGS = os.path.join(harness.HERE, "configs")


def _cfg(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,total", [("vgg-a", 7.609e9),
                                        ("cd-dnn", 45.12e6)])
def test_forward_macs_match_the_published_sizes(name, total):
    macs = flops.forward_macs(_cfg(name))
    assert macs["total"] == pytest.approx(total, rel=5e-4)
    assert macs["conv"] + macs["fc"] == macs["total"]


def test_vgg_a_is_mostly_convolution():
    macs = flops.forward_macs(_cfg("vgg-a"))
    assert macs["conv"] == pytest.approx(7.485e9, rel=5e-4)
    assert flops.forward_macs(_cfg("cd-dnn"))["conv"] == 0


def _benchmark_configs() -> dict:
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return {c["name"]: c["file"] for c in json.load(f)["configs"]}


@pytest.mark.parametrize("name", sorted(_benchmark_configs()))
def test_recorded_counts_are_the_arithmetic(name):
    """Every configuration the benchmark runs records the FLOP count of
    its family: the family module's own, or ``flops.py``'s for the conv
    and fc families, whose arithmetic is checked further."""
    with open(os.path.join(harness.ROOT, _benchmark_configs()[name])) as f:
        cfg = json.load(f)
    assert cfg["step_flops_per_sample"] == harness.step_flops_per_sample(cfg)
    if hasattr(harness.family(cfg), "step_flops_per_sample"):
        return
    assert cfg["forward_macs_per_sample"] == flops.forward_macs(cfg)
    assert cfg["step_flops_per_sample"] == flops.step_flops_per_sample(cfg)
    # three passes of 2 FLOPs per multiply-add, less the first layer's
    # input gradient; conv and fc parts add up to the whole
    first = flops.layer_macs(flops.weighted_layers(cfg)[0])
    assert cfg["step_flops_per_sample"] == 6 * flops.forward_macs(cfg)[
        "total"] - 2 * first
    assert (flops.step_flops_per_sample(cfg, "conv")
            + flops.step_flops_per_sample(cfg, "fc")
            == cfg["step_flops_per_sample"])


def test_conv_least_time_is_mostly_compute_on_vgg_a():
    # float32 activations make the first two layers bound by memory
    # (1.7 GB and 1.2 GB a pass at batch 128); the rest by compute
    cfg = _cfg("vgg-a")
    peak = peaks.lookup("TPU v5 lite")
    least = flops.conv_least_seconds(cfg, 128, peak["bf16_flops_per_s"],
                                     peak["hbm_bytes_per_s"])
    compute = (flops.step_flops_per_sample(cfg, "conv") * 128
               / peak["bf16_flops_per_s"])
    assert compute < least < 1.25 * compute


def test_peaks_table_knows_the_v5e_and_refuses_others():
    v5e = peaks.lookup("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(peaks.UnknownDevice):
        peaks.lookup("cpu")


@pytest.mark.parametrize("name,arch", [("vgg-a", "vgg-a"),
                                       ("cd-dnn", "cd-dnn")])
def test_program_config_is_the_registry_one(name, arch):
    from repro.configs import get_config
    cfg = _cfg(name)
    built = harness.family(cfg).program_config(cfg)
    want = get_config(arch)
    fields = ("layers", "image_size", "num_classes") if cfg[
        "family"] == "cnn" else ("input_dim", "hidden_dim", "num_hidden",
                                 "output_dim")
    for f in fields:
        assert getattr(built, f) == getattr(want, f)


def test_a_family_module_keeps_its_own_count(monkeypatch):
    """A family beyond conv and fc counts its own FLOPs; the harness takes
    that count, and ``flops.py``'s only where the family keeps none."""
    import types
    own = types.SimpleNamespace(step_flops_per_sample=lambda cfg: 6 * cfg[
        "params"])
    monkeypatch.setattr(harness, "family", lambda cfg: own)
    assert harness.step_flops_per_sample({"params": 7}) == 42
