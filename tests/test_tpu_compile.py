"""Compile for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX, so the kernels of the main path and
whole full-width train steps are compiled here for a ``v5e:2x2`` topology:
what the chip's compiler refuses (a block not aligned to the tiling, more
VMEM than a kernel may use, a program larger than HBM) fails here, without
a chip.  Nothing runs, so these tests say nothing about results or time.

The topology is described only inside the module fixture: one process at a
time may load the TPU library, so no import may do it.  ``interpret=False``
is explicit everywhere, because the kernels' auto-selection sees the CPU.
"""
import functools
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, SingleDeviceSharding

import chip_smoke

HBM_BYTES = 16 * 2**30          # one v5e chip
MIB = 2**20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # before the TPU library loads: its logs go nowhere, not to /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


# ---------------------------------------------------------------------------
# ring hop kernels at vgg-a's default bucket and at one larger than VMEM
# ---------------------------------------------------------------------------
def _hop_args(kernel, chunk, sds):
    from repro.kernels import ring
    R = ring.tile_rows(chunk)
    chunks = sds((4, R, ring.LANES), jnp.float32)
    c = sds((), jnp.int32)
    if kernel == "ring_hop_accum":
        return chunks, sds((R, ring.LANES), jnp.float32), c
    if kernel == "int8_quantize":
        return (sds((R, ring.LANES), jnp.float32),)
    if kernel == "ring_hop_int8":
        return (chunks, sds((R, ring.LANES), jnp.int8),
                sds((1,), jnp.float32), c)
    k = math.ceil(0.05 * chunk)
    return chunks, sds((k,), jnp.float32), sds((k,), jnp.int32), c


@pytest.mark.parametrize("bucket_mib", [4, 64])
@pytest.mark.parametrize("kernel", ["ring_hop_accum", "int8_quantize",
                                    "ring_hop_int8", "ring_hop_topk"])
def test_ring_kernel_compiles(one_chip, kernel, bucket_mib):
    from repro.kernels import ring
    chunk = bucket_mib * MIB // 4 // 4          # f32 bucket over G = 4
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    fn = functools.partial(getattr(ring, kernel), interpret=False)
    compiled = _compile(fn, *_hop_args(kernel, chunk, sds))
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# paged decode attention at a served model's width
# ---------------------------------------------------------------------------
def test_paged_decode_attention_compiles(one_chip):
    from repro.api import ServeSpec
    from repro.configs import get_config
    from repro.kernels.paged_attn import paged_decode_attention
    arch = "mixtral-8x22b"                      # GQA 48/8, head_dim 128
    cfg, spec = get_config(arch), ServeSpec(arch=arch)
    B, ps = spec.max_batch, spec.page_size
    pool = (spec.num_pages, ps, cfg.num_kv_heads, cfg.head_dim)
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    fn = functools.partial(paged_decode_attention, window=cfg.sliding_window,
                           interpret=False)
    compiled = _compile(
        fn, sds((B, cfg.num_heads, cfg.head_dim), jnp.bfloat16),
        sds(pool, jnp.bfloat16), sds(pool, jnp.bfloat16),
        sds((B, spec.pages_per_request), jnp.int32), sds((B,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# whole full-width vgg-a zero1 train steps
# ---------------------------------------------------------------------------
def _train_step(devices, argv):
    """The train step ``compile_run`` assembles for the train CLI's
    ``argv``, on a (len(devices), 1) data mesh of described devices,
    lowered from shapes."""
    from repro.api.assemble import assemble_step, param_shardings
    from repro.api.families import adapter_for
    from repro.configs import get_config
    from repro.core.sharding import ShardingRules
    from repro.data.pipeline import BATCH_SPECS
    from repro.launch.train import parse_run_spec
    from repro.telemetry import make_recorder

    spec = parse_run_spec(argv)
    cfg = get_config(spec.arch)
    family = adapter_for(cfg)
    mesh = Mesh(np.array(devices).reshape(len(devices), 1), ("data", "model"))
    rules = ShardingRules()
    params = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(functools.partial(family.init, cfg),
                       jax.random.PRNGKey(spec.seed)),
        param_shardings(family, cfg, mesh, rules))
    parts = assemble_step(spec, cfg, family, mesh, rules, params,
                          make_recorder(None))
    hw, batch = cfg.image_size, spec.batch
    batch_sds = {
        "images": jax.ShapeDtypeStruct(
            (batch, hw, hw, 3), jnp.float32,
            sharding=rules.sharding(BATCH_SPECS["images"],
                                    (batch, hw, hw, 3), mesh)),
        "labels": jax.ShapeDtypeStruct(
            (batch,), jnp.int32,
            sharding=rules.sharding(BATCH_SPECS["labels"], (batch,), mesh))}
    with jax.set_mesh(mesh):
        state = jax.eval_shape(parts.init_fn, params)
        return jax.jit(parts.train_step, donate_argnums=(0, 1)).lower(
            params, state, 0, batch_sds).compile()


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def test_vgg_a_zero1_step_fits_one_chip(topo):
    # the step chip_smoke.py trains on one chip
    compiled = _train_step(topo.devices[:1], chip_smoke.ONE_CHIP_ARGV)
    assert 0 < _device_bytes(compiled) < HBM_BYTES


def test_vgg_a_zero1_pallas_ring_step_compiles_on_four_chips(topo,
                                                             monkeypatch):
    from repro.comm import backends
    from repro.comm.backends.pallas_ring import PallasRingBackend
    # the backend resolves by name and would pick interpret mode on this CPU
    monkeypatch.setitem(backends._FACTORIES, "pallas-ring",
                        functools.partial(PallasRingBackend, interpret=False))
    # the pallas-ring step chip_smoke.py --chips 4 trains
    compiled = _train_step(
        topo.devices[:4], chip_smoke.FOUR_CHIP_ARGV + ["--parallel", "zero1"]
        + chip_smoke.FOUR_CHIP_BACKENDS["pallas-ring"])
    assert "tpu_custom_call" in compiled.as_text()
    assert 0 < _device_bytes(compiled) < HBM_BYTES
