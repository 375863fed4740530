"""Compile for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX, so the kernels of the main path and
whole full-width train steps are compiled here for a ``v5e:2x2`` topology:
what the chip's compiler refuses (a block not aligned to the tiling, more
VMEM than a kernel may use, a program larger than HBM) fails here, without
a chip.  Nothing runs, so these tests say nothing about results or time.

The topology is described only inside the module fixture: one process at a
time may load the TPU library, so no import may do it.  ``interpret=False``
is explicit everywhere, because the kernels' auto-selection sees the CPU.

The compiled steps also carry the step-phase scopes
(``repro.telemetry.scopes``): each is compiled once, in a module fixture,
and both its size and its ops' phases are checked on that one compile.
"""
import functools
import math
import os
import re
import subprocess
import sys
import textwrap

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
    n = spec.batch
    if family.family == "cnn":
        hw = cfg.image_size
        fields = {"images": ((n, hw, hw, 3), jnp.float32),
                  "labels": ((n,), jnp.int32)}
    else:
        fields = {"frames": ((n, cfg.input_dim), jnp.float32),
                  "senones": ((n,), jnp.int32)}
    batch_sds = {
        k: jax.ShapeDtypeStruct(
            shape, dtype,
            sharding=rules.sharding(BATCH_SPECS[k], shape, mesh))
        for k, (shape, dtype) in fields.items()}
    with jax.set_mesh(mesh):
        state = jax.eval_shape(parts.init_fn, params)
        return jax.jit(parts.train_step, donate_argnums=(0, 1)).lower(
            params, state, 0, batch_sds).compile()


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


# the compiled steps: train CLI argv and chips
STEPS = {
    # the step chip_smoke.py trains on one chip
    "vgg_a_one_chip": (chip_smoke.ONE_CHIP_ARGV, 1),
    # zero1 with lax collectives on a (4, 1) data mesh
    "vgg_a_four_chips": (chip_smoke.FOUR_CHIP_ARGV + ["--parallel", "zero1"]
                         + chip_smoke.FOUR_CHIP_BACKENDS["lax"], 4),
    "cd_dnn_one_chip": (["--arch", "cd-dnn", "--parallel", "zero1",
                         "--batch", "1024", "--schedule", "constant"], 1),
}


def _step_of(topo, name):
    argv, n_chips = STEPS[name]
    return _train_step(topo.devices[:n_chips], argv)


@pytest.fixture(scope="module")
def vgg_a_one_chip(topo):
    return _step_of(topo, "vgg_a_one_chip")


@pytest.fixture(scope="module")
def vgg_a_four_chips(topo):
    return _step_of(topo, "vgg_a_four_chips")


@pytest.fixture(scope="module")
def cd_dnn_one_chip(topo):
    return _step_of(topo, "cd_dnn_one_chip")


def test_vgg_a_zero1_step_fits_one_chip(vgg_a_one_chip):
    assert 0 < _device_bytes(vgg_a_one_chip) < HBM_BYTES


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


# ---------------------------------------------------------------------------
# step-phase scopes: every compute and collective op names its phase
# ---------------------------------------------------------------------------
# what a fusion that only moves data holds: the compiler's relayouts of a
# tensor into a fusion buffer (the pack of a bucket, the (1, n) strip
# layout) carry no op_name
LAYOUT_ONLY = {"parameter", "constant", "bitcast", "reshape", "copy",
               "broadcast", "tuple", "get-tuple-element", "dynamic-slice",
               "dynamic-update-slice", "custom-call"}


def scope_report(text):
    """Each compute or collective instruction of the executed computations
    of ``text``: (phase from its own op_name, phase ``hlo_phases`` gives
    it, why its own op_name names no phase — ``None`` when it does,
    ``"layout-only"`` for a fusion that only moves or fills data (a
    relayout, a broadcast constant), ``"fused ops"`` for a multi-output
    fusion whose fused ops carry the op_names, ``"combined collective"``
    for a collective the compiler rewrote, alone or inside a fusion — or
    ``"missing"``)."""
    from repro.telemetry.scopes import (COLLECTIVE, hlo_phases, parse_hlo,
                                        phase_of)
    comps = parse_hlo(text)
    placed = hlo_phases(text)
    # fused computations and reducers run inside their caller
    called = {c for instrs in comps.values() for i in instrs
              if i.opcode == "fusion" for c in i.callees}
    called.update(re.findall(r"to_apply=%([\w.\-]+)", text))
    out = {}
    for comp, instrs in comps.items():
        if comp in called:
            continue
        for i in instrs:
            collective = bool(COLLECTIVE.match(i.opcode))
            if i.opcode not in ("convolution", "dot", "fusion") \
                    and not collective:
                continue
            inner = [j for c in i.callees if i.opcode == "fusion"
                     for j in comps.get(c, [])]
            own = phase_of(i.op_name) if i.op_name else None
            why = None
            if own in (None, "other"):
                if inner and {j.opcode for j in inner} <= LAYOUT_ONLY:
                    why = "layout-only"
                elif own is None and any(j.op_name for j in inner):
                    why = "fused ops"
                elif own is None and (collective or any(
                        COLLECTIVE.match(j.opcode) for j in inner)):
                    # a reduce-scatter the compiler made an all-reduce
                    # and a slice inside a fusion of its own
                    why = "combined collective"
                else:
                    why = "missing"
            out[i.name] = (own, placed[i.name], why)
    return out


def _check_scopes(text, phases=("fwd", "bwd", "reduce", "apply",
                                "broadcast")):
    report = scope_report(text)
    assert report
    unplaced = {n: r for n, r in report.items() if r[1] == "other"}
    assert not unplaced, unplaced
    missing = {n: r for n, r in report.items() if r[2] == "missing"}
    assert not missing, missing
    seen = {r[0] for r in report.values()} | {r[1] for r in report.values()}
    for p in phases:
        assert p in seen, (p, sorted(seen - {None}))
    return report


@pytest.mark.parametrize("step", ["vgg_a_one_chip", "vgg_a_four_chips",
                                  "cd_dnn_one_chip"])
def test_every_op_of_the_compiled_step_names_its_phase(step, request):
    report = _check_scopes(request.getfixturevalue(step).as_text())
    # the only ops without an op_name are the named kinds
    assert {r[2] for r in report.values()} <= {
        None, "fused ops", "layout-only", "combined collective"}


def test_four_chip_exchange_falls_under_bwd_and_update(vgg_a_four_chips):
    """GSPMD's gradient all-reduces run in the backward pass, the explicit
    zero1 exchange in ``update/reduce`` and ``update/broadcast``."""
    from repro.telemetry.scopes import COLLECTIVE, parse_hlo
    codes = {i.name: i.opcode
             for instrs in parse_hlo(vgg_a_four_chips.as_text()).values()
             for i in instrs}
    report = scope_report(vgg_a_four_chips.as_text())
    by_phase = {}
    for name, (_, placed, _) in report.items():
        if COLLECTIVE.match(codes[name]):
            by_phase.setdefault(placed, set()).add(codes[name])
    assert "all-reduce" in by_phase.get("bwd", ())
    assert "all-reduce" in by_phase.get("reduce", ())
    assert "all-gather" in by_phase.get("broadcast", ())


def test_overlapped_step_names_its_phases_on_four_cpu_devices():
    """The overlapped step, compiled on four virtual CPU devices: the
    reduce-scatters the backward-pass hooks issue fall under
    ``update/reduce``, whose scope outranks the backward pass around it."""
    code = textwrap.dedent("""
        import contextlib, jax
        from repro.api import RunSpec, compile_run
        from repro.comm.bucketer import CommConfig
        run = compile_run(RunSpec(arch="vgg-a", smoke=True,
                                  parallel="zero1", batch=8,
                                  comm=CommConfig(overlap=True)))
        with jax.set_mesh(run.mesh):
            c = run.jit_step.lower(run.params, run.opt_state, 0,
                                   next(run.data)).compile()
        run.close()
        print(c.as_text())
    """)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(root, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    # the CPU compiler drops the op_name of many ops it rewrites (weight-
    # gradient convolutions, pooling windows); those it keeps must name a
    # phase, and every collective keeps its own
    report = scope_report(out.stdout)
    kept = {n: r for n, r in report.items() if r[0] is not None}
    assert not {n: r for n, r in kept.items() if r[2] == "missing"}
    for p in ("fwd", "bwd", "reduce", "apply", "broadcast"):
        assert p in {r[0] for r in kept.values()}, p
    from repro.telemetry.scopes import COLLECTIVE, parse_hlo
    codes = {i.name: i.opcode for instrs in parse_hlo(out.stdout).values()
             for i in instrs}
    coll = {n: report[n][0] for n in report if COLLECTIVE.match(codes[n])}
    assert {c for n, c in codes.items() if n in coll} >= {
        "reduce-scatter", "all-gather"}
    assert all(p not in (None, "other") for p in coll.values()), coll
    assert {p for n, p in coll.items()
            if codes[n] == "reduce-scatter"} == {"reduce"}


# ---------------------------------------------------------------------------
# row-blocked buckets: the update moves no weight into another layout
# ---------------------------------------------------------------------------
_ARRAY = re.compile(r"[a-z]+\d*\[([\d,]*)\]\{([\d,]*)(?::([^}]*))?\}")
_TILING = re.compile(r"T(?:\([\d,]+\))+")


def _arrays(hlo_type):
    """(dims, minor_to_major, tiling) of each array in an HLO type: one
    array, or a tuple of them.  The memory space (``S(n)``) is left out:
    moving an array between memories keeps its layout."""
    out = []
    for dims, m2m, tail in _ARRAY.findall(hlo_type):
        tiling = _TILING.search(tail or "")
        out.append((tuple(int(d) for d in dims.split(",") if d),
                    tuple(int(d) for d in m2m.split(",") if d),
                    tiling.group(0) if tiling else ""))
    return out


def _physical(dims, m2m, tiling):
    """An array's arrangement in memory up to a bitcast.  Unit dimensions
    do not matter; a row-major array tiled on its last two dimensions is
    its ``(rows, cols)`` view, whatever leading dimensions make up its
    rows, when its second-minor dimension is a whole number of tiles."""
    keep = [d for d, n in enumerate(dims) if n != 1]
    rank = {d: i for i, d in enumerate(keep)}
    dims = tuple(dims[d] for d in keep)
    m2m = tuple(rank[d] for d in m2m if d in rank)
    tile = re.match(r"T\((\d+),(\d+)\)", tiling)
    if (len(dims) >= 2 and m2m == tuple(reversed(range(len(dims))))
            and tile and dims[-2] % int(tile.group(1)) == 0):
        return (math.prod(dims[:-1]), dims[-1]), tiling
    return dims, m2m, tiling


def _relayouts(argv, n_chips, text):
    """Copies, ``while`` loops and layout-only or dynamic-update-slice
    fusions of the compiled step ``text`` whose result holds as many
    elements as a row-blocked bucket's leaf, in another layout than the
    leaf's: what the strip update would spend moving the weight."""
    from repro.api.families import adapter_for
    from repro.comm.bucketer import CommConfig, plan_buckets
    from repro.configs import get_config
    from repro.launch.train import parse_run_spec
    from repro.telemetry.scopes import parse_hlo
    spec = parse_run_spec(argv)
    cfg = get_config(spec.arch)
    params = jax.eval_shape(functools.partial(adapter_for(cfg).init, cfg),
                            jax.random.PRNGKey(spec.seed))
    comm = spec.comm if isinstance(spec.comm, CommConfig) else CommConfig()
    plan = plan_buckets(params, n_chips, comm.bucket_bytes)
    # the params lead the entry computation's arguments, in tree order
    entry = re.search(r"entry_computation_layout=\{\((.*?)\)->", text)
    param_layouts = _arrays(entry.group(1))[:plan.n_leaves]
    leaf = {}
    for b in plan.buckets:
        if b.row_blocked:
            dims, m2m, tiling = param_layouts[b.slots[0].index]
            leaf[b.size] = _physical(dims, m2m, tiling)
    assert leaf, "no row-blocked bucket"
    comps = parse_hlo(text)
    types = dict(re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) [\w\-]+\(",
                            text, re.M))
    found = {}
    for instrs in comps.values():
        for i in instrs:
            inner = {j.opcode for c in i.callees for j in comps.get(c, [])}
            if not (i.opcode in ("copy", "copy-start", "copy-done", "while")
                    or i.opcode == "fusion" and (
                        "dynamic-update-slice" in i.name
                        or inner and inner <= LAYOUT_ONLY)):
                continue
            for dims, m2m, tiling in _arrays(types.get(i.name, "")):
                n = math.prod(dims)
                if n in leaf and _physical(dims, m2m, tiling) != leaf[n]:
                    found[i.name] = (i.opcode, dims, m2m, tiling)
    return found


@pytest.mark.parametrize("step", list(STEPS))
def test_update_moves_no_row_blocked_weight_into_another_layout(step,
                                                                request):
    argv, n_chips = STEPS[step]
    text = request.getfixturevalue(step).as_text()
    assert not _relayouts(argv, n_chips, text)


@pytest.mark.parametrize("op_name,phase", [
    ("jit(train_step)/jvp(fwd)/conv_general_dilated", "fwd"),
    ("jit(train_step)/transpose(jvp(fwd))/conv_general_dilated", "bwd"),
    ("jit(train_step)/transpose(jvp(fwd))/rematted_computation/dot_general",
     "bwd"),
    ("jit(train_step)/clip/sqrt", "clip"),
    ("jit(train_step)/shard_map/update/reduce/reduce_scatter", "reduce"),
    ("jit(train_step)/shard_map/transpose(jvp(fwd))/update/reduce/psum",
     "reduce"),
    ("jit(train_step)/shard_map/transpose(jvp(update/reduce))/"
     "reduce_scatter", "reduce"),
    ("jit(train_step)/update/apply/sub", "apply"),
    ("jit(train_step)/shard_map/update/broadcast/all_gather", "broadcast"),
    ("jit(train_step)/reduce_sum", "other"),
    ("jit(train_step)/fwd_like/add", "other"),
    ("jit(train_step)/jvp(fwd)/update_ms/add", "fwd"),
    ("", "other"),
])
def test_phase_of(op_name, phase):
    from repro.telemetry.scopes import PHASES, phase_of
    assert phase_of(op_name) == phase
    assert phase in PHASES
