"""Paper Fig. 5 — convergence identity of distributed synchronous SGD.

The paper's claim: because nothing about the algorithm changes (no
hyperparameters, no compression, no asynchrony), the 32-node and 64-node
training curves OVERLAP the serial curve exactly.  We verify the mechanism:
training a reduced VGG-A with the same global batch split into 1, 2 and 4
synchronous 'nodes' (gradient-accumulation shards, the single-host
equivalent of data parallelism) yields identical loss trajectories.

The PARALLEL_MODES extension rides the same harness: the sync / stale-sync
/ gossip rows train the same net under the three consistency models' exact
node-level gradient math (full mean / one-step-old mean / rotating
GossipGraD pair mean — mirroring ``optim.dist`` + ``comm.backends.gossip``)
and report the final losses next to each mode's per-step wire-cost
prediction from ``core.balance`` — the convergence-vs-wire-time trade in
one table.

The compressed-wire rows (``CommConfig.wire_format``) do the same for the
lossy encodings: the int8 curve simulates the ring's per-hop
quantize / fp32-accumulate / re-quantize chain per chunk (the exact math
of ``kernels.ring.ring_hop_int8`` via the ``kernels.ref`` oracles), the
topk curve carries each node's error-feedback residual across steps and
re-selects per hop (mirroring ``optim.dist.make_topk_ef_update`` +
``comm.backends.pallas_ring``).  ``--out`` persists the rows and the
within-tolerance convergence gates as BENCH_fig5.json for the CI
regression gate."""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.comm.backends.pallas_ring import topk_chunk_k
from repro.configs import XEON_E5_2698V3_FDR, get_config, smoke_variant
from repro.core import balance
from repro.data import stream_for
from repro.kernels import ref as kref
from repro.models import cnn
from repro.optim import MomentumSGD, linear_scale_warmup

GLOBAL_BATCH = 16
STEPS = 8

INT8_TOL = 0.01   # acceptance: int8 final loss within 1% of fp32
TOPK_TOL = 0.05
# ratio for the GATED topk curve.  At the train-path default (0.05) the
# 8-step smoke gap is ~44% — the error-feedback residual closes it over
# LONG horizons, eight steps only bounds it (measured dose-response:
# ratio 0.05 -> 0.44, 0.10 -> 0.10, 0.25 -> 0.035); 0.25 is the densest
# ratio where topk still pays on the wire (2x fewer bytes than fp32, see
# core.balance.wire_reduce_factor) AND converges inside TOPK_TOL here
TOPK_RATIO = 0.25

# linear-scaling validation operating point (Goyal et al. recipe as wired
# into RunSpec via --schedule linear-scale-warmup): everything seeded, so
# these curves are bit-deterministic run to run
LSW_BASE_LR = 2e-3
LSW_STEPS = 40        # base-batch steps; the 2x batch runs LSW_STEPS/2
LSW_SCALE = 2
LSW_WARMUP = 5


def train_curve(num_nodes: int, seed: int = 0):
    cfg = smoke_variant(get_config("vgg-a"))
    params = cnn.init_params(cfg, jax.random.PRNGKey(seed))
    opt = MomentumSGD(momentum=0.9)
    state = opt.init(params)
    stream = stream_for(cfg, GLOBAL_BATCH, 0, seed=seed)
    losses = []

    @jax.jit
    def grad_on(params, batch):
        return jax.value_and_grad(
            lambda p: cnn.loss_fn(p, cfg, batch))(params)

    for _ in range(STEPS):
        batch = jax.tree.map(jnp.asarray, next(stream))
        shard = GLOBAL_BATCH // num_nodes
        loss_sum, grads = 0.0, None
        for i in range(num_nodes):   # synchronous nodes: grads averaged
            sub = jax.tree.map(lambda t: t[i * shard:(i + 1) * shard], batch)
            lv, g = grad_on(params, sub)
            loss_sum += float(lv) / num_nodes
            grads = g if grads is None else jax.tree.map(
                lambda a, b: a + b, grads, g)
        grads = jax.tree.map(lambda g: g / num_nodes, grads)
        params, state = opt.update(grads, state, params, 5e-3)
        losses.append(loss_sum)
    return np.array(losses)


def _mix_grads(mode: str, node_grads, carried, step: int):
    """One step of each consistency model's gradient math, at node level.

    ``node_grads`` is the per-node gradient-tree list; returns (tree the
    optimizer applies, carried state for the next step).  The math mirrors
    the device implementations exactly: sync is the full mean
    (``optim.dist.UpdatePlan.reduce``); stale-sync applies LAST step's mean
    and carries this step's (``make_stale_sync_update`` — step 0 applies
    its own); gossip flattens the trees to one fusion buffer and takes, for
    strip i, the pair mean of nodes i and (i - s) % N with the GossipGraD
    shift s = 1 + step % (N-1) (``comm.backends.gossip`` + the strip
    all-gather reassembly)."""
    n = len(node_grads)
    mean = jax.tree.map(lambda *g: sum(g) / n, *node_grads)
    if mode == "sync":
        return mean, None
    if mode == "stale":
        return (mean if carried is None else carried), mean
    assert mode == "gossip"
    leaves = [jax.tree.leaves(g) for g in node_grads]
    flats, shapes = [], [leaf.shape for leaf in leaves[0]]
    for ls in leaves:
        v = np.concatenate([np.asarray(leaf).ravel() for leaf in ls])
        pad = (-v.size) % n
        if pad:
            v = np.concatenate([v, np.zeros(pad, v.dtype)])
        flats.append(v.reshape(n, -1))     # node's buffer as n chunks
    s = 1 + step % (n - 1)
    strips = [(flats[i][i] + flats[(i - s) % n][i]) / 2.0 for i in range(n)]
    buf, out, off = np.concatenate(strips), [], 0
    for shp in shapes:
        size = int(np.prod(shp))
        out.append(jnp.asarray(buf[off:off + size].reshape(shp)))
        off += size
    treedef = jax.tree.structure(node_grads[0])
    return jax.tree.unflatten(treedef, out), None


def train_curve_mode(mode: str, num_nodes: int = 4, seed: int = 0):
    """``train_curve`` generalized over the consistency model: "sync"
    reproduces ``train_curve(num_nodes)`` exactly; "stale" and "gossip"
    swap in their gradient math via :func:`_mix_grads`."""
    cfg = smoke_variant(get_config("vgg-a"))
    params = cnn.init_params(cfg, jax.random.PRNGKey(seed))
    opt = MomentumSGD(momentum=0.9)
    state = opt.init(params)
    stream = stream_for(cfg, GLOBAL_BATCH, 0, seed=seed)
    losses, carried = [], None

    @jax.jit
    def grad_on(params, batch):
        return jax.value_and_grad(
            lambda p: cnn.loss_fn(p, cfg, batch))(params)

    for step in range(STEPS):
        batch = jax.tree.map(jnp.asarray, next(stream))
        shard = GLOBAL_BATCH // num_nodes
        loss_sum, node_grads = 0.0, []
        for i in range(num_nodes):
            sub = jax.tree.map(lambda t: t[i * shard:(i + 1) * shard], batch)
            lv, g = grad_on(params, sub)
            loss_sum += float(lv) / num_nodes
            node_grads.append(g)
        grads, carried = _mix_grads(mode, node_grads, carried, step)
        params, state = opt.update(grads, state, params, 5e-3)
        losses.append(loss_sum)
    return np.array(losses)


def parallel_mode_rows(num_nodes: int = 4):
    """The three-way consistency-model comparison: final smoke-VGG-A loss
    per mode plus each mode's predicted per-step wire seconds on the
    paper's FDR hardware (``core.balance``) — sync pays the full ring
    round-trip, gossip one partner exchange + the gather, stale-sync the
    sync bytes but hidden behind a whole step of compute."""
    cfg = smoke_variant(get_config("vgg-a"))
    params = cnn.init_params(cfg, jax.random.PRNGKey(0))
    total_bytes = sum(leaf.size * 4 for leaf in jax.tree.leaves(params))
    n_tensors = len(jax.tree.leaves(params))
    hw = XEON_E5_2698V3_FDR
    bucket = 4 * 2 ** 20
    t_sync = balance.bucketed_allreduce_time(total_bytes, n_tensors, bucket,
                                             num_nodes, hw)
    t_gossip = balance.gossip_exchange_time(total_bytes, n_tensors, bucket,
                                            num_nodes, hw)
    c_sync = train_curve_mode("sync", num_nodes)
    c_stale = train_curve_mode("stale", num_nodes)
    c_gossip = train_curve_mode("gossip", num_nodes)
    return [
        ("fig5/mode_final_loss_sync", float(c_sync[-1]), None),
        ("fig5/mode_final_loss_stale", float(c_stale[-1]),
         float(c_sync[-1])),
        ("fig5/mode_final_loss_gossip", float(c_gossip[-1]),
         float(c_sync[-1])),
        ("fig5/mode_wire_s_per_step_sync", t_sync, None),
        # stale-sync sends the sync bytes but a full step of compute hides
        # them; report the wire time it must hide (exposure is
        # stale_sync_exposed_time(t_sync, compute) -> 0 for these nets)
        ("fig5/mode_wire_s_per_step_stale_hidden", t_sync, t_sync),
        ("fig5/mode_wire_s_per_step_gossip", t_gossip, t_sync),
    ]


def _flatten_pad(g, n: int):
    """Gradient tree -> (n, m) chunked fusion buffer (zero-padded to a
    multiple of n — the bucketer's padding contract)."""
    v = jnp.concatenate([leaf.ravel().astype(jnp.float32)
                         for leaf in jax.tree.leaves(g)])
    pad = (-v.size) % n
    if pad:
        v = jnp.concatenate([v, jnp.zeros((pad,), jnp.float32)])
    return v.reshape(n, -1)


def _unflatten(buf, template):
    out, off = [], 0
    for leaf in jax.tree.leaves(template):
        out.append(buf[off:off + leaf.size].reshape(leaf.shape))
        off += leaf.size
    return jax.tree.unflatten(jax.tree.structure(template), out)


def _ring_reduce_compressed(fmt: str, flats, ratio: float):
    """The compressed ring reduce-scatter at node level: ``flats`` is the
    per-node list of (n, m) chunked buffers; chunk c starts at node c+1,
    hops the ring accumulating each node's contribution, and lands on its
    owner c — int8 dequantizes / fp32-accumulates / re-quantizes per hop
    (``kernels.ref.ring_hop_int8_ref``), topk re-selects its k wire
    entries per hop except the last (``ring_hop_topk_ref``; the owner
    keeps the dense accumulator).  Returns the dense concatenated sum."""
    n = len(flats)
    m = flats[0].shape[1]
    strips = []
    for c in range(n):
        start = (c + 1) % n
        if fmt == "int8":
            q, s = kref.int8_quantize_ref(flats[start][c])
            for j in range(2, n + 1):
                q, s = kref.ring_hop_int8_ref(flats[(c + j) % n], q, s, c)
            strips.append(kref.int8_dequantize_ref(q, s))
        else:
            assert fmt == "topk"
            k = topk_chunk_k(m, ratio)
            vals, idx = kref.topk_select_ref(flats[start][c], k)
            dense = kref.topk_scatter_ref(vals, idx, m)
            for j in range(2, n + 1):
                dense = kref.ring_hop_topk_ref(flats[(c + j) % n],
                                               vals, idx, c)
                if j < n:
                    vals, idx = kref.topk_select_ref(dense, k)
            strips.append(dense)
    return jnp.concatenate(strips)


def train_curve_wire(fmt: str, num_nodes: int = 4, seed: int = 0,
                     ratio: float = TOPK_RATIO):
    """``train_curve`` with the compressed-wire gradient path: per step the
    node gradients go through the node-level compressed ring of
    :func:`_ring_reduce_compressed`; topk first adds each node's carried
    error-feedback residual, keeps the bucket-level top k
    (``topk_mask_ref``, floor = num_nodes like ``make_topk_ef_update``)
    and carries the remainder to the next step."""
    cfg = smoke_variant(get_config("vgg-a"))
    params = cnn.init_params(cfg, jax.random.PRNGKey(seed))
    opt = MomentumSGD(momentum=0.9)
    state = opt.init(params)
    stream = stream_for(cfg, GLOBAL_BATCH, 0, seed=seed)
    losses, residuals = [], None

    @jax.jit
    def grad_on(params, batch):
        return jax.value_and_grad(
            lambda p: cnn.loss_fn(p, cfg, batch))(params)

    for _ in range(STEPS):
        batch = jax.tree.map(jnp.asarray, next(stream))
        shard = GLOBAL_BATCH // num_nodes
        loss_sum, node_grads = 0.0, []
        for i in range(num_nodes):
            sub = jax.tree.map(lambda t: t[i * shard:(i + 1) * shard], batch)
            lv, g = grad_on(params, sub)
            loss_sum += float(lv) / num_nodes
            node_grads.append(g)
        bufs = [_flatten_pad(g, num_nodes) for g in node_grads]
        if fmt == "topk":
            kb = topk_chunk_k(bufs[0].size, ratio, floor=num_nodes)
            kept = []
            new_res = []
            for i, b in enumerate(bufs):
                flat = b.reshape(-1)
                if residuals is not None:
                    flat = flat + residuals[i]
                keep = kref.topk_mask_ref(flat, kb)
                new_res.append(flat - keep)
                kept.append(keep.reshape(num_nodes, -1))
            residuals, bufs = new_res, kept
        total = _ring_reduce_compressed(fmt, bufs, ratio) / num_nodes
        grads = _unflatten(total, node_grads[0])
        params, state = opt.update(grads, state, params, 5e-3)
        losses.append(loss_sum)
    return np.array(losses)


def wire_format_rows(num_nodes: int = 4):
    """Compressed-wire convergence vs the fp32 reference: the acceptance
    gate is the relative final-loss gap (int8 within 1%, topk within its
    looser band) — persisted as booleans in BENCH_fig5.json's gates."""
    c_fp32 = train_curve_mode("sync", num_nodes)
    c_int8 = train_curve_wire("int8", num_nodes)
    c_topk = train_curve_wire("topk", num_nodes)
    f = float(c_fp32[-1])
    gap_int8 = abs(float(c_int8[-1]) - f) / abs(f)
    gap_topk = abs(float(c_topk[-1]) - f) / abs(f)
    return [
        ("fig5/wire_final_loss_fp32", f, None),
        ("fig5/wire_final_loss_int8", float(c_int8[-1]), f),
        ("fig5/wire_final_loss_topk", float(c_topk[-1]), f),
        ("fig5/wire_rel_gap_int8", gap_int8, INT8_TOL),
        ("fig5/wire_rel_gap_topk", gap_topk, TOPK_TOL),
    ]


def train_curve_sched(batch: int, steps: int, lr_fn, seed: int = 0):
    """Single-node trajectory under an arbitrary per-step LR schedule —
    the harness for the linear-scaling rows."""
    cfg = smoke_variant(get_config("vgg-a"))
    params = cnn.init_params(cfg, jax.random.PRNGKey(seed))
    opt = MomentumSGD(momentum=0.9)
    state = opt.init(params)
    stream = stream_for(cfg, batch, 0, seed=seed)

    @jax.jit
    def grad_on(params, batch):
        return jax.value_and_grad(
            lambda p: cnn.loss_fn(p, cfg, batch))(params)

    losses = []
    for step in range(steps):
        batch_ = jax.tree.map(jnp.asarray, next(stream))
        lv, g = grad_on(params, batch_)
        params, state = opt.update(g, state, params, float(lr_fn(step)))
        losses.append(float(lv))
    return np.array(losses)


def linear_scaling_rows():
    """Goyal et al. linear-scaling validation (the ``--schedule
    linear-scale-warmup`` recipe): at EQUAL samples seen, doubling the
    global batch with warmed-up 2x LR must land closer to the base-batch
    trajectory than the same doubled batch at the unscaled LR.  All three
    runs are seeded and single-host, so the comparison is deterministic;
    the final row is the gate (< 1 means the recipe closed part of the
    large-batch gap)."""
    sched = linear_scale_warmup(LSW_BASE_LR, LSW_SCALE, LSW_WARMUP,
                                LSW_STEPS // LSW_SCALE, final_frac=1.0)
    base = train_curve_sched(GLOBAL_BATCH, LSW_STEPS,
                             lambda s: LSW_BASE_LR)
    scaled = train_curve_sched(GLOBAL_BATCH * LSW_SCALE,
                               LSW_STEPS // LSW_SCALE, sched)
    unscaled = train_curve_sched(GLOBAL_BATCH * LSW_SCALE,
                                 LSW_STEPS // LSW_SCALE,
                                 lambda s: LSW_BASE_LR)
    gap_lsw = abs(float(scaled[-1]) - float(base[-1]))
    gap_plain = abs(float(unscaled[-1]) - float(base[-1]))
    return [
        ("fig5/lsw_lr_start", float(sched(0)), LSW_BASE_LR),
        ("fig5/lsw_lr_peak", float(sched(LSW_WARMUP)),
         LSW_BASE_LR * LSW_SCALE),
        ("fig5/lsw_final_loss_base_batch", float(base[-1]), None),
        ("fig5/lsw_final_loss_2x_batch_scaled", float(scaled[-1]),
         float(base[-1])),
        ("fig5/lsw_final_loss_2x_batch_unscaled", float(unscaled[-1]),
         float(base[-1])),
        ("fig5/lsw_gap_ratio_vs_unscaled", gap_lsw / gap_plain, 1.0),
    ]


def rows():
    c1 = train_curve(1)
    c2 = train_curve(2)
    c4 = train_curve(4)
    out = [("fig5/final_loss_serial", float(c1[-1]), None),
           ("fig5/final_loss_2node", float(c2[-1]), float(c1[-1])),
           ("fig5/final_loss_4node", float(c4[-1]), float(c1[-1])),
           ("fig5/max_curve_divergence_2node",
            float(np.max(np.abs(c1 - c2))), 0.0),
           ("fig5/max_curve_divergence_4node",
            float(np.max(np.abs(c1 - c4))), 0.0)]
    return out + linear_scaling_rows() + parallel_mode_rows() \
        + wire_format_rows()


def report() -> dict:
    """The persisted BENCH_fig5.json payload: every row plus the
    compressed-wire convergence gates CI asserts."""
    rws = rows()
    d = {name: {"value": v, "ref": ref} for name, v, ref in rws}
    dev = jax.devices()[0]
    return {
        "benchmark": "fig5_convergence",
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count()},
        "rows": d,
        "gates": {
            "int8_within_tol":
                d["fig5/wire_rel_gap_int8"]["value"] <= INT8_TOL,
            "topk_within_tol":
                d["fig5/wire_rel_gap_topk"]["value"] <= TOPK_TOL,
        },
    }


def main(argv=None):
    import argparse
    import json
    import os.path

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="persist the rows + convergence gates as JSON "
                         "(CI: benchmarks/BENCH_fig5.json)")
    args = ap.parse_args(argv)
    rep = report()
    print(f"{'metric':45s} {'value':>12s} {'paper/ref':>10s}")
    for name, row in rep["rows"].items():
        ref = row["ref"]
        p = f"{ref:10.4f}" if ref is not None else "         -"
        print(f"{name:45s} {row['value']:12.6f} {p}")
    if args.out:
        out = args.out if os.path.isabs(args.out) else os.path.join(
            os.path.dirname(os.path.abspath(__file__)), args.out)
        with open(out, "w") as f:
            json.dump(rep, f, indent=2)
            f.write("\n")
        print(f"# wrote {out}  (int8_within_tol="
              f"{rep['gates']['int8_within_tol']}, topk_within_tol="
              f"{rep['gates']['topk_within_tol']})")


if __name__ == "__main__":
    main()
