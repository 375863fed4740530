"""Paper §3.4 ring collectives as Pallas kernels.

The paper's part-reduce / part-broadcast pair is bandwidth-optimal when run
as a RING: each of the G members repeatedly sends one 1/G chunk to its right
neighbor and combines the chunk it receives from the left — G-1 neighbor
exchanges move 2*(G-1)/G of the buffer per member (``core.balance.
ring_collective_time``).  This module implements that schedule explicitly:

``ring_reduce_scatter`` / ``ring_all_gather``
    The full §3.4 ring over a stacked ``(G, N)`` buffer (member p's partial
    in row p) in ONE kernel: grid ``(G-1 steps, G members)``, executed in
    step-major order, with a double-buffered mailbox ``(2, G, chunk)``
    standing in for the neighbor RDMA slots — program ``(s, p)`` writes the
    chunk it "sends" into the slot program ``(s+1, p+1)`` reads, alternating
    buffer parity per step exactly like the double-buffered remote-copy ring
    of the Pallas TPU guide (send/recv slot = step % 2).  On a real slice
    the same schedule runs one program per chip with
    ``pltpu.make_async_remote_copy`` to the right neighbor; the stacked
    single-core form keeps the rotation/parity logic identical and runs
    under ``interpret=True`` on CPU, where it is validated against the
    ``kernels.ref`` oracles (tests/test_kernels.py).

``ring_hop_accum``
    The per-hop combine of the distributed ring — ``recv + chunks[c]`` with
    the chunk index prefetched as a scalar — used by
    ``repro.comm.backends.PallasRingBackend`` inside ``shard_map``: there
    the neighbor exchange itself is a ``lax.ppermute`` (XLA's ICI neighbor
    DMA), and this kernel is the compute the ring overlaps with it.  The hop
    kernels take chunks in a lane-dense ``(R, 128)`` tile layout
    (:func:`to_tiles`) and walk it with a grid of bounded blocks, so they
    compile for Mosaic at any bucket size (``tests/test_tpu_compile.py``
    compiles them for a v5e at 4 MiB and 64 MiB buckets).

Chunk/owner convention (must match ``lax.psum_scatter(tiled=True)`` so the
backends are interchangeable): the buffer splits into G equal chunks along
dim 0 and flat group member i ends up owning chunk i.  At step s, member p
receives the partial sum of chunk ``(p - 2 - s) % G``, adds its own
contribution, and forwards it — after G-1 hops the fully-reduced chunk p
lands on member p.  All-gather inverts it: member p's strip visits every
member in G-1 hops, arriving at member p+k as chunk ``(p) = ((p+k) - k)``.

Accumulation happens in the input dtype: the ring's hop-adds ARE the wire
arithmetic, so a bf16 wire dtype accumulates in bf16 per hop (the schedule
layer casts back to fp32 after the reduce, and the cross-pod hop of the
hierarchical schedule always runs fp32 — see ``repro.comm.schedule``).

``int8_quantize`` / ``ring_hop_int8`` / ``ring_hop_topk``
    The compressed wire formats (``CommConfig.wire_format``), fused into
    the per-hop combine: ``ring_hop_int8`` dequantizes the received int8
    message against its per-message scale, adds the local chunk partial in
    **f32**, and re-quantizes against a fresh max-abs scale — one rounding
    per hop, so quantization error stays additive across the G-1 hops
    instead of compounding.  ``ring_hop_topk`` scatter-adds a received
    (values, indices) sparse message dense and adds the local partial; the
    top-k RE-selection for the next hop is plain ``lax.top_k`` in the
    backend (selection is not a memory-bound combine, fusing it buys
    nothing).  The int8 scale is written through SMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# the full §3.4 ring, one kernel (stacked single-core form)
# ---------------------------------------------------------------------------
def _reduce_scatter_kernel(x_ref, out_ref, buf_ref):
    """Program (s, p): member p's step-s hop of the ring reduce-scatter.

    x_ref    (G, G, n)  member p's local partials, split into G chunks
    out_ref  (G, n)     member p's fully-reduced strip (written at s=G-2)
    buf_ref  (2, G, n)  double-buffered mailboxes: slot ``(s+1) % 2, q`` is
                        what q's left neighbor sent it for step s+1
    """
    s, p = pl.program_id(0), pl.program_id(1)
    G = pl.num_programs(1)
    steps = pl.num_programs(0)
    c = jnp.mod(p - 2 - s, G)       # chunk whose partial arrives this step
    left = jnp.mod(p - 1, G)
    recv = jax.lax.cond(
        s == 0,
        # first hop: the left neighbor sends its RAW local chunk
        lambda: x_ref[left, c],
        lambda: buf_ref[jnp.mod(s, 2), p])
    acc = recv + x_ref[p, c]
    # "send" to the right neighbor: the mailbox it reads at step s+1
    buf_ref[jnp.mod(s + 1, 2), jnp.mod(p + 1, G)] = acc

    @pl.when(s == steps - 1)
    def _():
        out_ref[p] = acc            # c == p at the final step


def _all_gather_kernel(x_ref, out_ref, buf_ref):
    """Program (s, p): member p's step-s hop of the ring all-gather.

    x_ref    (G, n)     member p's strip in row p
    out_ref  (G, G, n)  row p = member p's gathered copy, chunk o = strip o
    buf_ref  (2, G, n)  double-buffered mailboxes (parity = step % 2)
    """
    s, p = pl.program_id(0), pl.program_id(1)
    G = pl.num_programs(1)
    o = jnp.mod(p - 1 - s, G)       # owner of the strip arriving this step
    left = jnp.mod(p - 1, G)

    @pl.when(s == 0)
    def _():
        out_ref[p, p] = x_ref[p]    # own strip needs no hop

    recv = jax.lax.cond(
        s == 0,
        lambda: x_ref[left],
        lambda: buf_ref[jnp.mod(s, 2), p])
    out_ref[p, o] = recv
    buf_ref[jnp.mod(s + 1, 2), jnp.mod(p + 1, G)] = recv


def ring_reduce_scatter(stacked: jax.Array, *,
                        interpret: Optional[bool] = None) -> jax.Array:
    """Reduce-scatter a stacked ``(G, N)`` buffer of per-member partials:
    row p of the ``(G, N // G)`` result is the fully-reduced chunk p —
    member p's strip under the §3.4 owner convention.  ``N % G == 0``
    (fusion buckets are padded to a strip multiple by ``repro.comm``)."""
    G, N = stacked.shape
    if N % G:
        raise ValueError(f"buffer size {N} not divisible by group {G}")
    n = N // G
    if G == 1:
        return stacked.reshape(1, N)
    out, _ = pl.pallas_call(
        _reduce_scatter_kernel,
        grid=(G - 1, G),
        out_shape=(jax.ShapeDtypeStruct((G, n), stacked.dtype),
                   jax.ShapeDtypeStruct((2, G, n), stacked.dtype)),
        interpret=_auto_interpret(interpret),
    )(stacked.reshape(G, G, n))
    return out


def ring_all_gather(strips: jax.Array, *,
                    interpret: Optional[bool] = None) -> jax.Array:
    """All-gather per-member ``(G, n)`` strips into ``(G, G * n)``: every
    row is the full buffer, strips concatenated in owner order (the §3.4
    part-broadcast)."""
    G, n = strips.shape
    if G == 1:
        return strips
    out, _ = pl.pallas_call(
        _all_gather_kernel,
        grid=(G - 1, G),
        out_shape=(jax.ShapeDtypeStruct((G, G, n), strips.dtype),
                   jax.ShapeDtypeStruct((2, G, n), strips.dtype)),
        interpret=_auto_interpret(interpret),
    )(strips)
    return out.reshape(G, G * n)


# ---------------------------------------------------------------------------
# the per-hop combine of the distributed ring (used inside shard_map)
# ---------------------------------------------------------------------------
#: the hop kernels work on lane-dense tiles: a chunk of n elements is laid
#: out as ``(R, LANES)`` rows, R a multiple of ``ROW_ALIGN`` (the int8
#: sublane tile, the strictest dtype on the wire) and split into equal
#: blocks of at most ``MAX_BLOCK_ROWS`` rows — the grid walks the blocks, so
#: the VMEM a hop uses is bounded by the block (512 KiB of f32), never by
#: the bucket size.
LANES = 128
ROW_ALIGN = 32
MAX_BLOCK_ROWS = 1024


def tile_rows(n: int) -> int:
    """Rows R of the ``(R, LANES)`` tile layout of an n-element chunk: the
    fewest blocks of at most ``MAX_BLOCK_ROWS`` rows, each rounded up to
    ``ROW_ALIGN`` — the pad stays under ``ROW_ALIGN`` rows per block."""
    rows = max(pl.cdiv(n, LANES), 1)
    nblocks = pl.cdiv(rows, MAX_BLOCK_ROWS)
    block = pl.cdiv(pl.cdiv(rows, nblocks), ROW_ALIGN) * ROW_ALIGN
    return nblocks * block


def to_tiles(x: jax.Array) -> jax.Array:
    """Zero-pad the last axis (n elements) to the tile layout: ``(..., n)``
    -> ``(..., tile_rows(n), LANES)``."""
    n = x.shape[-1]
    pad = tile_rows(n) * LANES - n
    x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x.reshape(x.shape[:-1] + (-1, LANES))


def from_tiles(t: jax.Array, n: int) -> jax.Array:
    """Inverse of :func:`to_tiles`: flatten the tiles and strip the pad."""
    return t.reshape(t.shape[:-2] + (-1,))[..., :n]


def _blocks(rows: int) -> tuple:
    """(block rows, number of blocks) of an R-row tile layout."""
    nb = pl.cdiv(rows, MAX_BLOCK_ROWS)
    br = rows // nb
    if rows % nb or br % ROW_ALIGN:
        raise ValueError(f"{rows} rows is not a tile layout (see tile_rows)")
    return br, nb


def _hop_accum_kernel(c_ref, chunk_ref, recv_ref, out_ref):
    # chunk_ref is block j of the chunk the index map selected with the
    # prefetched chunk index — the rest of the local buffer never moves
    del c_ref
    out_ref[...] = recv_ref[...] + chunk_ref[0].astype(recv_ref.dtype)


def ring_hop_accum(chunks: jax.Array, recv: jax.Array, c: jax.Array, *,
                   interpret: Optional[bool] = None) -> jax.Array:
    """One ring hop: add this member's local partial of chunk ``c`` (a
    traced index — it depends on ``lax.axis_index``) to the partial just
    received from the left neighbor.  ``chunks`` is ``(G, R, LANES)`` and
    ``recv`` and the result are ``(R, LANES)`` (:func:`to_tiles` layout);
    the sum is in ``recv``'s dtype.

    ``c`` rides in as a scalar-prefetch argument driving the chunks
    BlockSpec index map, so only blocks of the selected chunk are brought
    into VMEM — O(n) traffic per hop, not O(G*n)."""
    from jax.experimental.pallas import tpu as pltpu
    br, nb = _blocks(chunks.shape[1])
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, br, LANES), lambda j, c_ref: (c_ref[0], j, 0)),
                  pl.BlockSpec((br, LANES), lambda j, c_ref: (j, 0))],
        out_specs=pl.BlockSpec((br, LANES), lambda j, c_ref: (j, 0)),
    )
    return pl.pallas_call(
        _hop_accum_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(recv.shape, recv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_auto_interpret(interpret),
    )(jnp.asarray(c, jnp.int32).reshape(1), chunks, recv)


# ---------------------------------------------------------------------------
# compressed wire formats fused into the hop (CommConfig.wire_format)
# ---------------------------------------------------------------------------
def _int8_requant_kernel(c_ref, chunk_ref, *refs, has_msg: bool):
    """Grid ``(2, blocks)``.  Phase 0 folds ``max|acc|`` of every block into
    an SMEM scalar; phase 1 recomputes each block's ``acc`` and writes it
    quantized against ``scale = max|acc| * (1/127)`` (the scale is a property
    of the whole message, so the grid reads the inputs twice rather than
    holding the chunk in VMEM)."""
    del c_ref
    if has_msg:
        q_ref, s_ref, qout_ref, sout_ref, amax_ref = refs
    else:
        qout_ref, sout_ref, amax_ref = refs
    phase, j = pl.program_id(0), pl.program_id(1)
    acc = chunk_ref[0].astype(jnp.float32)
    if has_msg:
        acc = q_ref[...].astype(jnp.float32) * s_ref[0] + acc

    @pl.when(jnp.logical_and(phase == 0, j == 0))
    def _():
        amax_ref[0] = jnp.float32(0.0)

    @pl.when(phase == 0)
    def _():
        amax_ref[0] = jnp.maximum(amax_ref[0], jnp.max(jnp.abs(acc)))

    @pl.when(phase == 1)
    def _():
        s = amax_ref[0] * (1.0 / 127.0)
        s = jnp.where(s > 0, s, 1.0)   # all-zero message: keep dequant defined
        qout_ref[...] = jnp.round(acc / s).astype(jnp.int8)

        @pl.when(j == 0)
        def _():
            sout_ref[0] = s


def _int8_requant(chunks, c, q=None, scale=None, *, interpret=None):
    from jax.experimental.pallas import tpu as pltpu
    R = chunks.shape[1]
    br, nb = _blocks(R)
    has_msg = q is not None
    in_specs = [pl.BlockSpec((1, br, LANES),
                             lambda p, j, c_ref: (c_ref[0], j, 0))]
    args = [chunks]
    if has_msg:
        in_specs += [pl.BlockSpec((br, LANES), lambda p, j, c_ref: (j, 0)),
                     pl.BlockSpec(memory_space=pltpu.SMEM)]
        args += [q, scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(2, nb),
        in_specs=in_specs,
        # phase 0 parks the output on block 0 without writing it; phase 1
        # then visits every block once, so each is written back exactly once
        out_specs=[pl.BlockSpec((br, LANES), lambda p, j, c_ref: (j * p, 0)),
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        scratch_shapes=[pltpu.SMEM((1,), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_int8_requant_kernel, has_msg=has_msg),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((R, LANES), jnp.int8),
                   jax.ShapeDtypeStruct((1,), jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=_auto_interpret(interpret),
    )(jnp.asarray(c, jnp.int32).reshape(1), *args)


def int8_quantize(x: jax.Array, *,
                  interpret: Optional[bool] = None) -> tuple:
    """Quantize an ``(R, LANES)`` f32 message to ``(q int8 (R, LANES),
    scale f32 (1,))`` with a symmetric per-message max-abs scale
    (``kernels.ref.int8_quantize_ref`` is the oracle; zero pad quantizes to
    zero and leaves the scale alone).  Used for the FIRST send of the int8
    ring — every later hop re-quantizes inside ``ring_hop_int8``."""
    return _int8_requant(x[None], 0, interpret=interpret)


def ring_hop_int8(chunks: jax.Array, q: jax.Array, scale: jax.Array,
                  c: jax.Array, *,
                  interpret: Optional[bool] = None) -> tuple:
    """One int8 ring hop, fully fused: dequantize the received message
    ``(q, scale)``, add this member's local partial of chunk ``c`` in f32,
    re-quantize against a fresh max-abs scale.  Returns the next wire
    message ``(q' int8 (R, LANES), scale' f32 (1,))``.  Same tile layout and
    scalar-prefetch chunk selection as :func:`ring_hop_accum`."""
    return _int8_requant(chunks, c, q, scale, interpret=interpret)


def ring_hop_topk(chunks: jax.Array, vals: jax.Array, idx: jax.Array,
                  c: jax.Array, *,
                  interpret: Optional[bool] = None) -> jax.Array:
    """One top-k ring hop combine: scatter-add the received sparse message
    ``(vals, idx)`` (indices into the flattened tiles) into a dense f32
    ``(R, LANES)`` buffer and add this member's local partial of chunk
    ``c``.  The scatter is XLA's — a data-dependent scatter has no tiled
    Mosaic form — and the add is the :func:`ring_hop_accum` kernel.  The
    backend re-selects its top-k before forwarding (and keeps the dense
    result on the final hop, so the LAST combine loses nothing)."""
    R = chunks.shape[1]
    dense = jnp.zeros((R * LANES,), jnp.float32).at[idx].add(
        vals.astype(jnp.float32))
    return ring_hop_accum(chunks, dense.reshape(R, LANES), c,
                          interpret=interpret)
