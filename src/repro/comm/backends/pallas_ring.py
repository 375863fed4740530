"""The paper's §3.4 ring, explicitly: neighbor exchange via ``lax.ppermute``
(one hop to the right per step — XLA's ICI neighbor DMA on TPU) with the
per-hop chunk combine in a Pallas kernel (``kernels.ring.ring_hop_accum``).

Schedule (identical to the stacked ``kernels.ring`` kernels, whose
interpret-mode validation pins it against the jnp oracles):

    reduce-scatter   member p sends its local chunk (p-1)%G first; at step
                     s it receives the partial of chunk (p-2-s)%G, adds its
                     own contribution (the Pallas hop kernel) and forwards.
                     After G-1 hops the fully-reduced chunk p sits on
                     member p — the ``lax.psum_scatter(tiled=True)`` owner
                     convention, so this backend and ``LaxBackend`` are
                     drop-in interchangeable.
    all-gather       member p's strip travels the ring; at step s the strip
                     of owner (p-1-s)%G arrives and is placed (pure data
                     movement — no kernel needed).

Costs 2*(G-1) messages of ``size/G`` like the lax ring, but with the hop
pipeline under kernel control: ``core.balance.RING_BACKEND_MODELS`` carries
this backend's latency/bandwidth constants (lower per-message dispatch
latency, a small per-hop rotation bubble) for the predicted-vs-measured
rows of ``benchmarks/comm_bucket_sweep.py``.

Operates on the schedules' canonical 1-D fusion buffers (``dim == 0``);
buffer sizes are strip multiples by construction (``repro.comm.bucketer``
pads every bucket to the group size).  On CPU the hop kernel runs in
interpret mode (auto-detected), which is what the equivalence tests
exercise; on TPU (``interpret=False``) the hop kernels compile for Mosaic.
Each bucket's ``(G, padded_size/G)`` chunks are zero-padded once to the
kernels' ``(R, 128)`` tile layout (``kernels.ring.to_tiles``) and the pad
is stripped from the owned strip, so strip sizes are unchanged
(``tests/test_tpu_compile.py`` compiles the 4-device step for a v5e).

**Compressed wire formats** (``wire_format``, bound by the schedule layer
via ``bind_wire_format``): ``"int8"`` replaces the hop combine with
``kernels.ring.ring_hop_int8`` — the ppermute moves (int8 message, f32
scale) instead of a dense f32 chunk, each hop dequantizes + accumulates in
f32 + re-quantizes fresh inside the kernel; ``"topk"`` moves (values,
indices) messages, the hop scatter-adds them dense
(``kernels.ring.ring_hop_topk``) and re-selects top-k before forwarding
(the final hop keeps the dense accumulator).  The part-broadcast of
updated weights is NEVER compressed — lossy weights would break the
replicated-params invariant the §3.4 update relies on.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.collectives import AxisNames, axis_size, flat_group_index, flatten_pad, unflatten
from repro.kernels.ring import (
    from_tiles,
    int8_quantize,
    ring_hop_accum,
    ring_hop_int8,
    ring_hop_topk,
    to_tiles,
)


def _ring_perm(G: int) -> List[Tuple[int, int]]:
    return [(i, (i + 1) % G) for i in range(G)]


def topk_chunk_k(n: int, ratio: float, floor: int = 1) -> int:
    """Entries kept per ``n``-element wire message at ``ratio`` (>= floor,
    <= n; the n cap wins — ``lax.top_k`` rejects k > n) — shared by both
    ring backends so their wire layouts agree."""
    return min(n, max(floor, math.ceil(ratio * n)))


def _topk_select(x: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """(values, int32 indices) of the k largest-|x| entries (jnp: selection
    is not a memory-bound combine, so it stays outside the Pallas hop)."""
    _, idx = lax.top_k(jnp.abs(x), k)
    return x[idx], idx.astype(jnp.int32)


@dataclass(frozen=True)
class PallasRingBackend:
    """``interpret=None`` auto-selects Pallas interpret mode off-TPU."""
    name: str = "pallas-ring"
    interpret: Optional[bool] = None
    wire_format: str = "fp32"
    topk_ratio: float = 0.05

    def bind_wire_format(self, wire_format: str,
                         topk_ratio: float) -> "PallasRingBackend":
        import dataclasses
        return dataclasses.replace(self, wire_format=wire_format,
                                   topk_ratio=topk_ratio)

    def _check(self, x: jax.Array, dim: int) -> None:
        if dim != 0 or x.ndim != 1:
            raise NotImplementedError(
                "PallasRingBackend implements the schedules' canonical 1-D "
                f"fusion-buffer form (dim=0); got dim={dim}, "
                f"shape={x.shape}. Flatten first (see collectives."
                "flatten_pad) or use LaxBackend.")

    def part_reduce(self, x: jax.Array, axis_name: AxisNames,
                    dim: int = 0) -> jax.Array:
        self._check(x, dim)
        G = axis_size(axis_name)
        if G == 1:
            return x
        if x.size % G:
            raise ValueError(
                f"buffer size {x.size} not a strip multiple of group {G}")
        p = flat_group_index(axis_name)
        n = x.size // G
        # one pad per bucket, to the hop kernels' lane-dense tile layout;
        # the pad is stripped from the owned strip, so strip sizes (and the
        # bucket layout every checkpoint records) never see it
        chunks = to_tiles(x.reshape(G, n))
        perm = _ring_perm(G)
        if self.wire_format == "int8":
            return self._part_reduce_int8(chunks, n, axis_name, p, perm)
        if self.wire_format == "topk":
            return self._part_reduce_topk(chunks, n, axis_name, p, perm)
        send = chunks[jnp.mod(p - 1, G)]
        for s in range(G - 1):
            recv = lax.ppermute(send, axis_name, perm=perm)
            c = jnp.mod(p - 2 - s, G)
            send = ring_hop_accum(chunks, recv, c, interpret=self.interpret)
        return from_tiles(send, n)

    def _part_reduce_int8(self, chunks, n, axis_name, p, perm) -> jax.Array:
        """The same ring with (int8, scale) wire messages; every combine is
        the fused dequantize-accumulate-requantize hop kernel."""
        G = chunks.shape[0]
        chunks = chunks.astype(jnp.float32)
        q, s = int8_quantize(chunks[jnp.mod(p - 1, G)],
                             interpret=self.interpret)
        for step in range(G - 1):
            qr = lax.ppermute(q, axis_name, perm=perm)
            sr = lax.ppermute(s, axis_name, perm=perm)
            c = jnp.mod(p - 2 - step, G)
            q, s = ring_hop_int8(chunks, qr, sr, c, interpret=self.interpret)
        # the owned strip leaves the wire once, at the very end
        return from_tiles(q, n).astype(jnp.float32) * s[0]

    def _part_reduce_topk(self, chunks, n, axis_name, p, perm) -> jax.Array:
        """The same ring with (values, indices) sparse messages; the hop
        scatter-adds them dense, re-selection precedes each forward (never
        the final hop — the owned strip keeps the dense sum).  Selection
        runs on the unpadded chunk, so k and the indices are the lax
        backend's."""
        G = chunks.shape[0]
        chunks = chunks.astype(jnp.float32)
        k = topk_chunk_k(n, self.topk_ratio)
        dense = chunks[jnp.mod(p - 1, G)]
        vals, idx = _topk_select(from_tiles(dense, n), k)
        for step in range(G - 1):
            vr = lax.ppermute(vals, axis_name, perm=perm)
            ir = lax.ppermute(idx, axis_name, perm=perm)
            c = jnp.mod(p - 2 - step, G)
            dense = ring_hop_topk(chunks, vr, ir, c,
                                  interpret=self.interpret)
            if step < G - 2:
                vals, idx = _topk_select(from_tiles(dense, n), k)
        return from_tiles(dense, n)

    def part_broadcast(self, x: jax.Array, axis_name: AxisNames,
                       dim: int = 0) -> jax.Array:
        self._check(x, dim)
        G = axis_size(axis_name)
        if G == 1:
            return x
        p = flat_group_index(axis_name)
        perm = _ring_perm(G)
        out = jnp.zeros((G, x.size), x.dtype).at[p].set(x)
        send = x
        for s in range(G - 1):
            recv = lax.ppermute(send, axis_name, perm=perm)
            out = out.at[jnp.mod(p - 1 - s, G)].set(recv)
            send = recv
        return out.reshape(G * x.size)

    def psum(self, x: jax.Array, axis_name: AxisNames) -> jax.Array:
        G = axis_size(axis_name)
        if G == 1:
            return x
        flat = flatten_pad(x, G)
        strips = self.part_reduce(flat, axis_name)
        return unflatten(self.part_broadcast(strips, axis_name), x.shape)
