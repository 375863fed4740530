"""The reference backend: ``jax.lax`` collectives (XLA picks the wire
algorithm).  Reproduces the seed behavior bit-for-bit — it IS the seed
path, with ``core.collectives`` as its internals.

The compressed wire formats (``wire_format in {"int8", "topk"}``, bound by
the schedule layer via ``bind_wire_format``) have no dense ``psum_scatter``
equivalent, so for them this backend runs the SAME ring schedule as
``PallasRingBackend`` but with the per-hop combine as plain jnp — literally
the ``kernels.ref`` oracles — making it the jnp fallback path the Pallas
ring is equivalence-tested against.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.collectives import (
    AxisNames,
    axis_size,
    flat_group_index,
    part_broadcast,
    part_reduce,
)


@dataclass(frozen=True)
class LaxBackend:
    """``lax.psum_scatter`` / ``lax.all_gather`` / ``lax.psum`` — on TPU,
    XLA lowers these to the same bidirectional ICI ring the §3.4 cost model
    assumes (``core.balance.ring_collective_time(backend="lax")``)."""
    name: str = "lax"
    wire_format: str = "fp32"
    topk_ratio: float = 0.05

    @property
    def takes_rows(self) -> bool:
        """The dense wires reduce and gather along dim 0 of a buffer of
        any rank, so a row-blocked buffer needs no flattening; the
        compressed ring runs on the 1-D form."""
        return self.wire_format in ("fp32", "bf16")

    def bind_wire_format(self, wire_format: str,
                         topk_ratio: float) -> "LaxBackend":
        return dataclasses.replace(self, wire_format=wire_format,
                                   topk_ratio=topk_ratio)

    def part_reduce(self, x: jax.Array, axis_name: AxisNames,
                    dim: int = 0) -> jax.Array:
        if self.wire_format in ("int8", "topk"):
            return self._compressed_part_reduce(x, axis_name, dim)
        return part_reduce(x, axis_name, dim)

    def part_broadcast(self, x: jax.Array, axis_name: AxisNames,
                       dim: int = 0) -> jax.Array:
        return part_broadcast(x, axis_name, dim)

    def psum(self, x: jax.Array, axis_name: AxisNames) -> jax.Array:
        return lax.psum(x, axis_name)

    def _compressed_part_reduce(self, x: jax.Array, axis_name: AxisNames,
                                dim: int) -> jax.Array:
        """The §3.4 ring schedule with compressed wire messages, hop math
        straight from the ``kernels.ref`` oracles (jnp, no Pallas)."""
        from repro.comm.backends.pallas_ring import topk_chunk_k
        from repro.kernels import ref as kref

        if dim != 0 or x.ndim != 1:
            raise NotImplementedError(
                "compressed wire formats operate on the schedules' "
                f"canonical 1-D fusion-buffer form (dim=0); got dim={dim}, "
                f"shape={x.shape}")
        G = axis_size(axis_name)
        if G == 1:
            return x
        if x.size % G:
            raise ValueError(
                f"buffer size {x.size} not a strip multiple of group {G}")
        p = flat_group_index(axis_name)
        chunks = x.reshape(G, x.size // G).astype(jnp.float32)
        perm = [(i, (i + 1) % G) for i in range(G)]
        if self.wire_format == "int8":
            q, s = kref.int8_quantize_ref(chunks[jnp.mod(p - 1, G)])
            for step in range(G - 1):
                qr = lax.ppermute(q, axis_name, perm=perm)
                sr = lax.ppermute(s, axis_name, perm=perm)
                c = jnp.mod(p - 2 - step, G)
                q, s = kref.ring_hop_int8_ref(chunks, qr, sr, c)
            return kref.int8_dequantize_ref(q, s)
        n = chunks.shape[1]
        k = topk_chunk_k(n, self.topk_ratio)
        vals, idx = kref.topk_select_ref(chunks[jnp.mod(p - 1, G)], k)
        dense = chunks[jnp.mod(p - 1, G)]
        for step in range(G - 1):
            vr = lax.ppermute(vals, axis_name, perm=perm)
            ir = lax.ppermute(idx, axis_name, perm=perm)
            c = jnp.mod(p - 2 - step, G)
            dense = kref.ring_hop_topk_ref(chunks, vr, ir, c)
            if step < G - 2:
                vals, idx = kref.topk_select_ref(dense, k)
        return dense
