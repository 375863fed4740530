"""Pluggable collective backends — the extension point behind the schedule
seam (``repro.comm.schedule``).

A backend is one implementation of the paper's group collectives
(part-reduce / part-broadcast / psum), called INSIDE ``jax.shard_map`` over
a mesh axis or axis tuple.  The schedules (``FlatSchedule`` /
``HierarchicalSchedule``) own everything else — bucket layout, wire-dtype
casts, the two-level pod composition — so a new backend only has to honor
the :class:`~repro.comm.backends.base.CollectiveBackend` contract:

**Strip ownership.**  ``part_reduce`` splits the buffer into G equal chunks
along ``dim`` and must deliver fully-reduced chunk i to the group member
whose flat index (``core.collectives.flat_group_index`` — row-major over
the axis tuple) is i.  ``part_broadcast`` is the exact inverse: chunks
reassembled in owner order.  This is the ``lax.psum_scatter(tiled=True)``
convention; the ZeRO-1 strip update slices params with the same index, so
a backend with a different owner mapping would silently corrupt training —
the equivalence tests (zero1 == serial per backend) pin it.

**Wire-dtype semantics.**  Backends are dtype-transparent: they reduce in
whatever dtype the schedule hands them (the "wire" arithmetic — a bf16
reduce accumulates in bf16 on the wire) and never cast.  The schedule
layer owns the fp32 accumulate after each stage and the always-fp32
cross-pod hop and weight broadcast.

**Compressed wire formats.**  ``CommConfig.wire_format in {"int8",
"topk"}`` is bound into a backend via its optional ``bind_wire_format``
method (``schedule.bind_wire_format`` probes with ``getattr`` — a backend
without it, e.g. gossip, only supports the dense formats and ``MODE_CAPS``
enforces that).  A compressed ``part_reduce`` takes f32 buffers and
returns f32 strips, owning quantize/dequantize internally: int8 moves
(int8 message, per-message f32 max-abs scale) pairs with f32 accumulation
per hop; topk moves (values, int32 indices) with per-hop re-selection.
``LaxBackend`` runs these as an explicit jnp ppermute ring (the
``kernels.ref`` oracle math — the fallback reference), ``PallasRingBackend``
fuses the combine into ``kernels/ring.py`` hop kernels.

**Shapes.**  The schedules pass 1-D fusion buffers whose size is a
multiple of the group (``bucketer`` pads every bucket), and row-blocked
``(rows, cols)`` buffers split along dim 0 only to a backend whose
``takes_rows`` attribute is true (``LaxBackend`` on a dense wire); the
others get such a buffer flattened.  A backend may reject anything else
with ``NotImplementedError`` (``PallasRingBackend`` and ``GossipBackend``
do).

Selection is by name end-to-end: ``CommConfig(backend=...)`` →
``make_schedule`` → here.  ``HierarchicalSchedule`` takes one backend per
level, so e.g. the Pallas ring can run in-pod while the cross-pod hop
stays on lax (the default pairing).  Adding a backend — host NCCL/Gloo,
compressed wire formats — means one module here, a ``COLLECTIVE_BACKENDS``
entry, and per-backend constants in ``core.balance.RING_BACKEND_MODELS``;
every schedule, update builder, overlap hook, launcher flag and benchmark
picks it up.

Backends:

``lax`` (:class:`LaxBackend`, the default)
    ``jax.lax`` collectives — XLA's own ring/tree selection.  Bit-for-bit
    the seed behavior; ``core.collectives`` is its internals.
``pallas-ring`` (:class:`PallasRingBackend`)
    The paper's §3.4 ring explicitly: ``lax.ppermute`` neighbor exchange
    with the per-hop combine in a Pallas kernel (``kernels/ring.py``, whose
    stacked form is oracle-validated in interpret mode).
``gossip`` (:class:`GossipBackend`)
    GossipGraD partner exchange: one chunk-sized ``lax.ppermute`` message
    per step under the rotating pairing ``partner = (rank + step + 1) %
    world_size`` instead of the full ring reduction.  NOT a drop-in ring
    replacement — ``part_reduce`` delivers the rotating PAIR mean, a
    deliberate consistency-model change selected by ``parallel="gossip"``
    (``api.spec.MODE_CAPS`` rejects it under the synchronous modes).  Its
    partner rotation is step-scheduled: bind the train step with
    ``bind_step`` / ``schedule.bind_step``.
"""
from __future__ import annotations

from typing import Union

from repro.comm.backends.base import CollectiveBackend  # noqa: F401
from repro.comm.backends.gossip import GossipBackend
from repro.comm.backends.lax_backend import LaxBackend
from repro.comm.backends.pallas_ring import PallasRingBackend

COLLECTIVE_BACKENDS = ("lax", "pallas-ring", "gossip")

_FACTORIES = {"lax": LaxBackend, "pallas-ring": PallasRingBackend,
              "gossip": GossipBackend}


def get_backend(backend: Union[str, CollectiveBackend]) -> CollectiveBackend:
    """Resolve a backend name to an instance; instances pass through (so
    callers can hand in a pre-configured or third-party backend)."""
    if isinstance(backend, str):
        try:
            return _FACTORIES[backend]()
        except KeyError:
            raise ValueError(
                f"unknown collective backend {backend!r}; "
                f"known: {COLLECTIVE_BACKENDS}") from None
    return backend
