"""Collective schedules for the bucketed gradient reduction (paper §3.4).

Both schedules implement the same contract, to be called INSIDE
``jax.shard_map``: ``reduce`` turns a replicated-shape fusion buffer of
partial sums into this member's strip (sum over the group, fp32 out) — a
1-D chunk, or a row block of a row-blocked ``(rows, cols)`` buffer
(``comm.bucketer.row_blocks``),
``broadcast`` is its exact inverse on updated strips, and ``owner_index`` is
the flat strip index the member owns — ``reduce`` scatters strip ``i`` to
the member whose ``owner_index() == i``, and params must be sliced with the
same index for the ZeRO-1 strip update to line up.

The schedules are also the BACKEND seam: the actual wire collectives go
through a :class:`~repro.comm.backends.CollectiveBackend` (``lax`` — the
seed behavior — or ``pallas-ring``, the paper's explicit ring; see
``repro.comm.backends``).  Schedules own bucket layout, wire-dtype casts
and level composition; backends own the group collectives, so swapping one
never touches the optimizer rewiring.

FlatSchedule
    One ring over the (possibly composed) group: backend part-reduce /
    part-broadcast over the axis tuple, exactly the seed per-tensor path
    but per bucket.  Wire dtype applies to the single reduce stage.

HierarchicalSchedule (paper §3.3/§3.4 group composition)
    For ``axes == (outer, inner)`` — canonically ``("pod", "data")``: the
    in-pod reduce-scatter runs over ``inner`` first (wire dtype, ring of
    G_in members, full bucket bytes), then the cross-pod hop reduce-scatters
    the 1/G_in strips over ``outer`` in fp32 (fp32 accumulate across pods,
    strip bytes only on the slow link).  Member ``(p, d)`` owns flat strip
    ``d * G_out + p``; ``broadcast`` inverts with all-gathers in the
    opposite order.  Each level takes its own backend — the intended
    pairing is the Pallas ring in-pod (fast uniform links) with lax on the
    cross-pod hop.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from repro.comm.backends import CollectiveBackend, LaxBackend, get_backend
from repro.core.collectives import AxisNames, axis_size, flat_group_index


def _by_rows(backend: CollectiveBackend, op, x: jax.Array,
             axes: AxisNames) -> jax.Array:
    """Backend collective ``op`` along dim 0 of ``x``.  A backend whose
    ``takes_rows`` is false (the ring kernels, compressed wires, gossip)
    gets a row-blocked buffer flattened — chunk i of the flat buffer is
    row block i, so it moves exactly the 1-D buffer's bytes — and the
    result is viewed back as rows of ``x``'s width."""
    if x.ndim == 1 or getattr(backend, "takes_rows", False):
        return op(x, axes, dim=0)
    return op(x.reshape(-1), axes, dim=0).reshape(-1, *x.shape[1:])


def group_axes(mesh: Mesh, data_axes) -> Tuple[Tuple[str, ...], AxisNames, int]:
    """(axes, axis_arg, G) for the data-parallel group actually present on
    ``mesh``: requested axes filtered to the mesh, the single-name-or-tuple
    form the collectives take, and the group size.  The one derivation every
    consumer of a schedule (update builders, the overlapped train step) must
    agree on."""
    axes = tuple(a for a in data_axes if a in mesh.axis_names)
    axis_arg = axes if len(axes) > 1 else axes[0]
    G = 1
    for a in axes:
        G *= mesh.shape[a]
    return axes, axis_arg, G


@dataclass(frozen=True)
class FlatSchedule:
    """Single-level ring over all data axes at once."""
    axes: AxisNames
    backend: CollectiveBackend = field(default_factory=LaxBackend)

    def group_size(self) -> int:
        return axis_size(self.axes)

    def owner_index(self) -> jax.Array:
        return flat_group_index(self.axes)

    def reduce(self, buf: jax.Array, wire_dtype=jnp.float32) -> jax.Array:
        strip = _by_rows(self.backend, self.backend.part_reduce,
                         buf.astype(wire_dtype), self.axes)
        return strip.astype(jnp.float32)

    def broadcast(self, strip: jax.Array) -> jax.Array:
        return _by_rows(self.backend, self.backend.part_broadcast, strip,
                        self.axes)


@dataclass(frozen=True)
class HierarchicalSchedule:
    """Two-level in-pod (``inner``) + cross-pod (``outer``) schedule, with
    a backend per level."""
    outer: str
    inner: str
    inner_backend: CollectiveBackend = field(default_factory=LaxBackend)
    outer_backend: CollectiveBackend = field(default_factory=LaxBackend)

    def group_size(self) -> int:
        return lax.axis_size(self.outer) * lax.axis_size(self.inner)

    def owner_index(self) -> jax.Array:
        # stage 1 scatters chunk d to inner member d; stage 2 scatters
        # sub-chunk p of chunk d to outer member p -> flat strip d*G_out + p
        return (lax.axis_index(self.inner) * lax.axis_size(self.outer)
                + lax.axis_index(self.outer))

    def reduce(self, buf: jax.Array, wire_dtype=jnp.float32) -> jax.Array:
        # a row-blocked buffer: row block d of the in-pod level splits
        # into the cross-pod level's blocks, so block d*G_out + p lands
        # on member (p, d), as the flat form's chunks do
        in_pod = _by_rows(self.inner_backend, self.inner_backend.part_reduce,
                          buf.astype(wire_dtype), self.inner)
        # cross-pod hop: strip bytes only, always fp32 accumulate
        return _by_rows(self.outer_backend, self.outer_backend.part_reduce,
                        in_pod.astype(jnp.float32), self.outer)

    def broadcast(self, strip: jax.Array) -> jax.Array:
        in_pod = _by_rows(self.outer_backend,
                          self.outer_backend.part_broadcast, strip,
                          self.outer)
        return _by_rows(self.inner_backend, self.inner_backend.part_broadcast,
                        in_pod, self.inner)


Schedule = Union[FlatSchedule, HierarchicalSchedule]


def bind_step(backend: CollectiveBackend, step) -> CollectiveBackend:
    """Bind the train-step index into a STEP-SCHEDULED backend (the gossip
    partner rotation).  Step-free backends (lax, pallas-ring) have no
    ``bind_step`` method and pass through untouched, so the update
    builders can bind unconditionally — ``step`` may be a traced scalar."""
    binder = getattr(backend, "bind_step", None)
    return backend if binder is None else binder(step)


def bind_wire_format(backend: CollectiveBackend, wire_format: Optional[str],
                     topk_ratio: float = 0.05) -> CollectiveBackend:
    """Bind a compressed wire format (``CommConfig.wire_format``) into a
    backend that supports one.  Same getattr convention as
    :func:`bind_step`: backends without ``bind_wire_format`` (gossip) pass
    through — ``MODE_CAPS`` already restricts which formats reach them.
    ``None`` and the dense formats bind too (a no-op for fp32/bf16 — the
    dense dtype ride stays with the schedule's wire-dtype cast)."""
    if wire_format is None:
        return backend
    binder = getattr(backend, "bind_wire_format", None)
    return backend if binder is None else binder(wire_format, topk_ratio)


def reduce_mean(sched: Schedule, buf: jax.Array, wire_dtype,
                G: int) -> jax.Array:
    """THE reduce phase for one fusion buffer: wire-dtype part-reduce
    through the schedule, mean in fp32.  The single definition shared by
    the monolithic pipeline (``optim.dist.UpdatePlan.reduce``) and the
    §3.1 backward-pass hooks (``comm.overlap``) — the two issue points can
    never disagree on the math."""
    return sched.reduce(buf, wire_dtype) / G


def make_schedule(axes: Union[str, Tuple[str, ...]],
                  hierarchical: bool = False,
                  backend: Union[str, CollectiveBackend] = "lax",
                  cross_backend: Union[str, CollectiveBackend, None] = None,
                  step=None, wire_format: Optional[str] = None,
                  topk_ratio: float = 0.05) -> Schedule:
    """Pick the schedule for ``axes`` and bind its backend(s).

    The hierarchical form needs exactly two axes ``(outer, inner)``; one
    axis degrades to the flat ring (a one-axis "hierarchy" IS the flat
    ring), and more than two is a config error — there is no defined
    composition order, so it raises instead of silently going flat.

    ``backend`` drives the flat ring, or the IN-POD level of the
    hierarchical schedule.  ``cross_backend`` sets the cross-pod hop and
    defaults to ``"lax"``: the hop crosses the slow inter-pod link where
    XLA's collective is the right tool (and an in-kernel ring buys
    nothing), which is the mixed pairing the backends package documents.

    ``step`` (may be traced) is bound into step-scheduled backends via
    :func:`bind_step` — the gossip partner rotation advances with it;
    step-free backends ignore it.

    ``wire_format`` binds a compressed encoding (:func:`bind_wire_format`)
    into BOTH levels: in-pod hops move compressed messages, and because the
    hierarchical reduce casts the in-pod strips back to f32 before the
    cross-pod hop, the bound outer backend's ``part_reduce`` re-encodes
    exactly once there — the cross-pod hop is the natural re-quantization
    point (compressed in-pod, one fresh quantization across pods).
    """
    def resolve(b):
        b = get_backend(b)
        b = b if step is None else bind_step(b, step)
        return bind_wire_format(b, wire_format, topk_ratio)

    if hierarchical and not isinstance(axes, str) and len(axes) > 2:
        raise ValueError(
            "hierarchical schedule composes exactly two axes "
            f"(outer, inner); got {len(axes)}: {axes}. Fold the extra axes "
            "into the mesh topology (e.g. one 'pod' x one 'data' axis) or "
            "use hierarchical=False for a single flat ring.")
    if hierarchical and not isinstance(axes, str) and len(axes) == 2:
        return HierarchicalSchedule(
            outer=axes[0], inner=axes[1],
            inner_backend=resolve(backend),
            outer_backend=resolve(
                "lax" if cross_backend is None else cross_backend))
    return FlatSchedule(axes=axes, backend=resolve(backend))
