"""Gradient bucketing: coalesce a tensor tree into fixed-byte fusion buffers.

The plan is computed host-side from static leaf shapes (greedy first-fit in
tree order, like PyTorch DDP's gradient buckets), so every offset below is a
Python int and ``pack``/``unpack`` trace to pure reshape/concat/slice ops —
no dynamic shapes inside jit.  Each bucket is padded to a multiple of the
group size ``G`` so that one ``part_reduce``/``part_broadcast`` pair moves
the whole bucket and every member owns an equal 1-D strip of it (the paper's
§3.4 strip scheme, applied per bucket instead of per tensor).

A bucket that holds one leaf of two or more dimensions, whose rows split
into ``G`` blocks of whole sublane tiles (:func:`row_blocks`), keeps the
leaf's 2-D ``(rows, cols)`` view as its buffer instead: member ``i``'s strip
is row block ``i``, which holds exactly the elements of the 1-D strip ``i``
in the same order.  A TPU lays a 2-D array out in ``(8, 128)`` tiles (for
4-byte elements) and a 1-D one in ``(1024,)`` tiles, so flattening a weight
is a physical copy there, and viewing it as ``(rows, cols)`` is not.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.collectives import padded_size

#: supported gradient wire formats (``CommConfig.wire_format``): how the
#: part-reduce encodes bytes on the wire.  ``fp32``/``bf16`` are the dense
#: dtypes the schedule always supported; ``int8`` quantizes each message
#: against a per-message max-abs scale (fp32 accumulate per hop, so error
#: does not compound across the G-1 hops); ``topk`` sends (values, indices)
#: of the largest-|g| entries with a local error-feedback residual carried
#: in strip state (``optim.dist.make_topk_ef_update``).
WIRE_FORMATS = ("fp32", "bf16", "int8", "topk")

#: wire_format implied by each reduce_dtype when ``wire_format`` is unset
_DTYPE_FORMATS = {"float32": "fp32", "bfloat16": "bf16"}


@dataclass(frozen=True)
class CommConfig:
    """Knobs of the gradient-communication subsystem (see package docstring
    for the paper-section mapping).

    bucket_bytes:  target fusion-buffer size.  ``<= 0`` disables fusion
                   (one bucket per tensor — the legacy per-tensor schedule).
                   A single tensor larger than ``bucket_bytes`` gets a
                   bucket of its own (buckets never split a tensor).
    reduce_dtype:  wire dtype of the gradient part-reduce, ``"float32"`` or
                   ``"bfloat16"``.  fp32 accumulate after every stage.
    hierarchical:  use the two-level in-pod + cross-pod schedule when the
                   data axes are a 2-tuple like ``("pod", "data")``.
    overlap:       issue each bucket's part-reduce inside the BACKWARD pass,
                   the moment the bucket's last contributing leaf gradient
                   materializes (the paper's §3.1 bubble schedule), instead
                   of reducing the whole tree after ``value_and_grad``
                   returns.  See :mod:`repro.comm.overlap`.
    backend:       collective backend name (``repro.comm.backends``):
                   ``"lax"`` (XLA collectives — the seed behavior) or
                   ``"pallas-ring"`` (the paper's explicit §3.4 ring with
                   the per-hop combine in a Pallas kernel).  Under the
                   hierarchical schedule this drives the IN-POD level.
    cross_backend: collective backend for the CROSS-POD hop of the
                   hierarchical schedule (ignored by the flat one).
                   Defaults to ``"lax"`` — on a real cluster the pod axis
                   is the process boundary (``launch.mesh.make_cluster_mesh``)
                   and lax lowers to the runtime's cross-host collectives
                   (gloo on CPU), which is the backend slot the multi-host
                   subsystem fills.
    wire_format:   bytes-on-wire encoding of the gradient part-reduce, one
                   of :data:`WIRE_FORMATS`.  ``None`` (the default) derives
                   it from ``reduce_dtype`` (``float32 -> "fp32"``,
                   ``bfloat16 -> "bf16"``) so existing configs keep their
                   meaning.  ``"int8"``/``"topk"`` compress the reduce side
                   only — the part-broadcast of updated weights is always
                   full precision.
    topk_ratio:    fraction of bucket elements kept per message when
                   ``wire_format == "topk"`` (0 < ratio <= 1).
    """
    bucket_bytes: int = 4 * 2**20
    reduce_dtype: str = "float32"
    hierarchical: bool = False
    overlap: bool = False
    backend: str = "lax"
    cross_backend: str = "lax"
    wire_format: Optional[str] = None
    topk_ratio: float = 0.05

    def __post_init__(self):
        # real exceptions, not asserts: config validation must survive -O
        if self.reduce_dtype not in _DTYPE_FORMATS:
            raise ValueError(
                f"reduce_dtype must be one of "
                f"{tuple(sorted(_DTYPE_FORMATS))}, got {self.reduce_dtype!r}")
        if self.wire_format is None:
            object.__setattr__(
                self, "wire_format", _DTYPE_FORMATS[self.reduce_dtype])
        if self.wire_format not in WIRE_FORMATS:
            raise ValueError(
                f"wire_format must be one of {WIRE_FORMATS}, "
                f"got {self.wire_format!r}")
        if (self.reduce_dtype == "bfloat16"
                and self.wire_format != "bf16"):
            raise ValueError(
                f"reduce_dtype='bfloat16' implies wire_format='bf16'; "
                f"got conflicting wire_format={self.wire_format!r}")
        if not (0.0 < self.topk_ratio <= 1.0):
            raise ValueError(
                f"topk_ratio must be in (0, 1], got {self.topk_ratio!r}")
        from repro.comm.backends import COLLECTIVE_BACKENDS
        for fld in ("backend", "cross_backend"):
            if getattr(self, fld) not in COLLECTIVE_BACKENDS:
                raise ValueError(
                    f"{fld} must be one of {COLLECTIVE_BACKENDS}, "
                    f"got {getattr(self, fld)!r}")

    @property
    def wire_dtype(self):
        """Dense dtype buffers are cast to before ``part_reduce`` — the
        compressed formats quantize from fp32 inside the backend, so only
        ``bf16`` changes the handed-off dtype."""
        return jnp.bfloat16 if self.wire_format == "bf16" else jnp.float32

    @property
    def compressed(self) -> bool:
        """Whether the reduce wire uses a non-dense encoding."""
        return self.wire_format in ("int8", "topk")


@dataclass(frozen=True)
class LeafSlot:
    """Where one tree leaf lives inside its bucket's packed buffer."""
    index: int                 # leaf position in the flattened tree
    shape: Tuple[int, ...]
    size: int                  # number of elements (== prod(shape))
    offset: int                # element offset inside the bucket buffer
    dtype: Optional[str] = None  # leaf dtype name; None = unknown (shape-
                                 # only planning, e.g. the sweep benchmark)


#: rows of one sublane tile by element bytes: the TPU's tiled 2-D layout
SUBLANE_ROWS = {4: 8, 2: 16, 1: 32}


def row_blocks(shape: Sequence[int], itemsize: int,
               group: int) -> Optional[Tuple[int, int]]:
    """``(rows, cols)`` of a leaf's 2-D view when its rows
    (``prod(shape[:-1])``) split into ``group`` blocks of whole sublane
    tiles, so each member's strip is a tile-aligned row block; else None
    (1-D leaves, and rows that do not split)."""
    tile = SUBLANE_ROWS.get(itemsize)
    if len(shape) < 2 or tile is None:
        return None
    rows = math.prod(shape[:-1])
    if rows % group or (rows // group) % tile:
        return None
    return rows, shape[-1]


@dataclass(frozen=True)
class Bucket:
    slots: Tuple[LeafSlot, ...]
    size: int                  # payload elements (sum of slot sizes)
    shape: Tuple[int, ...]     # the fusion buffer's: (size rounded up to a
                               # multiple of the group,), or a row-blocked
                               # leaf's (rows, cols)

    @property
    def padded_size(self) -> int:
        return math.prod(self.shape)

    @property
    def row_blocked(self) -> bool:
        """Whether the buffer is one leaf's 2-D view, split by rows."""
        return len(self.shape) == 2

    def strip_shape(self, group: int) -> Tuple[int, ...]:
        """One member's strip: ``(padded_size / G,)`` or ``(rows / G,
        cols)``."""
        return (self.shape[0] // group,) + self.shape[1:]

    @property
    def trigger_index(self) -> int:
        """The leaf (flat tree index) whose gradient completes this bucket.
        Backprop materializes leaf gradients in REVERSE tree order (the last
        layer's weight gradient first), so the bucket becomes reducible when
        its EARLIEST tree-order leaf — the latest in backprop — arrives."""
        return min(s.index for s in self.slots)


@dataclass(frozen=True)
class BucketPlan:
    buckets: Tuple[Bucket, ...]
    group: int                 # G: members of the part-reduce group
    n_leaves: int

    @property
    def n_collectives(self) -> int:
        """Collective pairs per step — the quantity bucketing shrinks from
        O(#tensors) to O(total_bytes / bucket_bytes)."""
        return len(self.buckets)

    @property
    def backprop_order(self) -> Tuple[int, ...]:
        """Bucket indices in backprop readiness order — the order the §3.1
        overlap schedule issues the part-reduces.  Descending trigger leaf:
        the bucket holding the LAST tree-order (= first materialized) leaves
        is ready first.  Ties (one leaf feeding two per-tensor buckets can't
        happen, but equal triggers under a custom leaf order can) break
        toward the later bucket — the one ordering rule, defined in
        ``core.balance.issue_order``."""
        from repro.core.balance import issue_order
        return issue_order(tuple(b.trigger_index for b in self.buckets))

    @property
    def total_elements(self) -> int:
        return sum(b.size for b in self.buckets)

    @property
    def total_padded(self) -> int:
        return sum(b.padded_size for b in self.buckets)

    @property
    def row_block_share(self) -> float:
        """Share of the payload elements in row-blocked buckets."""
        total = self.total_elements
        return (sum(b.size for b in self.buckets if b.row_blocked) / total
                if total else 0.0)


def _bucket(slots: List[LeafSlot], size: int, group: int,
            itemsize: int) -> Bucket:
    """A bucket of ``slots``: row-blocked when it holds one leaf that
    :func:`row_blocks` splits, else a padded 1-D buffer."""
    if len(slots) == 1:
        s = slots[0]
        isz = itemsize if s.dtype is None else np.dtype(s.dtype).itemsize
        rc = row_blocks(s.shape, isz, group)
        if rc is not None:
            return Bucket(tuple(slots), size, rc)
    return Bucket(tuple(slots), size, (padded_size(size, group),))


def plan_buckets(tree: Any, group: int, bucket_bytes: int,
                 itemsize: int = 4) -> BucketPlan:
    """Greedy first-fit bucket assignment over ``tree``'s leaves in tree
    order.  Shapes only — no array data is touched.  Buckets never mix
    dtypes (concatenating mixed leaves would silently promote them), so a
    dtype change in tree order also closes the current bucket; ``itemsize``
    is only the fallback for shape-only leaves with no ``.dtype``."""
    leaves = jax.tree.leaves(tree)
    cap = math.inf if bucket_bytes is None else bucket_bytes
    buckets: List[Bucket] = []
    slots: List[LeafSlot] = []
    fill = fill_bytes = 0
    cur_dtype: Optional[str] = None

    def close():
        nonlocal slots, fill, fill_bytes
        if slots:
            buckets.append(_bucket(slots, fill, group, itemsize))
        slots, fill, fill_bytes = [], 0, 0

    for i, leaf in enumerate(leaves):
        size = int(leaf.size) if hasattr(leaf, "size") else int(
            math.prod(leaf.shape))
        dt = getattr(leaf, "dtype", None)
        dt_name = None if dt is None else np.dtype(dt).name
        isz = itemsize if dt is None else np.dtype(dt).itemsize
        nbytes = size * isz
        if cap <= 0:
            # fusion disabled: per-tensor buckets (legacy schedule)
            buckets.append(_bucket(
                [LeafSlot(i, tuple(leaf.shape), size, 0, dt_name)], size,
                group, itemsize))
            continue
        if slots and (fill_bytes + nbytes > cap or dt_name != cur_dtype):
            close()
        cur_dtype = dt_name
        slots.append(LeafSlot(i, tuple(leaf.shape), size, fill, dt_name))
        fill += size
        fill_bytes += nbytes
        if fill_bytes >= cap:
            close()
    close()
    return BucketPlan(tuple(buckets), group, len(leaves))


def pack_bucket(flat_leaves: Sequence[jax.Array], bucket: Bucket) -> jax.Array:
    """Concatenate the bucket's leaves into one padded 1-D fusion buffer;
    a row-blocked bucket's buffer is its leaf viewed as ``(rows, cols)``.
    ``flat_leaves`` is indexed by flat tree position."""
    if bucket.row_blocked:
        return flat_leaves[bucket.slots[0].index].reshape(bucket.shape)
    parts = [flat_leaves[s.index].reshape(-1) for s in bucket.slots]
    pad = bucket.padded_size - bucket.size
    if pad:
        parts.append(jnp.zeros((pad,), parts[0].dtype))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


def unpack_buckets(buffers: Sequence[jax.Array],
                   plan: BucketPlan) -> List[jax.Array]:
    """Slice the fusion buffers back into leaves (tree order), restoring
    each leaf's recorded dtype (the optimizer may have promoted the bucket
    buffer, e.g. bf16 params updated against fp32 gradient strips)."""
    out: List[jax.Array] = [None] * plan.n_leaves
    for buf, bucket in zip(buffers, plan.buckets):
        for s in bucket.slots:
            if bucket.row_blocked:
                leaf = buf.reshape(s.shape)
            else:
                leaf = jax.lax.slice(
                    buf, (s.offset,), (s.offset + s.size,)).reshape(s.shape)
            if s.dtype is not None and leaf.dtype != np.dtype(s.dtype):
                leaf = leaf.astype(s.dtype)
            out[s.index] = leaf
    return out
