"""Paged-decode attention Pallas kernel: gather non-contiguous KV pages.

One decode token attends over a request's KV history scattered across
fixed-size pages of a physical pool (``serve.kvcache.PagedKVCache`` owns the
free-list; ``models.layers.PagedAttnCache`` is the device-side pytree).  The
kernel never materializes the gathered (B, n*ps, Hkv, D) cache that the
jnp reference path builds: the grid walks each request's LOGICAL pages and
the page table rides in as a scalar-prefetch argument driving the page-pool
BlockSpec index map — the same trick ``kernels.ring.ring_hop_accum`` uses to
select its chunk — so only the one (ps, Hkv, D) physical page the program
needs is copied into VMEM per step, wherever it sits in the pool.

Online softmax accumulates across a request's pages in VMEM scratch exactly
like ``kernels.flash_attention`` accumulates across kv blocks; pages fully
outside the valid set (beyond ``lengths`` or, for sliding-window layers,
older than the retention window) are skipped with ``pl.when``.

The math is 2-D dots per kv head — the g query heads that share it
against the page's (ps, D) keys and values — over free reshapes of q to
(B, Hkv, g, D) and of the pool to (P, ps, Hkv*D), so no GQA repeat and no
batched in-kernel einsum reaches Mosaic.

Correctness contract: ``kernels.ref.paged_decode_attention_ref``, swept in
tests/test_kernels.py under interpret mode (auto-enabled off-TPU, as with
the ring kernels).  ``tests/test_tpu_compile.py`` compiles the kernel for a
v5e at a served model's head count, kv heads and head_dim.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def _paged_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, ps: int, n_pages: int,
                  window: int, softcap: float, scale: float, hkv: int,
                  d: int):
    b, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]                       # valid tokens (incl. current)
    start = i * ps
    # page-level skip: any position of this logical page in the valid set?
    needed = start < length
    if window > 0:
        needed = jnp.logical_and(needed, start + ps - 1 > length - 1 - window)

    @pl.when(needed)
    def _body():
        g = q_ref.shape[2]
        pos = start + jax.lax.broadcasted_iota(jnp.int32, (g, ps), 1)
        mask = pos < length
        if window > 0:
            mask = jnp.logical_and(mask, pos > length - 1 - window)
        # one kv head at a time: its g query heads against the page's
        # (ps, D) keys and values, as plain 2-D dots
        for h in range(hkv):
            q = q_ref[0, h].astype(jnp.float32) * scale          # (g, D)
            k = k_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)  # (ps, D)
            v = v_ref[0, :, h * d:(h + 1) * d].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if softcap > 0:
                s = jnp.tanh(s / softcap) * softcap
            s = jnp.where(mask, s, NEG_INF)                      # (g, ps)

            m_prev = m_ref[h]                                    # (g, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + p.sum(-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p, v, preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(i == n_pages - 1)
    def _finalize():
        lsum = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / lsum).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, pages_k: jax.Array,
                           pages_v: jax.Array, page_table: jax.Array,
                           lengths: jax.Array, *, window: int = 0,
                           logit_softcap: float = 0.0,
                           interpret: Optional[bool] = None) -> jax.Array:
    """One-token attention over paged KV.  q: (B, Hq, D); pages_k/pages_v:
    (P, ps, Hkv, D); page_table: (B, n) int32; lengths: (B,) int32 valid
    token counts (>= 1 per request — see the oracle's contract).  Returns
    (B, Hq, D)."""
    B, Hq, D = q.shape
    P, ps, Hkv, _ = pages_k.shape
    n = page_table.shape[1]
    if Hq % Hkv:
        raise ValueError(f"GQA needs Hq % Hkv == 0, got {Hq}/{Hkv}")
    g = Hq // Hkv
    scale = D ** -0.5
    # free reshapes: query heads grouped by their kv head, and each page
    # row's kv heads side by side on the lane axis
    qg = q.reshape(B, Hkv, g, D)
    pk = pages_k.reshape(P, ps, Hkv * D)
    pv = pages_v.reshape(P, ps, Hkv * D)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                 # page_table, lengths
        grid=(B, n),
        in_specs=[
            pl.BlockSpec((1, Hkv, g, D), lambda b, i, pt, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, ps, Hkv * D),
                         lambda b, i, pt, ln: (pt[b, i], 0, 0)),
            pl.BlockSpec((1, ps, Hkv * D),
                         lambda b, i, pt, ln: (pt[b, i], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hkv, g, D),
                               lambda b, i, pt, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, g, D), jnp.float32),
            pltpu.VMEM((Hkv, g, 1), jnp.float32),
            pltpu.VMEM((Hkv, g, 1), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, ps=ps, n_pages=n, window=window,
        softcap=logit_softcap, scale=scale, hkv=Hkv, d=D)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, D), q.dtype),
        interpret=_auto_interpret(interpret),
    )(jnp.asarray(page_table, jnp.int32), jnp.asarray(lengths, jnp.int32),
      qg, pk, pv)
    return out.reshape(B, Hq, D)
