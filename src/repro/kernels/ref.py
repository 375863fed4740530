"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each kernel test sweeps shapes/dtypes and asserts the Pallas implementation
(interpret mode on CPU) matches these references to tight tolerances.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def matmul_ref(a: jax.Array, b: jax.Array,
               out_dtype=jnp.float32) -> jax.Array:
    """C = A @ B with f32 accumulation (MXU semantics)."""
    return jnp.matmul(a, b, preferred_element_type=jnp.float32).astype(out_dtype)


def conv2d_ref(x: jax.Array, w: jax.Array, stride: int = 1,
               padding: int = 0, out_dtype=jnp.float32) -> jax.Array:
    """NHWC x HWIO -> NHWC direct convolution (the paper's Algorithm 1,
    adapted to the TPU-native lane-contiguous channel-innermost layout)."""
    out = lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32),
        window_strides=(stride, stride),
        padding=[(padding, padding), (padding, padding)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return out.astype(out_dtype)


def _softcap(logits: jax.Array, cap: float) -> jax.Array:
    return jnp.tanh(logits / cap) * cap if cap > 0 else logits


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True,
                  window: int = 0,
                  logit_softcap: float = 0.0,
                  scale: Optional[float] = None,
                  out_dtype=None) -> jax.Array:
    """Multi-head attention oracle.

    q: (B, Sq, Hq, D);  k, v: (B, Skv, Hkv, D) with Hq % Hkv == 0 (GQA).
    ``window`` > 0 enables sliding-window causal attention (each query sees
    keys in (pos - window, pos]).  Softmax in f32.
    """
    B, Sq, Hq, D = q.shape
    _, Skv, Hkv, _ = k.shape
    g = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if g > 1:
        kf = jnp.repeat(kf, g, axis=2)
        vf = jnp.repeat(vf, g, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kf)
    logits = _softcap(logits, logit_softcap)
    q_pos = jnp.arange(Sq)[:, None] + (Skv - Sq)   # right-aligned
    k_pos = jnp.arange(Skv)[None, :]
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window and window > 0:
        mask &= k_pos > q_pos - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, vf)
    return out.astype(out_dtype or q.dtype)


def ring_reduce_scatter_ref(stacked: jax.Array) -> jax.Array:
    """Oracle for ``kernels.ring.ring_reduce_scatter``: row p of the result
    is the sum over members of chunk p (f32 accumulation; the ring kernel
    accumulates hop-by-hop in the wire dtype, so bf16 compares to
    tolerance)."""
    G, N = stacked.shape
    full = stacked.astype(jnp.float32).sum(axis=0)
    return full.reshape(G, N // G).astype(stacked.dtype)


def ring_all_gather_ref(strips: jax.Array) -> jax.Array:
    """Oracle for ``kernels.ring.ring_all_gather``: every member ends up
    with the full buffer — strips concatenated in owner order."""
    G, n = strips.shape
    return jnp.broadcast_to(strips.reshape(1, G * n), (G, G * n))


def int8_quantize_ref(x: jax.Array):
    """Oracle for ``kernels.ring.int8_quantize``: symmetric per-message
    max-abs quantization.  Returns ``(q int8 (n,), scale f32 (1,))`` with
    ``scale = max|x| * (1/127)`` (1.0 for an all-zero message so dequantize
    is well defined); round-to-nearest keeps ``|q| <= 127`` by construction.
    The scale is a product with the f32 reciprocal, not a quotient: XLA may
    rewrite a division by a constant into that product in one program and
    not in another, and the kernel must match this oracle bit for bit."""
    xf = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(xf)) * (1.0 / 127.0)
    s = jnp.where(s > 0, s, 1.0)
    q = jnp.round(xf / s).astype(jnp.int8)
    return q, s.reshape(1)


def int8_dequantize_ref(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of :func:`int8_quantize_ref` (f32 result)."""
    return q.astype(jnp.float32) * scale.reshape(())[None]


def ring_hop_int8_ref(chunks: jax.Array, q: jax.Array, scale: jax.Array,
                      c) -> tuple:
    """Oracle for ``kernels.ring.ring_hop_int8``: dequantize the received
    message, accumulate the local partial of chunk ``c`` in f32, and
    re-quantize against a FRESH max-abs scale — per-hop f32 accumulation is
    what keeps the quantization error additive (one rounding per hop)
    instead of compounding across the G-1 hops."""
    acc = int8_dequantize_ref(q, scale) + chunks[c].astype(jnp.float32)
    return int8_quantize_ref(acc)


def topk_select_ref(x: jax.Array, k: int) -> tuple:
    """Top-k sparsification oracle: the ``k`` largest-|x| entries as a
    ``(values f32 (k,), indices int32 (k,))`` wire message."""
    xf = x.astype(jnp.float32)
    _, idx = lax.top_k(jnp.abs(xf), k)
    return xf[idx], idx.astype(jnp.int32)


def topk_scatter_ref(vals: jax.Array, idx: jax.Array, n: int) -> jax.Array:
    """Densify a (values, indices) message into an ``(n,)`` f32 buffer
    (duplicate indices accumulate, matching the kernel's scatter-add)."""
    return jnp.zeros((n,), jnp.float32).at[idx].add(
        vals.astype(jnp.float32))


def ring_hop_topk_ref(chunks: jax.Array, vals: jax.Array, idx: jax.Array,
                      c) -> jax.Array:
    """Oracle for ``kernels.ring.ring_hop_topk``: scatter the received
    sparse message dense and add the local partial of chunk ``c`` (f32).
    Re-selection of the next hop's top-k stays OUTSIDE the kernel (the
    backend calls :func:`topk_select_ref`-equivalent jnp on the result)."""
    return topk_scatter_ref(vals, idx, chunks.shape[1]) \
        + chunks[c].astype(jnp.float32)


def topk_mask_ref(x: jax.Array, k: int) -> jax.Array:
    """Keep the ``k`` largest-|x| entries of ``x`` in place, zero the rest
    — the bucket-level sparsifier of the error-feedback update
    (``optim.dist.make_topk_ef_update``); the residual is ``x - mask``."""
    _, idx = lax.top_k(jnp.abs(x.astype(jnp.float32)), k)
    return jnp.zeros_like(x).at[idx].set(x[idx])


def paged_decode_attention_ref(q: jax.Array, pages_k: jax.Array,
                               pages_v: jax.Array, page_table: jax.Array,
                               lengths: jax.Array, *, window: int = 0,
                               logit_softcap: float = 0.0) -> jax.Array:
    """One-token attention over a PAGED KV cache (oracle for
    ``kernels.paged_attn.paged_decode_attention``).

    q: (B, Hq, D); pages_k/pages_v: (P, ps, Hkv, D) — the physical page
    pool; page_table: (B, n) int32 physical page id per logical page;
    lengths: (B,) int32 — number of VALID tokens per request (including the
    one just written).  Logical position ``p`` of request ``b`` lives in
    page ``page_table[b, p // ps]`` at offset ``p % ps``.  Positions
    >= lengths are masked; ``window`` > 0 additionally masks positions
    <= lengths - 1 - window (the ring-buffer SWA retention set: the last
    ``window`` tokens).  Requires lengths >= 1 (a fully-masked request's
    softmax would be degenerate — the serving engine never attends an
    empty cache).
    """
    B, Hq, D = q.shape
    _, ps, Hkv, _ = pages_k.shape
    n = page_table.shape[1]
    g = Hq // Hkv
    scale = D ** -0.5
    kg = pages_k[page_table].reshape(B, n * ps, Hkv, D).astype(jnp.float32)
    vg = pages_v[page_table].reshape(B, n * ps, Hkv, D).astype(jnp.float32)
    if g > 1:
        kg = jnp.repeat(kg, g, axis=2)
        vg = jnp.repeat(vg, g, axis=2)
    qf = q.astype(jnp.float32) * scale                 # (B, Hq, D)
    logits = jnp.einsum("bhd,bkhd->bhk", qf, kg)
    logits = _softcap(logits, logit_softcap)
    pos = jnp.arange(n * ps)[None, :]
    valid = pos < lengths[:, None]
    if window and window > 0:
        valid &= pos > lengths[:, None] - 1 - window
    logits = jnp.where(valid[:, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", p, vg)
    return out.astype(q.dtype)


def decode_attention_ref(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                         cache_len, *, window: int = 0,
                         logit_softcap: float = 0.0) -> jax.Array:
    """One-token attention against a (possibly ring-buffered) cache.

    q: (B, 1, Hq, D); caches: (B, C, Hkv, D); cache_len: (B,) valid lengths.
    Entries at index >= cache_len are masked.  With a ring buffer the caller
    guarantees only the most recent ``window`` entries are resident, so no
    extra position masking is needed beyond validity.
    """
    B, C, Hkv, D = k_cache.shape
    Hq = q.shape[2]
    g = Hq // Hkv
    scale = D ** -0.5
    qf = q[:, 0].astype(jnp.float32) * scale           # (B, Hq, D)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    if g > 1:
        kf = jnp.repeat(kf, g, axis=2)
        vf = jnp.repeat(vf, g, axis=2)
    logits = jnp.einsum("bhd,bkhd->bhk", qf, kf)
    logits = _softcap(logits, logit_softcap)
    valid = jnp.arange(C)[None, :] < cache_len[:, None]
    logits = jnp.where(valid[:, None, :], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhk,bkhd->bhd", p, vf)
    return out[:, None].astype(q.dtype)
