"""Per-kernel allclose sweeps against the ref.py oracles (interpret mode)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hypothesis_compat import given, settings, st
from repro.kernels import ops, ref
from repro.kernels.blocked_matmul import blocked_matmul
from repro.kernels.conv2d import conv2d_nhwc
from repro.kernels.flash_attention import flash_attention

RNG = np.random.default_rng(42)


def _arr(*shape, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=shape), dtype)


# ---------------------------------------------------------------------------
# blocked matmul
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,n,k", [
    (8, 128, 128), (128, 128, 128), (256, 512, 384), (64, 256, 1024),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_shapes_dtypes(m, n, k, dtype):
    a, b = _arr(m, k, dtype=dtype), _arr(k, n, dtype=dtype)
    out = blocked_matmul(a, b, interpret=True)
    want = ref.matmul_ref(a, b)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out, want, rtol=tol, atol=tol)


@given(st.integers(0, 3), st.integers(0, 3), st.integers(0, 3))
@settings(max_examples=12, deadline=None)
def test_matmul_hypothesis_pow2(i, j, p):
    m, n, k = 8 * 2**i, 128 * 2**j, 128 * 2**p
    a, b = _arr(m, k), _arr(k, n)
    np.testing.assert_allclose(blocked_matmul(a, b, interpret=True),
                               ref.matmul_ref(a, b), rtol=2e-4, atol=2e-4)


def test_matmul_uses_solver_blocking():
    from repro.core.blocking import solve_gemm_blocking
    blk = solve_gemm_blocking(256, 512, 384, vmem_bytes=2 * 2**20)
    a, b = _arr(256, 384), _arr(384, 512)
    out = blocked_matmul(a, b, blocking=blk, interpret=True)
    np.testing.assert_allclose(out, ref.matmul_ref(a, b), rtol=2e-4,
                               atol=2e-4)


# ---------------------------------------------------------------------------
# conv2d
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("k,stride,pad", [
    (3, 1, 1), (3, 2, 1), (5, 1, 0), (1, 1, 0), (11, 4, 0),
])
def test_conv_kernel_configs(k, stride, pad):
    h = max(k + 3, 12)
    x, w = _arr(2, h, h, 8), _arr(k, k, 8, 16)
    out = conv2d_nhwc(x, w, stride=stride, padding=pad, interpret=True)
    want = ref.conv2d_ref(x, w, stride=stride, padding=pad)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-4)


@given(ifm=st.sampled_from([3, 8, 16]), ofm=st.sampled_from([8, 16, 32]),
       size=st.sampled_from([8, 12, 16]))
@settings(max_examples=10, deadline=None)
def test_conv_hypothesis_channels(ifm, ofm, size):
    x, w = _arr(1, size, size, ifm), _arr(3, 3, ifm, ofm)
    np.testing.assert_allclose(
        conv2d_nhwc(x, w, stride=1, padding=1, interpret=True),
        ref.conv2d_ref(x, w, stride=1, padding=1), rtol=1e-4, atol=1e-4)


def test_conv_channel_blocking_matches():
    x, w = _arr(1, 12, 12, 32), _arr(3, 3, 32, 64)
    out = conv2d_nhwc(x, w, stride=1, padding=1, bifm=8, bofm=16,
                      interpret=True)
    np.testing.assert_allclose(out, ref.conv2d_ref(x, w, 1, 1),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (8, 1)])
@pytest.mark.parametrize("window,softcap", [(0, 0.0), (64, 0.0), (0, 30.0),
                                            (128, 50.0)])
def test_flash_attention_features(hq, hkv, window, softcap):
    q = _arr(2, 256, hq, 64)
    k = _arr(2, 256, hkv, 64)
    v = _arr(2, 256, hkv, 64)
    out = flash_attention(q, k, v, causal=True, window=window,
                          logit_softcap=softcap, interpret=True)
    want = ref.attention_ref(q, k, v, causal=True, window=window,
                             logit_softcap=softcap)
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                       (jnp.bfloat16, 2e-2)])
def test_flash_attention_dtypes(dtype, tol):
    q, k, v = (_arr(1, 128, 4, 128, dtype=dtype) for _ in range(3))
    out = flash_attention(q, k, v, interpret=True)
    want = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@given(sq=st.sampled_from([128, 256]), d=st.sampled_from([32, 64, 128]))
@settings(max_examples=8, deadline=None)
def test_flash_attention_hypothesis(sq, d):
    q, k, v = _arr(1, sq, 2, d), _arr(1, sq, 2, d), _arr(1, sq, 2, d)
    np.testing.assert_allclose(
        flash_attention(q, k, v, interpret=True),
        ref.attention_ref(q, k, v), rtol=3e-4, atol=3e-4)


def test_attention_op_gradient_matches_ref():
    q, k, v = _arr(1, 128, 4, 32), _arr(1, 128, 2, 32), _arr(1, 128, 2, 32)
    g = jax.grad(lambda *a: jnp.sum(ops.attention(*a, True, 0, 0.0) ** 2),
                 argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(ref.attention_ref(*a) ** 2),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)


def test_decode_attention_ref_ring_buffer_invariance():
    """Softmax over a set: ring-buffer rotation must not change output."""
    B, C, H, D = 2, 32, 4, 16
    k = _arr(B, C, H, D)
    v = _arr(B, C, H, D)
    q = _arr(B, 1, H, D)
    ln = jnp.full((B,), C, jnp.int32)
    out1 = ref.decode_attention_ref(q, k, v, ln)
    def rot(t):
        return jnp.roll(t, 7, axis=1)
    out2 = ref.decode_attention_ref(q, rot(k), rot(v), ln)
    np.testing.assert_allclose(out1, out2, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# §3.4 ring reduce-scatter / all-gather (kernels/ring.py)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 5e-2)])
@given(G=st.sampled_from([1, 2, 3, 4, 8]), n=st.sampled_from([1, 3, 8, 40]))
@settings(max_examples=12, deadline=None)
def test_ring_reduce_scatter_matches_oracle(dtype, tol, G, n):
    from repro.kernels.ring import ring_reduce_scatter
    stacked = _arr(G, G * n, dtype=dtype)
    got = ring_reduce_scatter(stacked, interpret=True)
    want = ref.ring_reduce_scatter_ref(stacked)
    assert got.shape == (G, n) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@given(G=st.sampled_from([1, 2, 3, 4, 8]), n=st.sampled_from([1, 3, 8, 40]))
@settings(max_examples=12, deadline=None)
def test_ring_all_gather_matches_oracle(dtype, G, n):
    from repro.kernels.ring import ring_all_gather
    strips = _arr(G, n, dtype=dtype)
    got = ring_all_gather(strips, interpret=True)
    want = ref.ring_all_gather_ref(strips)
    assert got.shape == (G, G * n)
    # pure data movement: must be EXACT in any dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(G=st.sampled_from([2, 4, 8]), n=st.sampled_from([2, 16]))
@settings(max_examples=8, deadline=None)
def test_ring_round_trip_is_allreduce(G, n):
    """all_gather(reduce_scatter(x)) == the replicated full sum on every
    member — the §3.4 part-reduce/part-broadcast identity the ZeRO-1 strip
    update relies on."""
    from repro.kernels.ring import ring_all_gather, ring_reduce_scatter
    stacked = _arr(G, G * n)
    full = ring_all_gather(ring_reduce_scatter(stacked, interpret=True),
                           interpret=True)
    want = np.broadcast_to(np.asarray(stacked).sum(axis=0), (G, G * n))
    np.testing.assert_allclose(np.asarray(full), want, rtol=1e-5, atol=1e-5)


def test_ring_reduce_scatter_rejects_ragged_buffer():
    from repro.kernels.ring import ring_reduce_scatter
    with pytest.raises(ValueError):
        ring_reduce_scatter(_arr(3, 10), interpret=True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ring_hop_accum_matches_jnp(dtype):
    """The distributed backend's per-hop combine: recv + chunks[c] for every
    valid (traced) chunk index."""
    from repro.kernels.ring import from_tiles, ring_hop_accum, to_tiles
    G, n = 4, 24
    chunks = _arr(G, n, dtype=dtype)
    recv = _arr(n, dtype=dtype)
    for c in range(G):
        got = from_tiles(ring_hop_accum(to_tiles(chunks), to_tiles(recv),
                                        jnp.int32(c), interpret=True), n)
        np.testing.assert_allclose(
            np.asarray(got, np.float32),
            np.asarray(recv + chunks[c], np.float32), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# paged decode attention (kernels/paged_attn.py, scalar-prefetch page gather)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("window,softcap", [
    (0, 0.0), (6, 0.0), (0, 30.0), (5, 50.0),
])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_paged_decode_matches_oracle(window, softcap, dtype, tol):
    from repro.kernels.paged_attn import paged_decode_attention
    B, n, ps, Hq, Hkv, D, P = 3, 5, 4, 8, 2, 16, 20
    pages_k = _arr(P, ps, Hkv, D, dtype=dtype)
    pages_v = _arr(P, ps, Hkv, D, dtype=dtype)
    q = _arr(B, Hq, D, dtype=dtype)
    # non-contiguous layout: each request's logical pages scattered over the
    # physical pool (never page 0, the null page)
    pt = jnp.asarray(RNG.permutation(P - 1)[:B * n].reshape(B, n) + 1,
                     jnp.int32)
    lengths = jnp.asarray([1, 9, n * ps], jnp.int32)   # edge: 1 and full
    got = paged_decode_attention(q, pages_k, pages_v, pt, lengths,
                                 window=window, logit_softcap=softcap,
                                 interpret=True)
    want = ref.paged_decode_attention_ref(q, pages_k, pages_v, pt, lengths,
                                          window=window,
                                          logit_softcap=softcap)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_paged_ref_matches_dense_decode_ref():
    """Identity page layout: the paged oracle must agree with the dense
    ring-buffer decode oracle (same math, different cache addressing)."""
    B, C, Hq, Hkv, D, ps = 2, 32, 4, 2, 16, 8
    n = C // ps
    P = 1 + B * n
    pages_k, pages_v = _arr(P, ps, Hkv, D), _arr(P, ps, Hkv, D)
    q = _arr(B, Hq, D)
    pt = jnp.arange(1, P, dtype=jnp.int32).reshape(B, n)
    lengths = jnp.asarray([5, C], jnp.int32)
    dense_k = pages_k[1:].reshape(B, C, Hkv, D)
    dense_v = pages_v[1:].reshape(B, C, Hkv, D)
    paged = ref.paged_decode_attention_ref(q, pages_k, pages_v, pt, lengths)
    dense = ref.decode_attention_ref(q[:, None], dense_k, dense_v, lengths)
    np.testing.assert_allclose(paged, dense[:, 0], rtol=1e-5, atol=1e-5)
