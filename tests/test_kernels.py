"""Per-kernel validation: shape/dtype sweeps + hypothesis property tests,
all in interpret mode (kernel body executes in Python on CPU) against the
pure-jnp oracles in kernels/ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.decode_attention import (decode_attention,
                                            mla_decode_attention)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def rand(key, shape, dtype, scale=1.0):
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# flash attention — shape x dtype x causality sweep
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,S,Hq,Hkv,hd,bq,bk", [
    (1, 32, 32, 4, 4, 32, 16, 16),      # MHA square
    (2, 64, 64, 8, 2, 32, 32, 16),      # GQA 4:1
    (1, 16, 64, 6, 3, 64, 16, 32),      # cross-length (T != S)
    (2, 128, 128, 4, 1, 16, 128, 64),   # MQA, single q block
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, T, S, Hq, Hkv, hd, bq, bk, dtype, causal):
    if causal and T != S:
        pytest.skip("causal cross-length not a served configuration")
    key = jax.random.key(hash((B, T, S, Hq, hd)) % 2**31)
    q = rand(key, (B, T, Hq, hd), dtype)
    k = rand(jax.random.fold_in(key, 1), (B, S, Hkv, hd), dtype)
    v = rand(jax.random.fold_in(key, 2), (B, S, Hkv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, blk_q=bq, blk_k=bk,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@given(st.integers(1, 3), st.sampled_from([16, 32, 64]),
       st.sampled_from([(4, 4), (4, 2), (8, 1)]),
       st.sampled_from([16, 32]))
@settings(max_examples=12, deadline=None)
def test_flash_attention_property(B, T, heads, hd):
    Hq, Hkv = heads
    key = jax.random.key(B * 1000 + T)
    q = rand(key, (B, T, Hq, hd), jnp.float32)
    k = rand(jax.random.fold_in(key, 1), (B, T, Hkv, hd), jnp.float32)
    v = rand(jax.random.fold_in(key, 2), (B, T, Hkv, hd), jnp.float32)
    out = flash_attention(q, k, v, causal=True, blk_q=16, blk_k=16,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)


def test_flash_attention_extreme_values():
    """Online softmax must survive large logits (no overflow in exp)."""
    key = jax.random.key(9)
    q = rand(key, (1, 32, 2, 16), jnp.float32, scale=30.0)
    k = rand(jax.random.fold_in(key, 1), (1, 32, 2, 16), jnp.float32,
             scale=30.0)
    v = rand(jax.random.fold_in(key, 2), (1, 32, 2, 16), jnp.float32)
    out = flash_attention(q, k, v, causal=True, blk_q=16, blk_k=16,
                          interpret=True)
    assert np.all(np.isfinite(np.asarray(out)))
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# decode attention — ragged lengths sweep over the stacked positions-minor
# cache (L, B, Hkv, hd, S), read at one layer
# ---------------------------------------------------------------------------
def stacked_kv(key, L, B, S, Hkv, hd, dtype):
    k = rand(jax.random.fold_in(key, 1), (L, B, Hkv, hd, S), dtype)
    v = rand(jax.random.fold_in(key, 2), (L, B, Hkv, hd, S), dtype)
    return k, v


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("L,layer,B,S,Hq,Hkv,hd,bk", [
    (1, 0, 1, 64, 4, 4, 32, 32),
    (3, 1, 3, 128, 8, 2, 32, 32),
    (2, 1, 2, 256, 16, 4, 64, 128),
    (4, 3, 4, 64, 4, 1, 16, 16),
])
def test_decode_attention_sweep(L, layer, B, S, Hq, Hkv, hd, bk, dtype):
    key = jax.random.key(hash((B, S, Hq)) % 2**31)
    q = rand(key, (B, Hq, hd), dtype)
    k, v = stacked_kv(key, L, B, S, Hkv, hd, dtype)
    lengths = jax.random.randint(jax.random.fold_in(key, 3), (B,), 1, S + 1)
    out = decode_attention(q, k, v, jnp.int32(layer), lengths, blk_k=bk,
                           interpret=True)
    want = ref.decode_attention_ref(q, k, v, layer, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@given(st.lists(st.integers(1, 64), min_size=1, max_size=4),
       st.integers(0, 2))
@settings(max_examples=15, deadline=None)
def test_decode_attention_ragged_property(lens, layer):
    L, B, S, Hq, Hkv, hd = 3, len(lens), 64, 4, 2, 16
    key = jax.random.key(sum(lens))
    q = rand(key, (B, Hq, hd), jnp.float32)
    k, v = stacked_kv(key, L, B, S, Hkv, hd, jnp.float32)
    lengths = jnp.asarray(lens, jnp.int32)
    out = decode_attention(q, k, v, jnp.int32(layer), lengths, blk_k=16,
                           interpret=True)
    want = ref.decode_attention_ref(q, k, v, layer, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=3e-5, atol=3e-5)
    # INVARIANT: cache contents past length[b], and every other layer,
    # must not affect the output
    k2 = k.at[:, :, :, :, -1].set(99.0)
    k2 = k2.at[(layer + 1) % L].set(-99.0)
    masked_same = decode_attention(
        q, k2, v, jnp.int32(layer), jnp.minimum(lengths, S - 1), blk_k=16,
        interpret=True)
    want2 = ref.decode_attention_ref(q, k, v, layer,
                                     jnp.minimum(lengths, S - 1))
    np.testing.assert_allclose(np.asarray(masked_same), np.asarray(want2),
                               rtol=3e-5, atol=3e-5)


# ---------------------------------------------------------------------------
# MLA decode — absorbed queries against the stacked latent cache
# (L, B, rank + rope, S), the first ``rank`` rows doubling as V
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("L,layer,B,S,H,rank,rope,bk", [
    (1, 0, 1, 64, 4, 32, 8, 32),
    (3, 2, 4, 256, 8, 64, 16, 64),
    (2, 1, 2, 128, 16, 32, 8, 128),     # one kv block
])
def test_mla_decode_attention_sweep(L, layer, B, S, H, rank, rope, bk,
                                    dtype):
    key = jax.random.key(hash((L, B, S, H, rank)) % 2**31)
    q = rand(key, (B, H, rank + rope), dtype)
    cache = rand(jax.random.fold_in(key, 1), (L, B, rank + rope, S), dtype)
    lengths = jax.random.randint(jax.random.fold_in(key, 2), (B,), 1, S + 1)
    scale = 0.13
    out = mla_decode_attention(q, cache, jnp.int32(layer), lengths,
                               scale=scale, rank=rank, blk_k=bk,
                               interpret=True)
    want = ref.mla_decode_attention_ref(q, cache, layer, lengths,
                                        scale=scale, rank=rank)
    assert out.shape == (B, H, rank)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])
    # positions past each length, and the other layers, do not count
    junk = cache.at[:, :, :, -1].set(99.0)
    if L > 1:
        junk = junk.at[(layer + 1) % L].set(-99.0)
    short = jnp.minimum(lengths, S - 1)
    np.testing.assert_allclose(
        np.asarray(mla_decode_attention(q, junk, jnp.int32(layer), short,
                                        scale=scale, rank=rank, blk_k=bk,
                                        interpret=True), np.float32),
        np.asarray(ref.mla_decode_attention_ref(q, cache, layer, short,
                                                scale=scale, rank=rank),
                   np.float32), **TOL[dtype])


def test_flash_attention_narrow_v_and_given_scale():
    """MLA's prefill form: v narrower than q/k, a softmax scale given."""
    key = jax.random.key(5)
    q = rand(key, (1, 64, 4, 24), jnp.float32)
    k = rand(jax.random.fold_in(key, 1), (1, 64, 4, 24), jnp.float32)
    v = rand(jax.random.fold_in(key, 2), (1, 64, 4, 16), jnp.float32)
    out = flash_attention(q, k, v, causal=True, scale=0.3, blk_q=16,
                          blk_k=32, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, scale=0.3)
    assert out.shape == (1, 64, 4, 16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# SSD scan — chunked kernel vs SEQUENTIAL recurrence oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,T,H,P,N,Q", [
    (1, 32, 2, 16, 8, 8),
    (2, 64, 3, 16, 8, 16),
    (1, 128, 4, 32, 16, 32),
    (2, 64, 1, 64, 64, 64),    # single chunk boundary case
])
def test_ssd_scan_sweep(B, T, H, P, N, Q, dtype):
    key = jax.random.key(hash((B, T, H, P, N)) % 2**31)
    u = rand(key, (B, T, H, P), dtype, 0.5)
    loga = -jax.random.uniform(jax.random.fold_in(key, 1), (B, T, H)) * 0.5
    Bm = rand(jax.random.fold_in(key, 2), (B, T, N), jnp.float32, 0.3)
    Cm = rand(jax.random.fold_in(key, 3), (B, T, N), jnp.float32, 0.3)
    y, st_ = ssd_scan(u, loga.astype(dtype), Bm, Cm, chunk=Q, interpret=True)
    yr, str_ = ref.ssd_ref(u, loga, Bm, Cm)
    tol = dict(rtol=2e-4, atol=2e-4) if dtype == jnp.float32 else \
        dict(rtol=4e-2, atol=4e-2)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(st_), np.asarray(str_),
                               rtol=1e-3, atol=1e-3)


@given(st.sampled_from([8, 16, 32]), st.sampled_from([8, 16]),
       st.integers(1, 2))
@settings(max_examples=10, deadline=None)
def test_ssd_chunk_size_invariance(Q, N, B):
    """Different chunkings of the same sequence give the same answer."""
    T, H, P = 64, 2, 16
    key = jax.random.key(Q * 100 + N)
    u = rand(key, (B, T, H, P), jnp.float32, 0.5)
    loga = -jax.random.uniform(jax.random.fold_in(key, 1), (B, T, H)) * 0.4
    Bm = rand(jax.random.fold_in(key, 2), (B, T, N), jnp.float32, 0.3)
    Cm = rand(jax.random.fold_in(key, 3), (B, T, N), jnp.float32, 0.3)
    y1, s1 = ssd_scan(u, loga, Bm, Cm, chunk=Q, interpret=True)
    y2, s2 = ssd_scan(u, loga, Bm, Cm, chunk=T, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
def test_ops_dispatch_backends():
    """ops.* wrappers: xla and interpret backends agree."""
    key = jax.random.key(3)
    q = rand(key, (1, 32, 4, 16), jnp.float32)
    k = rand(jax.random.fold_in(key, 1), (1, 32, 2, 16), jnp.float32)
    v = rand(jax.random.fold_in(key, 2), (1, 32, 2, 16), jnp.float32)
    a = ops.flash_attention(q, k, v, backend="xla")
    b = ops.flash_attention(q, k, v, backend="interpret", blk_q=16, blk_k=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=3e-5, atol=3e-5)
    ops.set_backend("xla")
    try:
        c = ops.flash_attention(q, k, v)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    finally:
        ops.set_backend(None)
