"""Pallas TPU decode attention: one new token vs a long slot-contiguous KV
cache, GQA, per-sequence valid lengths.

This is the steady-state op of the fabric's continuous-batching workers —
purely memory-bound (arithmetic intensity ~ 2 FLOPs/byte), so the tiling goal
is streaming the KV cache HBM->VMEM in (hd, blk_k) tiles exactly once while
the (g, hd) query tile for the kv-head group stays resident. Grid
(B, Hkv, S/blk_k); the kv dimension is sequential and carries the online-
softmax state (m, l, acc) for the whole head-group tile in VMEM.

The kernel reads the models' stacked cache as it is stored,
(L, B, Hkv, hd, S) with positions minor: the layer index and the lengths
are scalar-prefetched and pick each tile in the BlockSpec index map, so a
decode step's layer scan hands the kernel the whole cache and nothing is
sliced or transposed per layer. Positions minor also keeps the tiles
unpadded: hd 64 or 96 as the lane axis would be padded to 128.

Invalid cache positions (>= length[b]) are masked, so one compiled kernel
serves every request mix in the engine's slots.

``mla_decode_attention`` is the latent-attention (MLA) form: one latent
cache row per position, read once for every head (see its docstring).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(layer_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, blk_k: int, n_k: int,
                   scale: float):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]

    # skip kv blocks entirely past the valid prefix (saves HBM reads — this
    # is the decode analogue of causal block-skip)
    @pl.when(ki * blk_k < length)
    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale       # (g, hd)
        k = k_ref[...].astype(jnp.float32)               # (hd, blk_k)
        s = jax.lax.dot_general(q, k, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (g, blk_k)
        kpos = ki * blk_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        corr = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        v = v_ref[...].astype(jnp.float32)               # (hd, blk_k)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("blk_k", "interpret"))
def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     layer: jax.Array, lengths: jax.Array, *,
                     blk_k: int = 256, interpret: bool = False) -> jax.Array:
    """q: (B, Hq, hd); k_cache/v_cache: (L, B, Hkv, hd, S); layer: ()
    int32, the layer whose K/V are read; lengths: (B,) int32.
    Returns (B, Hq, hd)."""
    B, Hq, hd = q.shape
    _, _, Hkv, _, S = k_cache.shape
    g = Hq // Hkv
    blk_k = min(blk_k, S)
    assert S % blk_k == 0
    n_k = S // blk_k
    scale = 1.0 / (hd ** 0.5)

    qt = q.reshape(B, Hkv, g, hd)
    kv_spec = pl.BlockSpec((None, None, None, hd, blk_k),
                           lambda b, h, ki, layer, _: (layer[0], b, h, 0, ki))

    kernel = functools.partial(_decode_kernel, blk_k=blk_k, n_k=n_k,
                               scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # layer and lengths land in SMEM
        grid=(B, Hkv, n_k),
        in_specs=[
            pl.BlockSpec((None, None, g, hd),
                         lambda b, h, ki, *_: (b, h, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((None, None, g, hd),
                               lambda b, h, ki, *_: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, hd), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      lengths.astype(jnp.int32), qt, k_cache, v_cache)
    return out.reshape(B, Hq, hd)


# ---------------------------------------------------------------------------
# MLA: absorbed decode over the stacked latent cache
# ---------------------------------------------------------------------------
def _mla_decode_kernel(layer_ref, len_ref, q_ref, c_ref, o_ref,
                       acc_ref, m_ref, l_ref, *, blk_k: int, n_k: int,
                       scale: float, rank: int):
    b = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]

    @pl.when(ki * blk_k < length)
    def _compute():
        q = q_ref[...]                                   # (H, rank+rope)
        c = c_ref[...]                                   # (rank+rope, blk_k)
        s = jax.lax.dot_general(q, c, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        kpos = ki * blk_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)         # (H, blk_k)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        corr = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        v = c[:rank]                       # the normalised c_kv rows are V
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (H, rank)
        m_ref[...] = m_cur

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "rank", "blk_k",
                                             "interpret"))
def mla_decode_attention(q: jax.Array, cache: jax.Array, layer: jax.Array,
                         lengths: jax.Array, *, scale: float, rank: int,
                         blk_k: int = 256,
                         interpret: bool = False) -> jax.Array:
    """Absorbed MLA decode. q: (B, H, rank + rope), each head's query
    against [c_kv; k_pe]; cache: the stacked latent cache
    (L, B, rank + rope, S), positions minor, read at ``layer``; lengths:
    (B,) int32. Returns (B, H, rank): each head's softmax-weighted sum of
    the cache's first ``rank`` rows (the normalised c_kv).

    Grid (B, S/blk_k): every step reads one (rank + rope, blk_k) latent
    tile once for all H heads, so the cache is read once a step however
    many heads there are. A tile past a slot's length is neither computed
    nor fetched (its index map repeats the slot's last valid tile)."""
    B, H, D = q.shape
    S = cache.shape[-1]
    blk_k = min(blk_k, S)
    assert S % blk_k == 0
    n_k = S // blk_k

    def tile(b, ki, layer, lens):
        last = jnp.maximum((lens[b] + blk_k - 1) // blk_k - 1, 0)
        return (layer[0], b, 0, jnp.minimum(ki, last))

    kernel = functools.partial(_mla_decode_kernel, blk_k=blk_k, n_k=n_k,
                               scale=scale, rank=rank)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # layer and lengths land in SMEM
        grid=(B, n_k),
        in_specs=[
            pl.BlockSpec((None, H, D), lambda b, ki, *_: (b, 0, 0)),
            pl.BlockSpec((None, None, D, blk_k), tile),
        ],
        out_specs=pl.BlockSpec((None, H, rank), lambda b, ki, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, rank), jnp.float32),
            pltpu.VMEM((H,), jnp.float32),
            pltpu.VMEM((H,), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      lengths.astype(jnp.int32), q, cache)
