"""Pallas TPU flash attention (prefill): blocked online-softmax, GQA.

Tiling: grid (B, Hq, T/blk_q, S/blk_k); the innermost kv dimension is
"arbitrary" (sequential) and carries (m, l, acc) in VMEM scratch — fp32
accumulation on the MXU, one (blk_q, hd) output tile written at the last kv
step. Causal block-skip: fully-masked kv blocks are not computed.

Block shapes default to (128, 128) x hd — MXU-aligned; hd in {64..128}
pads to the lane width automatically. V may be narrower than Q and K (MLA
prefill: q/k 192 wide, v 128), and the softmax scale may be given (MLA's
YaRN factor).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  blk_q: int, blk_k: int, causal: bool, scale: float,
                  n_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute():
        q = q_ref[...].astype(jnp.float32) * scale        # (blk_q, hd)
        k = k_ref[...].astype(jnp.float32)                # (blk_k, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            qpos = qi * blk_q + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0)
            kpos = ki * blk_k + jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))   # (blk_q,)
        corr = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])                   # (blk_q, blk_k)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1)
        v = v_ref[...].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    if causal:
        # skip kv blocks strictly above the diagonal band
        @pl.when(ki * blk_k <= qi * blk_q + blk_q - 1)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "blk_q",
                                             "blk_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    blk_q: int = 128, blk_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q/k: (B, T|S, Hq|Hkv, hd); v: (B, S, Hkv, dv) -> (B, T, Hq, dv).
    ``scale`` defaults to 1/sqrt(hd)."""
    B, T, Hq, hd = q.shape
    _, S, Hkv, _ = k.shape
    dv = v.shape[-1]
    g = Hq // Hkv
    blk_q = min(blk_q, T)
    blk_k = min(blk_k, S)
    assert T % blk_q == 0 and S % blk_k == 0
    n_q, n_k = T // blk_q, S // blk_k
    scale = 1.0 / (hd ** 0.5) if scale is None else scale

    # layout: heads-major so each (b, h) pair owns contiguous (T, hd) tiles
    qt = q.transpose(0, 2, 1, 3)          # (B, Hq, T, hd)
    kt = k.transpose(0, 2, 1, 3)          # (B, Hkv, S, hd)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_flash_kernel, blk_q=blk_q, blk_k=blk_k,
                               causal=causal, scale=scale, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, n_q, n_k),
        in_specs=[
            # None-dims are squeezed: refs arrive as (blk, hd) tiles
            pl.BlockSpec((None, None, blk_q, hd),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((None, None, blk_k, hd),
                         lambda b, h, qi, ki, g=g: (b, h // g, ki, 0)),
            pl.BlockSpec((None, None, blk_k, dv),
                         lambda b, h, qi, ki, g=g: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, blk_q, dv),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, T, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, dv), jnp.float32),
            pltpu.VMEM((blk_q,), jnp.float32),
            pltpu.VMEM((blk_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)      # back to (B, T, Hq, dv)
