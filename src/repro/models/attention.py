"""GQA and latent (MLA) attention (train/prefill/decode) with optional
Pallas kernel dispatch.

Shapes follow the (B, T, H, hd) convention. KV caches are slot-contiguous
and stacked over layers, (L, B, H_kv, hd, L_max) with positions minor — the
TPU-native adaptation of paged attention (see DESIGN.md §3): contiguous
blocks DMA cleanly into VMEM; per-sequence lengths mask validity instead of
page tables. Positions minor keeps hd (64, 96) off the 128-wide lane axis,
so the stored cache is unpadded, and it is the layout the decode kernel
tiles, so the layer scan carries the cache and writes rows in place. MLA keeps
one latent row per position instead, (L, B, kv_lora_rank + qk_rope_dim,
L_max), in the same positions-minor layout (``mla_block``).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.distributed.logical import constrain

from .common import (ArchConfig, apply_rope, dense_init, mla_softmax_scale,
                     rmsnorm, rope_angles)

NEG_INF = -1e30


class AttnParams(NamedTuple):
    wq: jax.Array     # (d, Hq*hd)
    wk: jax.Array     # (d, Hkv*hd)
    wv: jax.Array     # (d, Hkv*hd)
    wo: jax.Array     # (Hq*hd, d)


def init_attn(key, cfg: ArchConfig) -> AttnParams:
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, hq, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    return AttnParams(
        dense_init(kq, (d, hq * hd), dtype=cfg.param_dtype),
        dense_init(kk, (d, hkv * hd), dtype=cfg.param_dtype),
        dense_init(kv, (d, hkv * hd), dtype=cfg.param_dtype),
        dense_init(ko, (hq * hd, d), dtype=cfg.param_dtype),
    )


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool, blk: int = 512,
                      scale: float | None = None) -> jax.Array:
    """Flash-style attention in PURE XLA: lax.scan over KV blocks with
    online softmax, rematerialized — the S^2 score tensor never exists.
    This is the lowering the dry-run compiles (the Pallas kernel plays this
    role on real TPU); without it, kimi-k2's train_4k cell materialized
    1.1 TB of fp32 scores per layer. q: (B,T,Hq,hd); k: (B,S,Hkv,hd);
    v: (B,S,Hkv,dv); ``scale`` defaults to 1/sqrt(hd)."""
    B, T, Hq, hd = q.shape
    _, S, Hkv, _ = k.shape
    dv = v.shape[-1]
    g = Hq // Hkv
    blk = min(blk, S)
    if S % blk:
        blk = S  # fallback: single block
    nb = S // blk
    qg = q.reshape(B, T, Hkv, g, hd).astype(jnp.float32)
    qg = qg / jnp.sqrt(float(hd)) if scale is None else qg * scale
    kb = jnp.moveaxis(k.reshape(B, nb, blk, Hkv, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nb, blk, Hkv, dv), 1, 0)
    qpos = jnp.arange(T)

    @jax.checkpoint
    def body(carry, inp):
        m, l, acc = carry
        k_b, v_b, b_idx = inp
        s = jnp.einsum("bthgd,bkhd->bhgtk", qg, k_b.astype(jnp.float32))
        if causal:
            kpos = b_idx * blk + jnp.arange(blk)
            mask = qpos[:, None] >= kpos[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + jnp.sum(p, axis=-1)
        upd = jnp.einsum("bhgtk,bkhd->bhgtd", p, v_b.astype(jnp.float32))
        acc_new = acc * corr[..., None] + upd
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hkv, g, T), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, g, T), jnp.float32)
    a0 = jnp.zeros((B, Hkv, g, T, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kb, vb, jnp.arange(nb)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, 3, 1).reshape(B, T, Hq, dv).astype(q.dtype)


def gqa_scores_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool) -> jax.Array:
    """Reference XLA attention. q: (B, Tq, Hq, hd), k/v: (B, Tk, Hkv, hd);
    causal aligns q[0] with k[0]."""
    B, Tq, Hq, hd = q.shape
    _, Tk, Hkv, _ = k.shape
    g = Hq // Hkv
    qg = q.reshape(B, Tq, Hkv, g, hd)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(float(hd))
    if Tq > 1:
        # XLA-fallback memory control: shard the S^2 score tensor's query
        # dim over "model" (head counts are too uneven across archs to rely
        # on head sharding). The TPU serving path never materializes this —
        # the Pallas flash kernel streams KV blocks instead.
        scores = constrain(scores, "batch", None, None, "q_seq", None)
    if causal:
        mask = jnp.arange(Tq)[:, None] >= jnp.arange(Tk)[None, :]
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(B, Tq, Hq, hd).astype(q.dtype)


def kv_zeros(cfg: ArchConfig, n_layers: int, batch: int,
             max_len: int) -> jax.Array:
    """An empty stacked K or V cache, (n_layers, batch, Hkv, hd, max_len)."""
    return jnp.zeros((n_layers, batch, cfg.n_kv_heads, cfg.hd, max_len),
                     cfg.compute_dtype)


def latent_zeros(cfg: ArchConfig, n_layers: int, batch: int,
                 max_len: int) -> jax.Array:
    """An empty stacked MLA latent cache, (n_layers, batch,
    kv_lora_rank + qk_rope_dim, max_len): per position the normalised
    c_kv, then the roped k_pe."""
    return jnp.zeros((n_layers, batch, cfg.kv_lora_rank + cfg.qk_rope_dim,
                      max_len), cfg.compute_dtype)


def write_rows(cache: jax.Array, rows: jax.Array, layer: jax.Array,
               index: jax.Array) -> jax.Array:
    """Write ``rows`` (B, T, *feat) into the stacked cache
    (L, B, *feat, S) at ``layer``, slot b's rows from position
    ``index[b]``: the KV caches' (Hkv, hd) or the latent cache's one
    feature axis. One dynamic_update_slice per slot keeps the write in
    place in the layer scan's carried cache (a single scatter over the
    slots made XLA pick another layout and copy the whole cache)."""
    rows = jnp.moveaxis(rows, 1, -1).astype(cache.dtype)   # (B,*feat,T)
    zeros = (0,) * (rows.ndim - 2)
    for b in range(rows.shape[0]):
        cache = jax.lax.dynamic_update_slice(
            cache, rows[b][None, None], (layer, b, *zeros, index[b]))
    return cache


def attention_block(p: AttnParams, x: jax.Array, cfg: ArchConfig, *,
                    causal: bool = True,
                    positions: jax.Array | None = None,
                    kv_cache: tuple[jax.Array, jax.Array] | None = None,
                    layer: jax.Array | None = None,
                    cache_index: jax.Array | None = None,
                    cross_kv: tuple[jax.Array, jax.Array] | None = None,
                    use_rope: bool = True,
                    ) -> tuple[jax.Array, tuple[jax.Array, jax.Array] | None]:
    """One attention sublayer (no residual/norm). Modes:
      * train/prefill: kv_cache None -> self-attention over x;
      * cached: kv_cache (K, V), the stacked (L, B, Hkv, hd, S) caches,
        with ``layer`` and cache_index -> write this call's rows at
        ``layer``, then attend. T == 1 (decode) attends the cache; T > 1
        (prefill) attends its own K/V, the slot's whole prefix under the
        contract that a prefill starts at index 0 on a fresh slot;
      * cross: cross_kv given -> encoder-decoder attention (ignores cache).
    Returns (out, updated_cache).
    """
    B, T, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (x @ p.wq.astype(cfg.compute_dtype)).reshape(B, T, hq, hd)
    if cross_kv is not None:
        k, v = cross_kv
        out = gqa_scores_attention(q, k, v, causal=False)
        return out.reshape(B, T, hq * hd) @ p.wo.astype(cfg.compute_dtype), None
    k = (x @ p.wk.astype(cfg.compute_dtype)).reshape(B, T, hkv, hd)
    v = (x @ p.wv.astype(cfg.compute_dtype)).reshape(B, T, hkv, hd)

    if positions is None:
        pos = jnp.arange(T)[None, :] if cache_index is None else \
            (cache_index[:, None] + jnp.arange(T)[None, :])
    else:
        pos = positions
    if use_rope:
        sin, cos = rope_angles(pos, hd, cfg.rope_theta)
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)

    new_cache = None
    if kv_cache is not None:
        ck = write_rows(kv_cache[0], k, layer, cache_index)
        cv = write_rows(kv_cache[1], v, layer, cache_index)
        new_cache = (ck, cv)
        if T == 1:
            # decode: every valid cached position is <= the current one,
            # so length masking alone is exact (no causal matrix needed).
            # Hot path -> Pallas decode-attention kernel on TPU.
            from repro.kernels import ops as kops
            out = kops.decode_attention(q[:, 0], ck, cv, layer,
                                        cache_index + 1)[:, None]
        elif T >= 1024:
            # long prefill: flash-style chunked lowering
            out = chunked_attention(q, k, v, causal=True)
        else:
            out = gqa_scores_attention(q, k, v, causal=True)
    else:
        if causal and q.shape[1] == k.shape[1]:
            # train/prefill hot path -> Pallas flash attention on TPU
            from repro.kernels import ops as kops
            out = kops.flash_attention(q, k, v, causal=True)
        else:
            out = gqa_scores_attention(q, k, v, causal=causal)
    out = out.reshape(B, T, hq * hd) @ p.wo.astype(cfg.compute_dtype)
    return out, new_cache


# ---------------------------------------------------------------------------
# MLA: multi-head latent attention (DeepSeek-V3 form, as Kimi-K2 ships it)
# ---------------------------------------------------------------------------
class MLAParams(NamedTuple):
    wq_a: jax.Array     # (d, q_lora)
    q_norm: jax.Array   # (q_lora,)
    wq_b: jax.Array     # (q_lora, H*(nope+rope))
    wkv_a: jax.Array    # (d, kv_lora + rope)   c_kv, then the shared k_pe
    kv_norm: jax.Array  # (kv_lora,)
    wkv_b: jax.Array    # (kv_lora, H*(nope+v)) per head k_nope, then v
    wo: jax.Array       # (H*v, d)


def init_mla(key, cfg: ArchConfig) -> MLAParams:
    kqa, kqb, kkva, kkvb, ko = jax.random.split(key, 5)
    d, h, ql, kl = cfg.d_model, cfg.n_heads, cfg.q_lora_rank, \
        cfg.kv_lora_rank
    dt = cfg.param_dtype
    return MLAParams(
        dense_init(kqa, (d, ql), dtype=dt), jnp.ones((ql,), dt),
        dense_init(kqb, (ql, h * cfg.qk_head_dim), dtype=dt),
        dense_init(kkva, (d, kl + cfg.qk_rope_dim), dtype=dt),
        jnp.ones((kl,), dt),
        dense_init(kkvb, (kl, h * (cfg.qk_nope_dim + cfg.v_head_dim)),
                   dtype=dt),
        dense_init(ko, (h * cfg.v_head_dim, d), dtype=dt))


def mla_block(p: MLAParams, x: jax.Array, cfg: ArchConfig, *,
              cache: jax.Array | None = None, layer: jax.Array | None = None,
              cache_index: jax.Array | None = None,
              ) -> tuple[jax.Array, jax.Array | None]:
    """One MLA sublayer (no residual/norm). x: (B, T, d).

    Without a cache (training) and for a prefill (T > 1, a fresh slot from
    index 0, as the engine prefills) it runs non-absorbed: per head q and
    k of qk_nope + qk_rope, v of v_head_dim, causal flash attention over
    the call's own K/V. With a cache it first writes each position's
    latent row (the normalised c_kv, then the roped k_pe) into the stacked
    latent cache at ``layer``; a decode step (T == 1) then attends that
    cache absorbed: q_nope goes through kv_b's key half into the latent
    space, the kernel reads each latent tile once for every head, and
    kv_b's value half maps the result back. RoPE is rotate-half with
    YaRN's frequencies; the softmax scale carries YaRN's mscale squared."""
    B, T, _ = x.shape
    h, nope, rope, dv = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                         cfg.v_head_dim)
    kl = cfg.kv_lora_rank
    cd = cfg.compute_dtype
    scale = mla_softmax_scale(cfg)
    pos = jnp.arange(T)[None, :] if cache_index is None else \
        cache_index[:, None] + jnp.arange(T)[None, :]
    sin, cos = rope_angles(pos, rope, cfg.rope_theta, cfg)

    q = rmsnorm(x @ p.wq_a.astype(cd), p.q_norm, cfg.norm_eps)
    q = (q @ p.wq_b.astype(cd)).reshape(B, T, h, nope + rope)
    q_nope, q_pe = q[..., :nope], apply_rope(q[..., nope:], sin, cos)
    ckv = x @ p.wkv_a.astype(cd)
    c_kv = rmsnorm(ckv[..., :kl], p.kv_norm, cfg.norm_eps)      # (B,T,kl)
    k_pe = apply_rope(ckv[..., None, kl:], sin, cos)            # (B,T,1,r)
    w_kv = p.wkv_b.astype(cd).reshape(kl, h, nope + dv)

    new_cache = None
    if cache is not None:
        new_cache = write_rows(
            cache, jnp.concatenate([c_kv, k_pe[:, :, 0]], axis=-1), layer,
            cache_index)
    from repro.kernels import ops as kops
    if cache is not None and T == 1:
        q_lat = jnp.einsum("bhn,khn->bhk", q_nope[:, 0], w_kv[..., :nope])
        qf = jnp.concatenate([q_lat, q_pe[:, 0]], axis=-1)    # (B,h,kl+r)
        o_lat = kops.mla_decode_attention(qf, new_cache, layer,
                                          cache_index + 1, scale=scale,
                                          rank=kl)
        out = jnp.einsum("bhk,khv->bhv", o_lat, w_kv[..., nope:])[:, None]
    else:
        kv = jnp.einsum("btk,khn->bthn", c_kv, w_kv)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe, (B, T, h, rope))], -1)
        qf = jnp.concatenate([q_nope, q_pe], axis=-1)
        out = kops.flash_attention(qf, k, kv[..., nope:], causal=True,
                                   scale=scale, **_prefill_kernel_args(T))
    return out.reshape(B, T, h * dv) @ p.wo.astype(cd), new_cache


def _prefill_kernel_args(T: int) -> dict:
    """Blocks of the Pallas prefill kernel: 512 x 512 where they divide
    the prompt, so a long prompt runs a few thousand grid steps and not a
    million; the kernel's defaults where those divide it; else the XLA
    form, since the kernel tiles whole blocks."""
    if T % 512 == 0:
        return {"blk_q": 512, "blk_k": 512}
    return {} if T % 128 == 0 or T <= 128 else {"backend": "xla"}
