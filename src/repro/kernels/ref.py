"""Pure-jnp oracles for every Pallas kernel (independent implementations —
the SSD oracle is the *sequential* recurrence, not the chunked algorithm,
so it cross-checks the chunking math itself)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        scale: float | None = None) -> jax.Array:
    """q: (B, T, Hq, hd); k: (B, S, Hkv, hd); v: (B, S, Hkv, dv); GQA by
    head broadcast; ``scale`` defaults to 1/sqrt(hd)."""
    from repro.distributed.logical import constrain
    B, T, Hq, hd = q.shape
    _, S, Hkv, _ = k.shape
    dv = v.shape[-1]
    g = Hq // Hkv
    qg = q.reshape(B, T, Hkv, g, hd).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    scores = jnp.einsum("bthgd,bshd->bhgts", qg, kf)
    scores = scores / jnp.sqrt(float(hd)) if scale is None \
        else scores * scale
    if T > 1:
        # memory control under GSPMD (no-op without an installed policy):
        # shard the S^2 tensor's query dim — see models/attention.py note
        scores = constrain(scores, "batch", None, None, "q_seq", None)
    if causal:
        mask = jnp.arange(T)[:, None] >= jnp.arange(S)[None, :]
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgts,bshd->bthgd", p, v.astype(jnp.float32))
    return out.reshape(B, T, Hq, dv).astype(q.dtype)


def decode_attention_ref(q: jax.Array, k_cache: jax.Array,
                         v_cache: jax.Array, layer: jax.Array,
                         lengths: jax.Array) -> jax.Array:
    """Single-token GQA decode. q: (B, Hq, hd); k_cache/v_cache: the
    stacked (L, B, Hkv, hd, S) cache, read at ``layer``; lengths: (B,)
    valid KV prefix. Returns (B, Hq, hd)."""
    B, Hq, hd = q.shape
    _, _, Hkv, _, S = k_cache.shape
    g = Hq // Hkv
    k, v = k_cache[layer], v_cache[layer]                    # (B,Hkv,hd,S)
    qg = q.reshape(B, Hkv, g, hd).astype(jnp.float32)
    scores = jnp.einsum("bhgd,bhds->bhgs", qg,
                        k.astype(jnp.float32)) / jnp.sqrt(float(hd))
    valid = jnp.arange(S)[None, :] < lengths[:, None]        # (B, S)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bhds->bhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Hq, hd).astype(q.dtype)


def mla_decode_attention_ref(q: jax.Array, cache: jax.Array,
                             layer: jax.Array, lengths: jax.Array, *,
                             scale: float, rank: int) -> jax.Array:
    """Absorbed MLA decode. q: (B, H, rank + rope); cache: the stacked
    latent cache (L, B, rank + rope, S), read at ``layer``; lengths: (B,)
    valid prefix. Returns (B, H, rank), the weighted sum of the cache's
    first ``rank`` rows."""
    c = cache[layer].astype(jnp.float32)                    # (B, D, S)
    s = jnp.einsum("bhd,bds->bhs", q.astype(jnp.float32), c) * scale
    valid = jnp.arange(c.shape[-1])[None, :] < lengths[:, None]
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,brs->bhr", p, c[:, :rank]).astype(q.dtype)


def ssd_ref(u: jax.Array, loga: jax.Array, Bm: jax.Array, Cm: jax.Array,
            h0: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
    """SEQUENTIAL SSD recurrence (the oracle the chunked kernel must match).
    u: (B, T, H, P) dt-weighted inputs; loga: (B, T, H) log decay;
    Bm/Cm: (B, T, N). Returns (y (B,T,H,P), final_state (B,H,N,P))."""
    Bsz, T, H, P = u.shape
    N = Bm.shape[-1]
    h_init = h0 if h0 is not None else jnp.zeros((Bsz, H, N, P), jnp.float32)

    def step(h, inp):
        u_t, la_t, b_t, c_t = inp
        a = jnp.exp(la_t)                                     # (B, H)
        h = h * a[..., None, None] + jnp.einsum("bn,bhp->bhnp", b_t, u_t)
        y = jnp.einsum("bn,bhnp->bhp", c_t, h)
        return h, y

    xs = (jnp.moveaxis(u.astype(jnp.float32), 1, 0),
          jnp.moveaxis(loga.astype(jnp.float32), 1, 0),
          jnp.moveaxis(Bm.astype(jnp.float32), 1, 0),
          jnp.moveaxis(Cm.astype(jnp.float32), 1, 0))
    hT, ys = jax.lax.scan(step, h_init, xs)
    return jnp.moveaxis(ys, 0, 1).astype(u.dtype), hT
