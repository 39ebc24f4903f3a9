"""Float32 reference of a dense decoder (GQA, RoPE, SwiGLU, RMSNorm),
layer by layer, and the logit-gap comparison.

Weights are made again from the seed with the program's documented random
initialisation (``weights`` in the configuration file): a key split into
embedding, layer and head keys, one key per layer, normal draws scaled by
0.02 (embedding) or 1/sqrt(fan-in) (projections), rounded to the served
dtype (bfloat16) and computed here in float32. Norm scales are ones. One
layer's weights exist at a time, so the reference fits beside nothing else
on a 16 GB chip at phi3-mini's widths.

Equations (Llama/Phi-3 form): h = x + Attn(RMSNorm(x)); out = h +
W_down(silu(W_gate n) * W_up n), n = RMSNorm(h); rotate-half RoPE with
base ``rope_theta``; causal softmax attention with 1/sqrt(head_dim)
scaling; grouped KV heads shared by ``heads / kv_heads`` query heads.
Every product runs at ``Precision.HIGHEST``.

The control computes the same forward in fp8 (float8_e4m3fn), the step
below the configuration's bfloat16 that a later change might take: every
linear layer's weights rounded with one scale per output channel and its
input activations with one scale per token (each absmax mapped to 448);
attention's own products stay in float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _dims(config: dict) -> dict:
    c = config["config"]
    heads = int(c["num_attention_heads"])
    d = int(c["hidden_size"])
    return {"L": int(c["num_hidden_layers"]), "d": d, "hq": heads,
            "hkv": int(c["num_key_value_heads"]),
            "hd": int(c.get("head_dim") or d // heads),
            "ff": int(c["intermediate_size"]), "V": int(c["vocab_size"]),
            "theta": float(c.get("rope_theta", 10000.0)),
            "eps": float(c.get("rms_norm_eps", 1e-5))}


def _dense(key, shape, fan_in):
    w = jax.random.normal(key, shape) / np.sqrt(fan_in)
    return w.astype(jnp.bfloat16).astype(jnp.float32)


def _fp8(x, axis):
    """Round to float8_e4m3fn with one scale per slice along ``axis``
    (its absmax mapped to the format's largest value, 448)."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, fp8: bool):
    """A linear layer: x (..., in) @ w (in, out); with ``fp8`` the input
    is rounded per token (the weights were rounded per column)."""
    return jnp.dot(_fp8(x, -1) if fp8 else x, w, precision=HI)


class Weights:
    """The served weights, made again from the seed one piece at a time."""

    def __init__(self, config: dict, seed: int, *, fp8: bool = False):
        self.m = m = _dims(config)
        self.fp8 = fp8
        ke, kl, ko = jax.random.split(jax.random.key(seed), 3)
        self._ke, self._ko = ke, ko
        self._layer_keys = jax.random.split(kl, m["L"])
        d, hq, hkv, hd, ff = m["d"], m["hq"], m["hkv"], m["hd"], m["ff"]

        def layer(key):
            k1, k2 = jax.random.split(key)
            kq, kk, kv, ko_ = jax.random.split(k1, 4)
            g, u, dn = jax.random.split(k2, 3)
            ws = {"wq": _dense(kq, (d, hq * hd), d),
                  "wk": _dense(kk, (d, hkv * hd), d),
                  "wv": _dense(kv, (d, hkv * hd), d),
                  "wo": _dense(ko_, (hq * hd, d), hq * hd),
                  "w_gate": _dense(g, (d, ff), d),
                  "w_up": _dense(u, (d, ff), d),
                  "w_down": _dense(dn, (ff, d), ff)}
            if fp8:
                ws = {k: _fp8(v, 0) for k, v in ws.items()}
            return ws

        def head(key):
            w = _dense(key, (d, m["V"]), d)
            return _fp8(w, 0) if fp8 else w

        def embed(key):
            e = (jax.random.normal(key, (m["V"], d)) * 0.02).astype(
                jnp.bfloat16).astype(jnp.float32)
            return _fp8(e, 1) if fp8 else e

        self._layer = jax.jit(layer)
        self._head = jax.jit(head)
        self._embed = jax.jit(embed)

    def embed(self):
        return self._embed(self._ke)

    def head(self):
        return self._head(self._ko)

    def layer(self, i: int) -> dict:
        return self._layer(self._layer_keys[i])


def _rmsnorm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """x: (T, H, hd); rotate-half form."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv
    s, c = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _make_layer(m: dict, fp8: bool):
    hq, hkv, hd = m["hq"], m["hkv"], m["hd"]
    g = hq // hkv

    def one(w, x):                         # x: (T, d), one sequence
        T = x.shape[0]
        pos = jnp.arange(T)
        n = _rmsnorm(x, m["eps"])
        q = _mm(n, w["wq"], fp8).reshape(T, hq, hd)
        k = _mm(n, w["wk"], fp8).reshape(T, hkv, hd)
        v = _mm(n, w["wv"], fp8).reshape(T, hkv, hd)
        q, k = _rope(q, pos, m["theta"]), _rope(k, pos, m["theta"])
        qg = q.reshape(T, hkv, g, hd)
        s = jnp.einsum("thgd,khd->hgtk", qg, k,
                       precision=HI) / math.sqrt(hd)
        causal = pos[:, None] >= pos[None, :]
        s = jnp.where(causal, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        a = jnp.einsum("hgtk,khd->thgd", p, v, precision=HI)
        h = x + _mm(a.reshape(T, hq * hd), w["wo"], fp8)
        n = _rmsnorm(h, m["eps"])
        gt = _mm(n, w["w_gate"], fp8)
        up = _mm(n, w["w_up"], fp8)
        return h + _mm(jax.nn.silu(gt) * up, w["w_down"], fp8)

    return jax.jit(lambda w, xs: jax.lax.map(lambda x: one(w, x), xs))


def logits_at(config: dict, seed: int, seqs: list[np.ndarray], *,
              fp8: bool = False):
    """Per sequence, the (T, V) float32 logits of the reference (or of
    the fp8 control) over the whole sequence. Sequences are padded on the
    right to one length, which causal attention leaves without effect."""
    w = Weights(config, seed, fp8=fp8)
    m = w.m
    T = max(len(s) for s in seqs)
    toks = np.zeros((len(seqs), T), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    h = w.embed()[jnp.asarray(toks)]
    layer = _make_layer(m, fp8)
    for i in range(m["L"]):
        h = layer(w.layer(i), h)
    head = w.head()
    out = []
    norm = jax.jit(lambda x, hd_: _mm(_rmsnorm(x, m["eps"]), hd_, fp8))
    for i, s in enumerate(seqs):
        out.append(norm(h[i, :len(s)], head))
    return out


def _gaps_at(rows, picked):
    """Per position, the reference's best logit less its logit of the
    picked token (0 where they agree)."""
    return jnp.max(rows, axis=-1) - jnp.take_along_axis(
        rows, picked[:, None], axis=-1)[:, 0]


def _summary(per_pos: list, prefix: str) -> dict:
    g = np.concatenate([np.asarray(x) for x in per_pos])
    return {f"{prefix}gap_max": float(g.max()),
            f"{prefix}gap_mean": float(g.mean()),
            f"{prefix}mismatch_share": float((g > 0).mean())}


def gaps(config: dict, seed: int, items: list[dict], *,
         control: bool = False) -> dict:
    """The comparison. ``items``: ``{"prompt": [...], "served": [...]}``
    (greedy tokens). For every served token, the gap by which the
    reference's logit of it lies below the reference's best at that
    position; ``gap_max`` is the widest. With ``control``, the same read
    for the token that the fp8 control puts first at each of those
    positions (``control_gap_max``). The mean gap and the share of
    positions that disagree with the reference are printed beside them."""
    seqs = [np.asarray(list(it["prompt"]) + list(it["served"][:-1]),
                       np.int32) for it in items]
    ref = logits_at(config, seed, seqs)
    rows = [lg[len(it["prompt"]) - 1:len(it["prompt"]) - 1
               + len(it["served"])] for it, lg in zip(items, ref)]
    per_pos = [_gaps_at(r, jnp.asarray(np.asarray(it["served"], np.int32)))
               for it, r in zip(items, rows)]
    out = {**_summary(per_pos, ""),
           "gap_per_item": [float(jnp.max(g)) for g in per_pos],
           "served_tokens": int(sum(len(it["served"]) for it in items))}
    if control:
        ctl = logits_at(config, seed, seqs, fp8=True)
        per_pos = [_gaps_at(r, jnp.argmax(lc[len(it["prompt"]) - 1:
                                             len(it["prompt"]) - 1
                                             + len(it["served"])], axis=-1))
                   for it, r, lc in zip(items, rows, ctl)]
        out.update(_summary(per_pos, "control_"))
    return out
