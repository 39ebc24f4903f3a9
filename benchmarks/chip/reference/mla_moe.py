"""Float32 reference of a latent-attention MoE decoder (Kimi-K2-Instruct,
the DeepSeek-V3 block), layer by layer, and the logit-gap comparison.

Equations: DeepSeek-V3's ``modeling_deepseek.py``, which Kimi-K2 ships,
in its non-absorbed form:

- attention (MLA): q = W_qb RMSNorm(W_qa n), split per head into q_nope
  (qk_nope_head_dim) and q_pe (qk_rope_head_dim); [c_kv, k_pe] = W_kva n;
  [k_nope, v] per head = W_kvb RMSNorm(c_kv); k_pe is one head shared by
  all; q_pe and k_pe rotated (rotate-half, see ``departures``) with YaRN's
  frequencies: the original ones below the correction range of
  ``beta_fast``/``beta_slow`` over ``original_max_position_embeddings``,
  divided by ``factor`` above it, a linear ramp between; cos and sin
  times mscale(factor, mscale) / mscale(factor, mscale_all_dim);
  causal softmax over [q_nope, q_pe] . [k_nope, k_pe] with the scale
  1/sqrt(qk_nope + qk_rope) times mscale(factor, mscale_all_dim)^2,
  where mscale(s, m) = 0.1 m ln s + 1; out = W_o (softmax . v);
- the first ``first_k_dense_replace`` layers: a SwiGLU of
  ``intermediate_size``;
- the others (noaux_tc): scores = sigmoid(W_r n) over all the published
  ``n_routed_experts`` (read from the file's ``published``); the top
  ``num_experts_per_tok`` of scores + bias are picked, weighted by their
  unbiased scores normalised to sum 1 (``norm_topk_prob``), times
  ``routed_scaling_factor``; each expert a SwiGLU of
  ``moe_intermediate_size``; plus ``n_shared_experts`` shared experts as
  one SwiGLU of that width times their count, for every token. Only the
  experts this chip holds (the configuration's ``n_routed_experts`` of
  them, from ``assumed.first_held_expert``) add their part, as in the
  program (model-configs §4);
- pre-norm residual blocks, RMSNorm with ``rms_norm_eps``, untied head.

Weights are made again from the seed as the configuration's ``weights``
says, one layer at a time, rounded to bfloat16 and computed in float32;
every product runs at ``Precision.HIGHEST``. Attention runs in blocks of
queries against every key (causal by mask), so a 16 k prompt fits.

The comparison leaves out a served token whose position the reference
routes at a tie: a held expert within ``ROUTING_TIE`` of the top-k
boundary in some MoE layer, which bfloat16's rounding may break either
way (``gaps``).

The control computes the same forward in fp8 (float8_e4m3fn), the step
below the served bfloat16: every linear layer but the router (which fp8
deployments keep wide) with its weights rounded one scale per output
channel and its inputs one scale per token; attention's own products and
the norms stay in float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference.dense import (HI, _dense, _fp8, _gaps_at, _mm, _rmsnorm,
                             _summary)

#: queries per block of the blocked attention
Q_BLOCK = 256
#: a held expert this near the top-k boundary (sigmoid-score units) is a
#: routing tie that bfloat16 may break either way (``gaps``). At the
#: published width on a TPU v5e the served bf16 program moves a held
#: expert's distance to the boundary off float32's by 0.003-0.006 on
#: average (first to last MoE layer), 0.014-0.034 at the 99.9th
#: percentile and 0.020-0.052 at most; every served token whose gap
#: showed a flipped expert (over 0.2) lay within 0.0025 of it (the
#: configuration's ``check.reasons``)
ROUTING_TIE = 0.01

#: published ``config`` key -> the program's ``ArchConfig`` field that the
#: width check holds equal to it (names only); the three cut keys are
#: checked at their cut values
WIDTHS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "intermediate_size": "d_ff",
          "moe_intermediate_size": "moe_d_ff",
          "num_attention_heads": "n_heads",
          "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
          "qk_nope_head_dim": "qk_nope_dim",
          "qk_rope_head_dim": "qk_rope_dim", "v_head_dim": "v_head_dim",
          "num_experts_per_tok": "top_k",
          "n_shared_experts": "n_shared_experts",
          "first_k_dense_replace": "n_dense_layers",
          "n_routed_experts": "experts_held", "vocab_size": "vocab_size"}


def published(c: dict) -> dict:
    """The configuration's keys: MLA derives none the check reads."""
    return c


def dims(config: dict) -> dict:
    c = config["config"]
    y = c["rope_scaling"]
    return {"L": int(c["num_hidden_layers"]), "d": int(c["hidden_size"]),
            "h": int(c["num_attention_heads"]),
            "ql": int(c["q_lora_rank"]), "kl": int(c["kv_lora_rank"]),
            "nope": int(c["qk_nope_head_dim"]),
            "rope": int(c["qk_rope_head_dim"]), "dv": int(c["v_head_dim"]),
            "ff": int(c["intermediate_size"]),
            "eff": int(c["moe_intermediate_size"]),
            "dense": int(c["first_k_dense_replace"]),
            "E": int(config["published"]["n_routed_experts"]),
            "held": int(c["n_routed_experts"]),
            "lo": int(config["assumed"]["first_held_expert"]),
            "k": int(c["num_experts_per_tok"]),
            "shared": int(c["n_shared_experts"]),
            "scale_r": float(c["routed_scaling_factor"]),
            "bias_std": float(config["assumed"]["router_bias_std"]),
            "V": int(c["vocab_size"]), "theta": float(c["rope_theta"]),
            "eps": float(c["rms_norm_eps"]), "yarn": y}


def _mlp(key, d, ff):
    g, u, dn = jax.random.split(key, 3)
    return {"gate": _dense(g, (d, ff), d), "up": _dense(u, (d, ff), d),
            "down": _dense(dn, (ff, d), ff)}


class Weights:
    """The served weights, made again from the seed one layer at a time:
    ``jax.random.key(seed)`` split into embedding, dense-layer, MoE-layer
    and head keys (``weights`` in the configuration file)."""

    def __init__(self, config: dict, seed: int, *, fp8: bool = False):
        self.m = m = dims(config)
        ke, kd, kl, ko = jax.random.split(jax.random.key(seed), 4)
        self._ke, self._ko = ke, ko
        self._dense_keys = jax.random.split(kd, m["dense"])
        self._moe_keys = jax.random.split(kl, m["L"] - m["dense"])
        d, h, ql, kvl = m["d"], m["h"], m["ql"], m["kl"]

        def attn(key):
            kqa, kqb, kkva, kkvb, ko_ = jax.random.split(key, 5)
            return {"wq_a": _dense(kqa, (d, ql), d),
                    "wq_b": _dense(kqb, (ql, h * (m["nope"] + m["rope"])),
                                   ql),
                    "wkv_a": _dense(kkva, (d, kvl + m["rope"]), d),
                    "wkv_b": _dense(kkvb, (kvl, h * (m["nope"] + m["dv"])),
                                    kvl),
                    "wo": _dense(ko_, (h * m["dv"], d), h * m["dv"])}

        def dense_layer(key):
            k1, k2 = jax.random.split(key)
            return {**attn(k1), "mlp": _mlp(k2, d, m["ff"])}

        def moe_layer(key):
            k1, k2 = jax.random.split(key)
            kr, kx, ks, kb = jax.random.split(k2, 4)
            experts = [_mlp(jax.random.fold_in(kx, e), d, m["eff"])
                       for e in range(m["lo"], m["lo"] + m["held"])]
            return {**attn(k1),
                    "router": jax.random.normal(kr, (d, m["E"]))
                    / np.sqrt(d),
                    "bias": jax.random.normal(kb, (m["E"],))
                    * m["bias_std"],
                    "experts": experts,
                    "shared": _mlp(ks, d, m["eff"] * m["shared"])}

        def rounded(fn):
            return jax.jit(lambda key: _round_tree(fn(key)) if fp8
                           else fn(key))

        self._dense_layer = rounded(dense_layer)
        self._moe_layer = rounded(moe_layer)
        self._head = jax.jit(lambda k: _round(_dense(k, (d, m["V"]), d),
                                              fp8, 0))
        self._embed = jax.jit(lambda k: _round(
            (jax.random.normal(k, (m["V"], d)) * 0.02).astype(
                jnp.bfloat16).astype(jnp.float32), fp8, 1))

    def embed(self):
        return self._embed(self._ke)

    def head(self):
        return self._head(self._ko)

    def layer(self, i: int) -> dict:
        m = self.m
        if i < m["dense"]:
            return self._dense_layer(self._dense_keys[i])
        return self._moe_layer(self._moe_keys[i - m["dense"]])


def _round(w, fp8: bool, axis: int):
    return _fp8(w, axis) if fp8 else w


def _round_tree(ws: dict) -> dict:
    """fp8 per output channel for every linear layer but the router."""
    return {k: v if k in ("router", "bias") else
            jax.tree.map(lambda w: _fp8(w, 0), v) for k, v in ws.items()}


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _inv_freq(m: dict) -> np.ndarray:
    """YaRN's inverse frequencies of the rotary part, float32."""
    y, dim, base = m["yarn"], m["rope"], m["theta"]
    orig = y["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / \
            (2 * math.log(base))
    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    expo = np.arange(0, dim, 2, dtype=np.float32) / dim
    extra = 1.0 / base ** expo
    inter = 1.0 / (y["factor"] * base ** expo)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def _rope(x, pos, m):
    """x: (T, H, r), rotate-half, YaRN frequencies and cos/sin factor."""
    y = m["yarn"]
    f = _yarn_mscale(y["factor"], y["mscale"]) / \
        _yarn_mscale(y["factor"], y["mscale_all_dim"])
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(_inv_freq(m))
    s, c = (jnp.sin(ang) * f)[:, None, :], (jnp.cos(ang) * f)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def softmax_scale(m: dict) -> float:
    y = m["yarn"]
    ms = _yarn_mscale(y["factor"], y["mscale_all_dim"]) \
        if y.get("mscale_all_dim") else 1.0
    return (m["nope"] + m["rope"]) ** -0.5 * ms * ms


def _swiglu(w, x, fp8):
    return _mm(jax.nn.silu(_mm(x, w["gate"], fp8)) * _mm(x, w["up"], fp8),
               w["down"], fp8)


def _attention(m: dict, w: dict, x, fp8: bool):
    """MLA, non-absorbed, over one sequence x: (T, d)."""
    T = x.shape[0]
    h, nope, rope, dv, kl = m["h"], m["nope"], m["rope"], m["dv"], m["kl"]
    pos = jnp.arange(T)
    q = _mm(_rmsnorm(_mm(x, w["wq_a"], fp8), m["eps"]), w["wq_b"], fp8)
    q = q.reshape(T, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, m)], -1)
    ckv = _mm(x, w["wkv_a"], fp8)
    kv = _mm(_rmsnorm(ckv[:, :kl], m["eps"]), w["wkv_b"], fp8)
    kv = kv.reshape(T, h, nope + dv)
    k_pe = _rope(ckv[:, None, kl:], pos, m)                   # (T, 1, r)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (T, h, rope))], -1)
    v = kv[..., nope:]
    scale = softmax_scale(m)
    nb = -(-T // Q_BLOCK)
    qp = jnp.pad(q, ((0, nb * Q_BLOCK - T), (0, 0), (0, 0)))

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(qp, i * Q_BLOCK, Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) * scale
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(qpos[:, None] >= pos[None, :], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                          precision=HI)

    a = jax.lax.map(block, jnp.arange(nb)).reshape(nb * Q_BLOCK, h * dv)
    return _mm(a[:T], w["wo"], fp8)


def _moe(m: dict, w: dict, n, fp8: bool):
    """noaux_tc routing over all experts; the held experts' parts (each
    applied to every token and kept where the token picked it), plus the
    shared expert. Also each token's routing margin: how far the held
    expert nearest the top-k boundary (midway between the k-th and the
    (k+1)-th biased score) lies from it."""
    scores = jax.nn.sigmoid(jnp.dot(n, w["router"], precision=HI))
    choice = scores + w["bias"]
    top, ids = jax.lax.top_k(choice, m["k"] + 1)
    ids = ids[:, :m["k"]]
    boundary = (top[:, -2] + top[:, -1]) / 2
    held = choice[:, m["lo"]:m["lo"] + m["held"]]
    margin = jnp.min(jnp.abs(held - boundary[:, None]), axis=-1)
    gates = jnp.take_along_axis(scores, ids, axis=-1)
    gates = gates / (gates.sum(-1, keepdims=True) + 1e-20) * m["scale_r"]
    out = _swiglu(w["shared"], n, fp8)
    for j, ew in enumerate(w["experts"]):
        g = jnp.sum(jnp.where(ids == m["lo"] + j, gates, 0.0), axis=-1)
        out = out + g[:, None] * _swiglu(ew, n, fp8)
    return out, margin


def _make_layer(m: dict, fp8: bool, dense: bool):
    def one(w, x):                           # x: (T, d), one sequence
        h = x + _attention(m, w, _rmsnorm(x, m["eps"]), fp8)
        n = _rmsnorm(h, m["eps"])
        if dense:
            return h + _swiglu(w["mlp"], n, fp8), jnp.full(x.shape[:1],
                                                           jnp.inf)
        f, margin = _moe(m, w, n, fp8)
        return h + f, margin
    return jax.jit(one)


def forward(config: dict, seed: int, seqs: list[np.ndarray], *,
            fp8: bool = False, starts: list[int] | None = None):
    """Per sequence, each on its own: the float32 logits of the reference
    (or of the fp8 control), (T - start, V) from position ``starts[i]``
    (0 by default) on, and each position's least routing margin over the
    MoE layers (``_moe``), (T - start,)."""
    w = Weights(config, seed, fp8=fp8)
    m = w.m
    emb = w.embed()
    hs = [emb[jnp.asarray(s)] for s in seqs]
    margins = [jnp.full(len(s), jnp.inf) for s in seqs]
    del emb
    layers = {dense: _make_layer(m, fp8, dense) for dense in (True, False)}
    for i in range(m["L"]):
        lw = w.layer(i)
        f = layers[i < m["dense"]]
        out = [f(lw, x) for x in hs]
        hs = [h for h, _ in out]
        margins = [jnp.minimum(a, b) for a, (_, b) in zip(margins, out)]
        del lw, out
    head = w.head()
    norm = jax.jit(lambda x, hd_: _mm(_rmsnorm(x, m["eps"]), hd_, fp8))
    starts = starts or [0] * len(seqs)
    return ([norm(x[s:], head) for x, s in zip(hs, starts)],
            [g[s:] for g, s in zip(margins, starts)])


def logits_at(config: dict, seed: int, seqs: list[np.ndarray], *,
              fp8: bool = False, starts: list[int] | None = None):
    """Per sequence, the logits of ``forward``."""
    return forward(config, seed, seqs, fp8=fp8, starts=starts)[0]


def gaps(config: dict, seed: int, items: list[dict], *,
         control: bool = False) -> dict:
    """The comparison (the contract in ``reference/__init__.py``): for
    every served token whose position the reference routes clear of a tie
    (below), the gap by which the reference's logit of it lies below the
    reference's best at that position; ``gap_max`` is the widest. With
    ``control``, the same read for the token that the fp8 control puts
    first at each of those positions.

    A routing tie: at a position where, in some MoE layer, a held
    expert's biased score lies within ``ROUTING_TIE`` of the top-k
    boundary, bfloat16's rounding of the hidden state may route the token
    the other way, and a flipped expert puts a served token up to ~1.4
    below the best with these random weights (the configuration's
    ``check``). The served token there says nothing of the program, and
    is not compared;
    ``tie_share`` is the share of served tokens left out."""
    seqs = [np.asarray(list(it["prompt"]) + list(it["served"][:-1]),
                       np.int32) for it in items]
    starts = [len(it["prompt"]) - 1 for it in items]
    rows, margins = forward(config, seed, seqs, starts=starts)
    clear = [np.asarray(g) >= ROUTING_TIE for g in margins]

    def per_position(picks):
        return [np.where(c, np.asarray(_gaps_at(r, p)), 0.0)
                for r, p, c in zip(rows, picks, clear)]

    per_pos = per_position([jnp.asarray(np.asarray(it["served"], np.int32))
                            for it in items])
    out = {**_summary(per_pos, ""),
           "tie_share": float(1 - np.concatenate(clear).mean()),
           "gap_per_item": [float(np.max(g)) for g in per_pos],
           "served_tokens": int(sum(len(it["served"]) for it in items))}
    if control:
        ctl = logits_at(config, seed, seqs, fp8=True, starts=starts)
        out.update(_summary(per_position([jnp.argmax(c, axis=-1)
                                          for c in ctl]), "control_"))
    return out
