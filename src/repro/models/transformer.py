"""Model assemblies for every assigned architecture family.

Uniform API per family (consumed by train/, serve/ and launch/dryrun):

    init(key)                       -> params pytree
    loss_fn(params, batch)          -> scalar loss       (train_4k cells)
    prefill(params, batch)          -> (last_logits, cache)   (prefill cells)
    decode(params, tokens, cache)   -> (logits, cache)   (decode cells)
    init_cache(batch, max_len)      -> cache pytree

All stacks are lax.scan over stacked layer params (compile time O(1) in
depth); remat policy per config.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.distributed.logical import constrain

from .attention import AttnParams, attention_block, init_attn
from .common import (ArchConfig, cross_entropy, dense_init, embed_init,
                     embed_lookup, rmsnorm, stacked)
from .ffn import MLPParams, MoEParams, init_mlp, init_moe, moe_block, swiglu
from .mamba2 import (Mamba2Params, MambaState, init_mamba2, init_mamba_state,
                     mamba2_block)


def _maybe_remat(fn, cfg: ArchConfig):
    return jax.checkpoint(fn) if cfg.remat == "block" else fn


# ===========================================================================
# Dense decoder LM (phi3 / minitron / smollm / llama / llava backbone)
# ===========================================================================
class DenseLayer(NamedTuple):
    attn: AttnParams
    mlp: MLPParams
    norm1: jax.Array
    norm2: jax.Array


def _init_dense_layer(cfg: ArchConfig):
    def init(key):
        k1, k2 = jax.random.split(key)
        return DenseLayer(init_attn(k1, cfg),
                          init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.param_dtype),
                          jnp.ones((cfg.d_model,), cfg.param_dtype),
                          jnp.ones((cfg.d_model,), cfg.param_dtype))
    return init


class DenseLM:
    """GQA + RoPE + SwiGLU decoder-only LM."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    def init(self, key):
        cfg = self.cfg
        ke, kl, ko = jax.random.split(key, 3)
        params = {
            "embed": embed_init(ke, (cfg.vocab_size, cfg.d_model),
                                cfg.param_dtype),
            "layers": stacked(_init_dense_layer(cfg), cfg.n_layers, kl),
            "final_norm": jnp.ones((cfg.d_model,), cfg.param_dtype),
            "lm_head": dense_init(ko, (cfg.d_model, cfg.vocab_size),
                                  dtype=cfg.param_dtype),
        }
        if cfg.family == "vlm":
            params["patch_proj"] = dense_init(
                jax.random.fold_in(ko, 1), (cfg.d_model, cfg.d_model),
                dtype=cfg.param_dtype)
        return params

    # -- shared trunk -------------------------------------------------------
    def _trunk(self, params, h):
        cfg = self.cfg

        def body(x, lp: DenseLayer):
            a, _ = attention_block(lp.attn, rmsnorm(x, lp.norm1,
                                                    cfg.norm_eps), cfg)
            x = constrain(x + a, "batch", "seq", "embed")
            x = x + swiglu(lp.mlp, rmsnorm(x, lp.norm2, cfg.norm_eps),
                           cfg.compute_dtype)
            # sequence-parallel residual: the value the scan SAVES for
            # backward is seq-sharded over "model"
            return constrain(x, "batch", "seq_res", "embed"), None

        h, _ = jax.lax.scan(_maybe_remat(body, cfg), h, params["layers"])
        return rmsnorm(h, params["final_norm"], cfg.norm_eps)

    def _embed(self, params, batch):
        cfg = self.cfg
        h = embed_lookup(params["embed"], batch["tokens"],
                         cfg.compute_dtype)
        if cfg.family == "vlm" and "patch_embeds" in batch:
            pe = batch["patch_embeds"].astype(cfg.compute_dtype) @ \
                params["patch_proj"].astype(cfg.compute_dtype)
            h = jnp.concatenate([pe, h], axis=1)
        return constrain(h, "batch", "seq", "embed")

    def loss_fn(self, params, batch):
        cfg = self.cfg
        h = self._trunk(params, self._embed(params, batch))
        if cfg.family == "vlm" and "patch_embeds" in batch:
            h = h[:, batch["patch_embeds"].shape[1]:]   # text positions only
        logits = constrain(
            h @ params["lm_head"].astype(cfg.compute_dtype),
            "batch", "seq", "vocab")
        return cross_entropy(logits, batch["labels"], batch.get("loss_mask"))

    # -- serving ------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.hd)
        return {"k": jnp.zeros(shape, cfg.compute_dtype),
                "v": jnp.zeros(shape, cfg.compute_dtype),
                "index": jnp.zeros((batch,), jnp.int32)}

    def _cached_trunk(self, params, h, cache):
        cfg = self.cfg
        idx = cache["index"]
        # prefill (T>1): sequence-parallel residuals turn the per-layer TP
        # all-reduce into reduce-scatter/all-gather pairs on bf16 (llava
        # prefill_32k: 58 TB of f32 all-reduce before this); decode keeps
        # the T==1 residual replicated.
        res_axis = "seq_res" if h.shape[1] > 1 else "seq"

        def body(x, inp):
            lp, ck, cv = inp
            a, new = attention_block(
                lp.attn, rmsnorm(x, lp.norm1, cfg.norm_eps), cfg,
                kv_cache=(ck, cv), cache_index=idx)
            x = constrain(x + a, "batch", "seq", "embed")
            x = x + swiglu(lp.mlp, rmsnorm(x, lp.norm2, cfg.norm_eps),
                           cfg.compute_dtype)
            return constrain(x, "batch", res_axis, "embed"), new

        h, (nk, nv) = jax.lax.scan(_maybe_remat(body, cfg), h,
                                   (params["layers"], cache["k"], cache["v"]))
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        new_cache = {"k": nk, "v": nv, "index": idx + h.shape[1]}
        return h, new_cache

    def prefill(self, params, batch, cache):
        cfg = self.cfg
        h = self._embed(params, batch)
        h, cache = self._cached_trunk(params, h, cache)
        logits = h[:, -1:] @ params["lm_head"].astype(cfg.compute_dtype)
        return logits, cache

    def decode(self, params, tokens, cache):
        cfg = self.cfg
        h = embed_lookup(params["embed"], tokens, cfg.compute_dtype)   # (B,1,d)
        h, cache = self._cached_trunk(params, h, cache)
        logits = h @ params["lm_head"].astype(cfg.compute_dtype)
        return logits, cache


# ===========================================================================
# MoE decoder LM (qwen2-moe / kimi-k2)
# ===========================================================================
class MoELayer(NamedTuple):
    attn: AttnParams
    moe: MoEParams
    norm1: jax.Array
    norm2: jax.Array


def _init_moe_layer(cfg: ArchConfig):
    def init(key):
        k1, k2 = jax.random.split(key)
        return MoELayer(init_attn(k1, cfg), init_moe(k2, cfg),
                        jnp.ones((cfg.d_model,), cfg.param_dtype),
                        jnp.ones((cfg.d_model,), cfg.param_dtype))
    return init


class MoELM(DenseLM):
    AUX_WEIGHT = 0.01

    def init(self, key):
        cfg = self.cfg
        ke, kl, ko = jax.random.split(key, 3)
        return {
            "embed": embed_init(ke, (cfg.vocab_size, cfg.d_model),
                                cfg.param_dtype),
            "layers": stacked(_init_moe_layer(cfg), cfg.n_layers, kl),
            "final_norm": jnp.ones((cfg.d_model,), cfg.param_dtype),
            "lm_head": dense_init(ko, (cfg.d_model, cfg.vocab_size),
                                  dtype=cfg.param_dtype),
        }

    def _trunk(self, params, h, collect_aux: bool = True):
        cfg = self.cfg

        def body(x, lp: MoELayer):
            a, _ = attention_block(lp.attn, rmsnorm(x, lp.norm1,
                                                    cfg.norm_eps), cfg)
            x = constrain(x + a, "batch", "seq", "embed")
            m, aux = moe_block(lp.moe, rmsnorm(x, lp.norm2, cfg.norm_eps), cfg)
            return constrain(x + m, "batch", "seq_res", "embed"), aux

        h, auxes = jax.lax.scan(_maybe_remat(body, cfg), h, params["layers"])
        return rmsnorm(h, params["final_norm"], cfg.norm_eps), jnp.mean(auxes)

    def loss_fn(self, params, batch):
        cfg = self.cfg
        h, aux = self._trunk(params, self._embed(params, batch))
        logits = constrain(
            h @ params["lm_head"].astype(cfg.compute_dtype),
            "batch", "seq", "vocab")
        return cross_entropy(logits, batch["labels"],
                             batch.get("loss_mask")) + self.AUX_WEIGHT * aux

    def _cached_trunk(self, params, h, cache):
        cfg = self.cfg
        idx = cache["index"]
        res_axis = "seq_res" if h.shape[1] > 1 else "seq"

        def body(x, inp):
            lp, ck, cv = inp
            a, new = attention_block(
                lp.attn, rmsnorm(x, lp.norm1, cfg.norm_eps), cfg,
                kv_cache=(ck, cv), cache_index=idx)
            x = constrain(x + a, "batch", "seq", "embed")
            m, _ = moe_block(lp.moe, rmsnorm(x, lp.norm2, cfg.norm_eps), cfg)
            return constrain(x + m, "batch", res_axis, "embed"), new

        h, (nk, nv) = jax.lax.scan(_maybe_remat(body, cfg), h,
                                   (params["layers"], cache["k"], cache["v"]))
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        return h, {"k": nk, "v": nv, "index": idx + h.shape[1]}


# ===========================================================================
# Pure SSM LM (mamba2-1.3b)
# ===========================================================================
class SSMLayer(NamedTuple):
    mamba: Mamba2Params
    norm: jax.Array


def _init_ssm_layer(cfg: ArchConfig):
    def init(key):
        return SSMLayer(init_mamba2(key, cfg),
                        jnp.ones((cfg.d_model,), cfg.param_dtype))
    return init


class MambaLM:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    def init(self, key):
        cfg = self.cfg
        ke, kl, ko = jax.random.split(key, 3)
        return {
            "embed": embed_init(ke, (cfg.vocab_size, cfg.d_model),
                                cfg.param_dtype),
            "layers": stacked(_init_ssm_layer(cfg), cfg.n_layers, kl),
            "final_norm": jnp.ones((cfg.d_model,), cfg.param_dtype),
            "lm_head": dense_init(ko, (cfg.d_model, cfg.vocab_size),
                                  dtype=cfg.param_dtype),
        }

    def loss_fn(self, params, batch):
        cfg = self.cfg
        h = constrain(embed_lookup(params["embed"], batch["tokens"],
                                   cfg.compute_dtype),
                      "batch", "seq", "embed")

        def body(x, lp: SSMLayer):
            m, _ = mamba2_block(lp.mamba, rmsnorm(x, lp.norm, cfg.norm_eps),
                                cfg)
            return x + m, None

        h, _ = jax.lax.scan(_maybe_remat(body, cfg), h, params["layers"])
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        logits = constrain(
            h @ params["lm_head"].astype(cfg.compute_dtype),
            "batch", "seq", "vocab")
        return cross_entropy(logits, batch["labels"], batch.get("loss_mask"))

    # -- serving: O(1) state ---------------------------------------------
    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        one = init_mamba_state(cfg, batch, cfg.compute_dtype)
        return {"state": jax.tree.map(
            lambda x: jnp.broadcast_to(x, (cfg.n_layers,) + x.shape),
            one), "index": jnp.zeros((batch,), jnp.int32)}

    def _run(self, params, h, cache, *, step: bool):
        cfg = self.cfg

        def body(x, inp):
            lp, st = inp
            m, new_st = mamba2_block(
                lp.mamba, rmsnorm(x, lp.norm, cfg.norm_eps), cfg,
                state=MambaState(*st), return_state=True)
            return x + m, tuple(new_st)

        h, new_states = jax.lax.scan(
            body, h, (params["layers"], tuple(cache["state"])))
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        return h, {"state": MambaState(*new_states),
                   "index": cache["index"] + h.shape[1]}

    def prefill(self, params, batch, cache):
        cfg = self.cfg
        h = embed_lookup(params["embed"], batch["tokens"],
                         cfg.compute_dtype)
        h, cache = self._run(params, h, cache, step=False)
        logits = h[:, -1:] @ params["lm_head"].astype(cfg.compute_dtype)
        return logits, cache

    def decode(self, params, tokens, cache):
        cfg = self.cfg
        h = embed_lookup(params["embed"], tokens, cfg.compute_dtype)
        h, cache = self._run(params, h, cache, step=True)
        logits = h @ params["lm_head"].astype(cfg.compute_dtype)
        return logits, cache


# ===========================================================================
# Hybrid (zamba2): mamba2 backbone + ONE shared attention block every k layers
# ===========================================================================
class HybridLM:
    def __init__(self, cfg: ArchConfig):
        assert cfg.attn_every > 0 and cfg.n_layers % cfg.attn_every == 0
        self.cfg = cfg
        self.n_groups = cfg.n_layers // cfg.attn_every

    def init(self, key):
        cfg = self.cfg
        ke, kl, ka, km, ko = jax.random.split(key, 5)
        layers = stacked(_init_ssm_layer(cfg), cfg.n_layers, kl)
        # reshape stacked (L, ...) -> (groups, per_group, ...) for nested scan
        layers = jax.tree.map(
            lambda x: x.reshape((self.n_groups, cfg.attn_every) + x.shape[1:]),
            layers)
        return {
            "embed": embed_init(ke, (cfg.vocab_size, cfg.d_model),
                                cfg.param_dtype),
            "layers": layers,
            # SHARED weights: one attention + MLP block reused every group
            "shared_attn": init_attn(ka, cfg),
            "shared_mlp": init_mlp(km, cfg.d_model, cfg.d_ff, cfg.param_dtype),
            "shared_norm1": jnp.ones((cfg.d_model,), cfg.param_dtype),
            "shared_norm2": jnp.ones((cfg.d_model,), cfg.param_dtype),
            "final_norm": jnp.ones((cfg.d_model,), cfg.param_dtype),
            "lm_head": dense_init(ko, (cfg.d_model, cfg.vocab_size),
                                  dtype=cfg.param_dtype),
        }

    def _shared_block(self, params, x, *, kv_cache=None, cache_index=None):
        cfg = self.cfg
        a, new = attention_block(
            params["shared_attn"],
            rmsnorm(x, params["shared_norm1"], cfg.norm_eps), cfg,
            kv_cache=kv_cache, cache_index=cache_index)
        x = x + a
        x = x + swiglu(params["shared_mlp"],
                       rmsnorm(x, params["shared_norm2"], cfg.norm_eps),
                       cfg.compute_dtype)
        return x, new

    def loss_fn(self, params, batch):
        cfg = self.cfg
        h = embed_lookup(params["embed"], batch["tokens"],
                         cfg.compute_dtype)

        def inner(x, lp: SSMLayer):
            m, _ = mamba2_block(lp.mamba, rmsnorm(x, lp.norm, cfg.norm_eps),
                                cfg)
            return x + m, None

        def group(x, glp):
            x, _ = self._shared_block(params, x)
            x, _ = jax.lax.scan(inner, x, glp)
            return x, None

        h, _ = jax.lax.scan(_maybe_remat(group, cfg), h, params["layers"])
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        logits = constrain(
            h @ params["lm_head"].astype(cfg.compute_dtype),
            "batch", "seq", "vocab")
        return cross_entropy(logits, batch["labels"], batch.get("loss_mask"))

    # -- serving: SSM states + per-group KV cache for the shared block -----
    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        one = init_mamba_state(cfg, batch, cfg.compute_dtype)
        states = jax.tree.map(
            lambda x: jnp.broadcast_to(
                x, (self.n_groups, cfg.attn_every) + x.shape), one)
        kshape = (self.n_groups, batch, max_len, cfg.n_kv_heads, cfg.hd)
        return {"state": states,
                "k": jnp.zeros(kshape, cfg.compute_dtype),
                "v": jnp.zeros(kshape, cfg.compute_dtype),
                "index": jnp.zeros((batch,), jnp.int32)}

    def _run(self, params, h, cache):
        cfg = self.cfg
        idx = cache["index"]

        def inner(x, inp):
            lp, st = inp
            m, new_st = mamba2_block(
                lp.mamba, rmsnorm(x, lp.norm, cfg.norm_eps), cfg,
                state=MambaState(*st), return_state=True)
            return x + m, tuple(new_st)

        def group(x, inp):
            glp, gst, ck, cv = inp
            x, new_kv = self._shared_block(params, x, kv_cache=(ck, cv),
                                           cache_index=idx)
            x, new_states = jax.lax.scan(inner, x, (glp, gst))
            return x, (new_states, new_kv[0], new_kv[1])

        h, (new_states, nk, nv) = jax.lax.scan(
            group, h, (params["layers"], tuple(cache["state"]),
                       cache["k"], cache["v"]))
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        return h, {"state": MambaState(*new_states), "k": nk, "v": nv,
                   "index": idx + h.shape[1]}

    def prefill(self, params, batch, cache):
        cfg = self.cfg
        h = embed_lookup(params["embed"], batch["tokens"],
                         cfg.compute_dtype)
        h, cache = self._run(params, h, cache)
        logits = h[:, -1:] @ params["lm_head"].astype(cfg.compute_dtype)
        return logits, cache

    def decode(self, params, tokens, cache):
        cfg = self.cfg
        h = embed_lookup(params["embed"], tokens, cfg.compute_dtype)
        h, cache = self._run(params, h, cache)
        logits = h @ params["lm_head"].astype(cfg.compute_dtype)
        return logits, cache


# ===========================================================================
# Encoder-decoder backbone (whisper-tiny); frame frontend is a stub
# ===========================================================================
class EncLayer(NamedTuple):
    attn: AttnParams
    mlp: MLPParams
    norm1: jax.Array
    norm2: jax.Array


class DecLayer(NamedTuple):
    self_attn: AttnParams
    cross_attn: AttnParams
    mlp: MLPParams
    norm1: jax.Array
    norm2: jax.Array
    norm3: jax.Array


def _sinusoid(T: int, d: int, dtype) -> jax.Array:
    pos = jnp.arange(T)[:, None].astype(jnp.float32)
    i = jnp.arange(d // 2)[None, :].astype(jnp.float32)
    ang = pos / jnp.power(10000.0, 2 * i / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)],
                           axis=-1).astype(dtype)


class EncDecLM:
    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    def init(self, key):
        cfg = self.cfg
        ke, kenc, kdec, ko = jax.random.split(key, 4)

        def init_enc(k):
            k1, k2 = jax.random.split(k)
            return EncLayer(init_attn(k1, cfg),
                            init_mlp(k2, cfg.d_model, cfg.d_ff,
                                     cfg.param_dtype),
                            jnp.ones((cfg.d_model,), cfg.param_dtype),
                            jnp.ones((cfg.d_model,), cfg.param_dtype))

        def init_dec(k):
            k1, k2, k3 = jax.random.split(k, 3)
            return DecLayer(init_attn(k1, cfg), init_attn(k2, cfg),
                            init_mlp(k3, cfg.d_model, cfg.d_ff,
                                     cfg.param_dtype),
                            jnp.ones((cfg.d_model,), cfg.param_dtype),
                            jnp.ones((cfg.d_model,), cfg.param_dtype),
                            jnp.ones((cfg.d_model,), cfg.param_dtype))

        return {
            "embed": embed_init(ke, (cfg.vocab_size, cfg.d_model),
                                cfg.param_dtype),
            "enc_layers": stacked(init_enc, cfg.n_enc_layers, kenc),
            "dec_layers": stacked(init_dec, cfg.n_layers, kdec),
            "enc_norm": jnp.ones((cfg.d_model,), cfg.param_dtype),
            "final_norm": jnp.ones((cfg.d_model,), cfg.param_dtype),
            "lm_head": dense_init(ko, (cfg.d_model, cfg.vocab_size),
                                  dtype=cfg.param_dtype),
        }

    def encode(self, params, frames):
        """frames: (B, T_enc, d) precomputed embeddings (stub frontend)."""
        cfg = self.cfg
        h = frames.astype(cfg.compute_dtype) + \
            _sinusoid(frames.shape[1], cfg.d_model, cfg.compute_dtype)[None]

        def body(x, lp: EncLayer):
            a, _ = attention_block(
                lp.attn, rmsnorm(x, lp.norm1, cfg.norm_eps), cfg,
                causal=False, use_rope=False)
            x = constrain(x + a, "batch", "seq", "embed")
            x = x + swiglu(lp.mlp, rmsnorm(x, lp.norm2, cfg.norm_eps),
                           cfg.compute_dtype)
            return constrain(x, "batch", "seq_res", "embed"), None

        h, _ = jax.lax.scan(_maybe_remat(body, cfg), h, params["enc_layers"])
        return rmsnorm(h, params["enc_norm"], cfg.norm_eps)

    def _cross_kv(self, params, enc_out):
        """Precompute per-decoder-layer cross K/V (stacked over layers)."""
        cfg = self.cfg
        B, Te, d = enc_out.shape

        def per_layer(lp: DecLayer):
            k = (enc_out @ lp.cross_attn.wk.astype(cfg.compute_dtype)
                 ).reshape(B, Te, cfg.n_kv_heads, cfg.hd)
            v = (enc_out @ lp.cross_attn.wv.astype(cfg.compute_dtype)
                 ).reshape(B, Te, cfg.n_kv_heads, cfg.hd)
            return k, v

        return jax.vmap(per_layer)(params["dec_layers"])

    def _decoder(self, params, h, cross_kv, *, kv_cache=None, index=None):
        """Decoder stack. RoPE provides decoder positions (index-aware)."""
        cfg = self.cfg
        ck, cv = cross_kv

        def body(x, inp):
            lp, cross_k_l, cross_v_l, self_k_l, self_v_l = inp
            cache_l = None if self_k_l is None else (self_k_l, self_v_l)
            a, new = attention_block(
                lp.self_attn, rmsnorm(x, lp.norm1, cfg.norm_eps), cfg,
                kv_cache=cache_l, cache_index=index)
            x = x + a
            c, _ = attention_block(
                lp.cross_attn, rmsnorm(x, lp.norm2, cfg.norm_eps), cfg,
                cross_kv=(cross_k_l, cross_v_l))
            x = x + c
            x = x + swiglu(lp.mlp, rmsnorm(x, lp.norm3, cfg.norm_eps),
                           cfg.compute_dtype)
            return constrain(x, "batch", "seq", "embed"), new

        if kv_cache is None:
            def body_nc(x, inp):
                lp, cross_k_l, cross_v_l = inp
                return body(x, (lp, cross_k_l, cross_v_l, None, None))
            h, _ = jax.lax.scan(_maybe_remat(body_nc, cfg), h,
                                (params["dec_layers"], ck, cv))
            new_cache = None
        else:
            h, (nk, nv) = jax.lax.scan(
                body, h, (params["dec_layers"], ck, cv,
                          kv_cache[0], kv_cache[1]))
            new_cache = (nk, nv)
        return rmsnorm(h, params["final_norm"], cfg.norm_eps), new_cache

    def loss_fn(self, params, batch):
        cfg = self.cfg
        enc_out = self.encode(params, batch["frames"])
        cross_kv = self._cross_kv(params, enc_out)
        h = embed_lookup(params["embed"], batch["tokens"],
                         cfg.compute_dtype)
        h, _ = self._decoder(params, h, cross_kv)
        logits = constrain(
            h @ params["lm_head"].astype(cfg.compute_dtype),
            "batch", "seq", "vocab")
        return cross_entropy(logits, batch["labels"], batch.get("loss_mask"))

    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        kshape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.hd)
        cross = (cfg.n_layers, batch, cfg.enc_len, cfg.n_kv_heads, cfg.hd)
        return {"k": jnp.zeros(kshape, cfg.compute_dtype),
                "v": jnp.zeros(kshape, cfg.compute_dtype),
                "cross_k": jnp.zeros(cross, cfg.compute_dtype),
                "cross_v": jnp.zeros(cross, cfg.compute_dtype),
                "index": jnp.zeros((batch,), jnp.int32)}

    def prefill(self, params, batch, cache):
        cfg = self.cfg
        enc_out = self.encode(params, batch["frames"])
        ck, cv = self._cross_kv(params, enc_out)
        h = embed_lookup(params["embed"], batch["tokens"],
                         cfg.compute_dtype)
        h, new_kv = self._decoder(params, h, (ck, cv),
                                  kv_cache=(cache["k"], cache["v"]),
                                  index=cache["index"])
        logits = h[:, -1:] @ params["lm_head"].astype(cfg.compute_dtype)
        return logits, {"k": new_kv[0], "v": new_kv[1], "cross_k": ck,
                        "cross_v": cv,
                        "index": cache["index"] + h.shape[1]}

    def decode(self, params, tokens, cache):
        cfg = self.cfg
        h = embed_lookup(params["embed"], tokens, cfg.compute_dtype)
        h, new_kv = self._decoder(
            params, h, (cache["cross_k"], cache["cross_v"]),
            kv_cache=(cache["k"], cache["v"]), index=cache["index"])
        logits = h @ params["lm_head"].astype(cfg.compute_dtype)
        return logits, {**cache, "k": new_kv[0], "v": new_kv[1],
                        "index": cache["index"] + 1}


FAMILIES = {
    "dense": DenseLM,
    "vlm": DenseLM,
    "moe": MoELM,
    "ssm": MambaLM,
    "hybrid": HybridLM,
    "encdec": EncDecLM,
}


def build_model(cfg: ArchConfig):
    return FAMILIES[cfg.family](cfg)
