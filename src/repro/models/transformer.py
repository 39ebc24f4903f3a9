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

from .attention import (AttnParams, MLAParams, attention_block, init_attn,
                        init_mla, kv_zeros, latent_zeros, mla_block)
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
        return {"k": kv_zeros(cfg, cfg.n_layers, batch, max_len),
                "v": kv_zeros(cfg, cfg.n_layers, batch, max_len),
                "index": jnp.zeros((batch,), jnp.int32)}

    def _ffn(self, lp: DenseLayer, x):
        return swiglu(lp.mlp, x, self.cfg.compute_dtype)

    def _cached_trunk(self, params, h, cache):
        cfg = self.cfg
        idx = cache["index"]
        # prefill (T>1): sequence-parallel residuals turn the per-layer TP
        # all-reduce into reduce-scatter/all-gather pairs on bf16 (llava
        # prefill_32k: 58 TB of f32 all-reduce before this); decode keeps
        # the T==1 residual replicated.
        res_axis = "seq_res" if h.shape[1] > 1 else "seq"

        def body(carry, inp):
            x, ck, cv = carry
            lp, layer = inp
            a, (ck, cv) = attention_block(
                lp.attn, rmsnorm(x, lp.norm1, cfg.norm_eps), cfg,
                kv_cache=(ck, cv), layer=layer, cache_index=idx)
            x = constrain(x + a, "batch", "seq", "embed")
            x = x + self._ffn(lp, rmsnorm(x, lp.norm2, cfg.norm_eps))
            return (constrain(x, "batch", res_axis, "embed"), ck, cv), None

        (h, nk, nv), _ = jax.lax.scan(
            body, (h, cache["k"], cache["v"]),
            (params["layers"], jnp.arange(cfg.n_layers)))
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
# MoE decoder LM (qwen2-moe)
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

    def _ffn(self, lp: MoELayer, x):
        return moe_block(lp.moe, x, self.cfg)[0]


# ===========================================================================
# Latent-attention MoE LM (Kimi-K2 / DeepSeek-V3 form)
# ===========================================================================
class LatentLayer(NamedTuple):
    attn: MLAParams
    ffn: MLPParams | MoEParams
    norm1: jax.Array
    norm2: jax.Array


def _init_latent_layer(cfg: ArchConfig, dense: bool):
    def init(key):
        k1, k2 = jax.random.split(key)
        ffn = init_mlp(k2, cfg.d_model, cfg.d_ff, cfg.param_dtype) \
            if dense else init_moe(k2, cfg)
        return LatentLayer(init_mla(k1, cfg), ffn,
                           jnp.ones((cfg.d_model,), cfg.param_dtype),
                           jnp.ones((cfg.d_model,), cfg.param_dtype))
    return init


class LatentMoELM:
    """MLA + MoE decoder: ``n_dense_layers`` leading layers with a dense
    SwiGLU of ``d_ff``, then a scan of MoE layers (routed experts of
    ``expert_ff``, a shared expert, ``route``'s router). Every layer's
    attention is MLA, and the serving cache is one stacked latent cache,
    (L, slots, kv_lora_rank + qk_rope_dim, positions), that both scans
    carry and write in place."""

    AUX_WEIGHT = 0.01

    def __init__(self, cfg: ArchConfig):
        assert 0 <= cfg.n_dense_layers < cfg.n_layers
        self.cfg = cfg
        self.n_moe = cfg.n_layers - cfg.n_dense_layers

    def init(self, key):
        cfg = self.cfg
        ke, kd, kl, ko = jax.random.split(key, 4)
        return {
            "embed": embed_init(ke, (cfg.vocab_size, cfg.d_model),
                                cfg.param_dtype),
            "dense_layers": stacked(_init_latent_layer(cfg, True),
                                    cfg.n_dense_layers, kd),
            "layers": stacked(_init_latent_layer(cfg, False), self.n_moe,
                              kl),
            "final_norm": jnp.ones((cfg.d_model,), cfg.param_dtype),
            "lm_head": dense_init(ko, (cfg.d_model, cfg.vocab_size),
                                  dtype=cfg.param_dtype),
        }

    def _layer(self, lp: LatentLayer, x, *, dense: bool, cache=None,
               layer=None, index=None):
        cfg = self.cfg
        with jax.named_scope("mla"):
            a, cache = mla_block(lp.attn, rmsnorm(x, lp.norm1, cfg.norm_eps),
                                 cfg, cache=cache, layer=layer,
                                 cache_index=index)
        x = constrain(x + a, "batch", "seq", "embed")
        n = rmsnorm(x, lp.norm2, cfg.norm_eps)
        if dense:
            f, aux = swiglu(lp.ffn, n, cfg.compute_dtype), jnp.zeros(())
        else:
            f, aux = moe_block(lp.ffn, n, cfg)
        return x + f, cache, aux

    def _stack(self, params, h, cache=None, index=None):
        """Both scans, dense then MoE: (h, new latent cache, the MoE
        layers' mean aux)."""
        cfg = self.cfg
        auxes = []
        for name, dense, first in (("dense_layers", True, 0),
                                   ("layers", False, cfg.n_dense_layers)):
            n_layers = cfg.n_dense_layers if dense else self.n_moe

            def body(carry, inp, dense=dense):
                x, c = carry
                lp, layer = inp
                x, c, aux = self._layer(lp, x, dense=dense, cache=c,
                                        layer=layer, index=index)
                return (constrain(x, "batch", "seq_res", "embed"), c), aux

            if cache is None:
                body = _maybe_remat(body, cfg)
            (h, cache), aux = jax.lax.scan(
                body, (h, cache),
                (params[name], first + jnp.arange(n_layers)))
            auxes.append(aux)
        h = rmsnorm(h, params["final_norm"], cfg.norm_eps)
        return h, cache, jnp.mean(auxes[-1])

    def _embed(self, params, tokens):
        cfg = self.cfg
        return constrain(embed_lookup(params["embed"], tokens,
                                      cfg.compute_dtype),
                         "batch", "seq", "embed")

    def loss_fn(self, params, batch):
        cfg = self.cfg
        h, _, aux = self._stack(params, self._embed(params, batch["tokens"]))
        logits = constrain(
            h @ params["lm_head"].astype(cfg.compute_dtype),
            "batch", "seq", "vocab")
        return cross_entropy(logits, batch["labels"],
                             batch.get("loss_mask")) + self.AUX_WEIGHT * aux

    # -- serving ------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        return {"latent": latent_zeros(cfg, cfg.n_layers, batch, max_len),
                "index": jnp.zeros((batch,), jnp.int32)}

    def _cached(self, params, tokens, cache):
        idx = cache["index"]
        h, latent, _ = self._stack(params, self._embed(params, tokens),
                                   cache["latent"], idx)
        return h, {"latent": latent, "index": idx + h.shape[1]}

    def prefill(self, params, batch, cache):
        h, cache = self._cached(params, batch["tokens"], cache)
        logits = h[:, -1:] @ params["lm_head"].astype(self.cfg.compute_dtype)
        return logits, cache

    def decode(self, params, tokens, cache):
        h, cache = self._cached(params, tokens, cache)
        logits = h @ params["lm_head"].astype(self.cfg.compute_dtype)
        return logits, cache


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

    def _shared_block(self, params, x, *, kv_cache=None, layer=None,
                      cache_index=None):
        cfg = self.cfg
        a, new = attention_block(
            params["shared_attn"],
            rmsnorm(x, params["shared_norm1"], cfg.norm_eps), cfg,
            kv_cache=kv_cache, layer=layer, cache_index=cache_index)
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
        return {"state": states,
                "k": kv_zeros(cfg, self.n_groups, batch, max_len),
                "v": kv_zeros(cfg, self.n_groups, batch, max_len),
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

        def group(carry, inp):
            x, ck, cv = carry
            glp, gst, g = inp
            x, (ck, cv) = self._shared_block(params, x, kv_cache=(ck, cv),
                                             layer=g, cache_index=idx)
            x, new_states = jax.lax.scan(inner, x, (glp, gst))
            return (x, ck, cv), new_states

        (h, nk, nv), new_states = jax.lax.scan(
            group, (h, cache["k"], cache["v"]),
            (params["layers"], tuple(cache["state"]),
             jnp.arange(self.n_groups)))
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

        def body(x, lp, cross_k_l, cross_v_l, kv_cache=None, layer=None):
            a, new = attention_block(
                lp.self_attn, rmsnorm(x, lp.norm1, cfg.norm_eps), cfg,
                kv_cache=kv_cache, layer=layer, cache_index=index)
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
                return body(x, *inp)[0], None
            h, _ = jax.lax.scan(_maybe_remat(body_nc, cfg), h,
                                (params["dec_layers"], ck, cv))
            new_cache = None
        else:
            def body_c(carry, inp):
                x, sk, sv = carry
                lp, cross_k_l, cross_v_l, layer = inp
                x, (sk, sv) = body(x, lp, cross_k_l, cross_v_l,
                                   kv_cache=(sk, sv), layer=layer)
                return (x, sk, sv), None
            (h, nk, nv), _ = jax.lax.scan(
                body_c, (h, kv_cache[0], kv_cache[1]),
                (params["dec_layers"], ck, cv, jnp.arange(cfg.n_layers)))
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
        cross = (cfg.n_layers, batch, cfg.enc_len, cfg.n_kv_heads, cfg.hd)
        return {"k": kv_zeros(cfg, cfg.n_layers, batch, max_len),
                "v": kv_zeros(cfg, cfg.n_layers, batch, max_len),
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
    "mla_moe": LatentMoELM,
    "ssm": MambaLM,
    "hybrid": HybridLM,
    "encdec": EncDecLM,
}


def build_model(cfg: ArchConfig):
    return FAMILIES[cfg.family](cfg)
