"""Post-training objectives used by the fabric's workflow operators:
SFT (causal LM), DPO, PPO-clip and a Bradley–Terry reward-model loss.

These are the real JAX implementations behind the GENERATE/SFT/DPO/PPO
operator types when the engine runs with the JaxExecutor (and behind the
examples' end-to-end drivers).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import cross_entropy, embed_lookup


def token_logprobs(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Per-token log p(label). logits: (B,T,V), labels: (B,T) -> (B,T)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1).squeeze(-1)
    return gold - logz


def sft_loss(model, params, batch) -> jax.Array:
    """Next-token prediction on (tokens, labels[, loss_mask])."""
    return model.loss_fn(params, batch)


def dpo_loss(model, params, ref_params, batch, *, beta: float = 0.1,
             ) -> jax.Array:
    """Direct Preference Optimization (Rafailov et al. 2023).
    batch: chosen/rejected token+label pairs with response masks."""
    def seq_lp(p, toks, labs, mask):
        h = model._trunk(p, embed_lookup(p["embed"], toks,
                                         model.cfg.compute_dtype))
        logits = h @ p["lm_head"].astype(model.cfg.compute_dtype)
        lp = token_logprobs(logits, labs)
        return jnp.sum(lp * mask, axis=-1)

    pc = seq_lp(params, batch["chosen"], batch["chosen_labels"],
                batch["chosen_mask"])
    pr = seq_lp(params, batch["rejected"], batch["rejected_labels"],
                batch["rejected_mask"])
    rc = seq_lp(ref_params, batch["chosen"], batch["chosen_labels"],
                batch["chosen_mask"])
    rr = seq_lp(ref_params, batch["rejected"], batch["rejected_labels"],
                batch["rejected_mask"])
    margin = beta * ((pc - rc) - (pr - rr))
    return -jnp.mean(jax.nn.log_sigmoid(margin))


def ppo_loss(model, params, batch, *, clip: float = 0.2,
             vf_coef: float = 0.0, ent_coef: float = 0.0) -> jax.Array:
    """Clipped-surrogate PPO policy loss over rollout tokens.
    batch: tokens, labels (actions), old_logprobs, advantages, mask."""
    cfg = model.cfg
    h = model._trunk(params, embed_lookup(params["embed"], batch["tokens"],
                                          cfg.compute_dtype))
    logits = h @ params["lm_head"].astype(cfg.compute_dtype)
    lp = token_logprobs(logits, batch["labels"])
    ratio = jnp.exp(lp - batch["old_logprobs"])
    adv = batch["advantages"]
    unclipped = ratio * adv
    clipped = jnp.clip(ratio, 1 - clip, 1 + clip) * adv
    mask = batch["mask"].astype(jnp.float32)
    pg = -jnp.sum(jnp.minimum(unclipped, clipped) * mask) / \
        jnp.maximum(jnp.sum(mask), 1.0)
    if ent_coef:
        p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        ent = -jnp.sum(p * jnp.log(p + 1e-9), axis=-1)
        pg = pg - ent_coef * jnp.sum(ent * mask) / jnp.maximum(
            jnp.sum(mask), 1.0)
    return pg


def reward_model_loss(model, params, batch) -> jax.Array:
    """Bradley–Terry pairwise loss; reward = mean final-hidden projection
    through lm_head[:, 0] (a cheap scalar head reusing existing weights)."""
    cfg = model.cfg

    def score(toks):
        h = model._trunk(params, embed_lookup(params["embed"], toks,
                                              cfg.compute_dtype))
        return (h[:, -1] @ params["lm_head"].astype(cfg.compute_dtype)
                )[:, 0].astype(jnp.float32)

    s_c = score(batch["chosen"])
    s_r = score(batch["rejected"])
    return -jnp.mean(jax.nn.log_sigmoid(s_c - s_r))
