"""Feed-forward layers: dense SwiGLU and Mixture-of-Experts.

MoE uses sort-based capacity dispatch (GShard/Switch style, adapted for TPU):
tokens are sorted by expert assignment, scattered into per-expert capacity
buffers, processed with one batched einsum over the expert dimension (which
shards cleanly over the mesh's model axis = expert parallelism), and combined
back with routing weights. No (T, E, C) one-hot dispatch tensor is ever
materialized — the buffers are (E, C, d), the only scalable layout at
kimi-k2's 384 experts x 1M-token batches.

``held_experts_block`` is the dropless form for a chip that holds only some
of the experts (``cfg.expert_lo``, ``cfg.n_held_experts``): it routes over
all of them, and runs a grouped matmul over exactly the (token, expert)
pairs that land on the experts held here, with no capacity.

Both route through ``route``: softmax top-k, or DeepSeek-V3's sigmoid
noaux_tc (a bias picks the experts, the unbiased scores weight them).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .common import ArchConfig, dense_init


class MLPParams(NamedTuple):
    w_gate: jax.Array   # (d, ff)
    w_up: jax.Array     # (d, ff)
    w_down: jax.Array   # (ff, d)


def init_mlp(key, d: int, ff: int, dtype) -> MLPParams:
    k1, k2, k3 = jax.random.split(key, 3)
    return MLPParams(dense_init(k1, (d, ff), dtype=dtype),
                     dense_init(k2, (d, ff), dtype=dtype),
                     dense_init(k3, (ff, d), dtype=dtype))


def swiglu(p: MLPParams, x: jax.Array, compute_dtype) -> jax.Array:
    g = x @ p.w_gate.astype(compute_dtype)
    u = x @ p.w_up.astype(compute_dtype)
    return (jax.nn.silu(g) * u) @ p.w_down.astype(compute_dtype)


class MoEParams(NamedTuple):
    router: jax.Array     # (d, E)
    w_gate: jax.Array     # (E_here, d, ff)  the experts held here
    w_up: jax.Array       # (E_here, d, ff)
    w_down: jax.Array     # (E_here, ff, d)
    shared: MLPParams | None   # shared experts, fused into one wide MLP
    bias: jax.Array | None = None   # (E,) sigmoid router's choice bias


def init_moe(key, cfg: ArchConfig) -> MoEParams:
    """Expert e's weights come from its own key (``fold_in(key, e)``), so
    a chip that holds experts lo .. lo+n-1 holds exactly what the whole
    layer holds for them."""
    d, ff, E = cfg.d_model, cfg.expert_ff, cfg.n_experts
    if cfg.n_held_experts:
        held = jnp.arange(cfg.expert_lo, cfg.expert_lo + cfg.n_held_experts)
    else:       # EP pads to a multiple of ep_size
        held = jnp.arange(max(E, cfg.moe_pad_experts))
    kr, ke, ks, kb = jax.random.split(key, 4)
    shared = None
    if cfg.n_shared_experts:
        shared = init_mlp(ks, d, ff * cfg.n_shared_experts, cfg.param_dtype)
    experts = jax.vmap(lambda e: init_mlp(jax.random.fold_in(ke, e), d, ff,
                                          cfg.param_dtype))(held)
    bias = None
    if cfg.router == "sigmoid":
        bias = jax.random.normal(kb, (E,)) * cfg.router_bias_std
    return MoEParams(
        dense_init(kr, (d, E), dtype=jnp.float32),   # router stays fp32
        experts.w_gate, experts.w_up, experts.w_down, shared, bias)


def route(router: jax.Array, bias: jax.Array | None, x: jax.Array,
          cfg: ArchConfig) -> tuple[jax.Array, jax.Array, jax.Array]:
    """x: (..., d) -> (gates (..., k) f32, expert ids (..., k), aux), over
    all ``cfg.n_experts`` experts, in float32.

    softmax: the top-k of the softmax, renormalised; aux is the
    Switch-style balance loss's two factors, stacked (2, E): each
    expert's mean probability and its share of top-1 picks over x's
    tokens. sigmoid (noaux_tc, n_group = topk_group = 1): sigmoid scores
    plus the bias pick the top-k, the unbiased scores weight them,
    normalised to sum 1 and times ``router_scale``; aux is zeros (the
    bias balances the load, no loss does)."""
    with jax.named_scope("router"):
        logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), router)
        E, k = cfg.n_experts, cfg.top_k
        lead = tuple(range(logits.ndim - 1))
        if cfg.router == "sigmoid":
            scores = jax.nn.sigmoid(logits)
            _, ids = jax.lax.top_k(scores + bias, k)
            gates = jnp.take_along_axis(scores, ids, axis=-1)
            gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
            return gates * cfg.router_scale, ids, jnp.zeros((2, E))
        probs = jax.nn.softmax(logits, axis=-1)
        gates, ids = jax.lax.top_k(probs, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        aux = jnp.stack([jnp.mean(probs, axis=lead),
                         jnp.mean(jax.nn.one_hot(ids[..., 0], E,
                                                 dtype=jnp.float32),
                                  axis=lead)])
        return gates, ids, aux


def _capacity(n_tokens: int, cfg: ArchConfig) -> int:
    cap = int(cfg.capacity_factor * n_tokens * cfg.top_k / cfg.n_experts)
    return max(8, -(-cap // 8) * 8)      # round up to 8 for TPU tiling


def moe_block(p: MoEParams, x: jax.Array, cfg: ArchConfig,
              ) -> tuple[jax.Array, jax.Array]:
    """x: (B, T, d) -> (out, aux_loss). Top-k routing with capacity drop.

    GROUPED dispatch: tokens are split into ``cfg.moe_groups`` groups whose
    leading axis shards over the mesh's data axis, so every sort/scatter/
    gather of the dispatch is SHARD-LOCAL under GSPMD (a single global
    argsort over 1M tokens turned into petabytes of all-reduce before this).
    The dispatch buffer is (G/data, E/model, C, d) — fully sharded; the
    expert einsum then all-gathers each model-shard's expert weights across
    the data axis (the documented baseline cost; the §Perf iteration
    replaces it with shard_map all-to-all EP).
    """
    from repro.distributed.logical import constrain, current_mesh

    if cfg.n_held_experts:
        return held_experts_block(p, x, cfg)
    mesh = current_mesh()
    if cfg.moe_impl == "ep" and mesh is not None:
        from .moe_ep import moe_block_ep
        return moe_block_ep(p, x, cfg, mesh)

    B, T, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    n = B * T
    G = max(1, getattr(cfg, "moe_groups", 1))
    if n % G:
        G = 1
    ng = n // G
    xt = x.reshape(G, ng, d)
    xt = constrain(xt, "batch", None, None)
    C = _capacity(ng, cfg)

    gate_vals, expert_ids, parts = route(p.router, p.bias, xt, cfg)
    aux = E * jnp.sum(parts[0] * parts[1])    # Switch-style balance loss

    # ---- sort-based dispatch, independent per group (shard-local) ----
    flat_expert = expert_ids.reshape(G, ng * k)
    order = jnp.argsort(flat_expert, axis=-1, stable=True)    # (G, ngk)
    sorted_expert = jnp.take_along_axis(flat_expert, order, axis=-1)
    token_of = order // k                                     # (G, ngk)
    first_idx = jax.vmap(
        lambda se: jnp.searchsorted(se, jnp.arange(E), side="left")
    )(sorted_expert)                                          # (G, E)
    ranks = jnp.arange(ng * k)[None, :] - jnp.take_along_axis(
        first_idx, sorted_expert, axis=-1)
    keep = ranks < C
    dest = jnp.where(keep, sorted_expert * C + ranks, E * C)  # (G, ngk)

    gidx = jnp.arange(G)[:, None]
    x_sorted = xt[gidx, token_of]                             # (G, ngk, d)
    buf = jnp.zeros((G, E * C + 1, d), dtype=x.dtype)
    buf = buf.at[gidx, dest].set(x_sorted)
    buf = buf[:, :E * C].reshape(G, E, C, d)
    buf = constrain(buf, "batch", "experts", None, None)

    # ---- expert compute (E over model; weights gathered over data) ----
    cd = cfg.compute_dtype
    w_gate, w_up, w_down = p.w_gate[:E], p.w_up[:E], p.w_down[:E]
    g = jnp.einsum("gecd,edf->gecf", buf, w_gate.astype(cd))
    u = jnp.einsum("gecd,edf->gecf", buf, w_up.astype(cd))
    h = jax.nn.silu(g) * u
    y = jnp.einsum("gecf,efd->gecd", h, w_down.astype(cd))
    y = constrain(y, "batch", "experts", None, None)

    # ---- combine (shard-local gather + weighted scatter-add) ----
    y_flat = jnp.concatenate(
        [y.reshape(G, E * C, d), jnp.zeros((G, 1, d), y.dtype)], axis=1)
    per_slot = y_flat[gidx, dest] * keep[..., None].astype(y.dtype)
    gates_sorted = jnp.take_along_axis(
        gate_vals.reshape(G, ng * k), order, axis=-1).astype(y.dtype)
    contrib = per_slot * gates_sorted[..., None]
    out = jnp.zeros((G, ng, d), dtype=y.dtype)
    out = out.at[gidx, token_of].add(contrib)

    if p.shared is not None:
        out = out + swiglu(p.shared, xt.astype(cd), cd)
    return out.reshape(B, T, d), aux


#: rows of (token, expert) pairs one pass of the held experts' grouped
#: matmul takes; a long prompt takes a few passes
HELD_CHUNK = 1024


def held_experts_block(p: MoEParams, x: jax.Array, cfg: ArchConfig,
                       ) -> tuple[jax.Array, jax.Array]:
    """Dropless MoE over the experts held here. x: (B, T, d) ->
    (out, aux). Routes every token over all ``n_experts`` experts, then
    computes exactly the pairs whose expert is held (``expert_lo`` ..
    ``expert_lo + experts_held - 1``), however many land on one: the pairs
    are sorted by expert and run through a grouped matmul in passes of
    ``HELD_CHUNK`` rows, as many passes as the pairs need. What the other
    experts would add is left out (model-configs §4); the shared expert is
    added for every token."""
    from repro.kernels import ops as kops

    B, T, d = x.shape
    n, k, held, lo = B * T, cfg.top_k, cfg.experts_held, cfg.expert_lo
    cd = cfg.compute_dtype
    xt = x.reshape(n, d)
    gates, ids, parts = route(p.router, p.bias, xt, cfg)
    aux = cfg.n_experts * jnp.sum(parts[0] * parts[1])

    with jax.named_scope("held_experts"):
        local = ids.reshape(-1) - lo
        key = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(key, stable=True)       # held pairs first
        sizes = jnp.bincount(key, length=held + 1)[:held]
        total = jnp.sum(sizes)
        ends = jnp.cumsum(sizes)
        starts = ends - sizes
        C = min(HELD_CHUNK, -(-n * min(k, held) // 8) * 8)
        pad = jnp.zeros((C,), order.dtype)
        token = jnp.concatenate([order // k, pad])
        weight = jnp.concatenate([gates.reshape(-1)[order], pad.astype(
            gates.dtype)])
        w_gate, w_up, w_down = (w.astype(cd) for w in
                                (p.w_gate, p.w_up, p.w_down))

        def one_pass(i, out):
            r0 = i * C
            valid = r0 + jnp.arange(C) < total
            tok = jax.lax.dynamic_slice(token, (r0,), (C,))
            wt = jax.lax.dynamic_slice(weight, (r0,), (C,))
            # the rows of each held expert that fall in this pass
            gs = jnp.clip(jnp.minimum(ends, r0 + C)
                          - jnp.maximum(starts, r0), 0).astype(jnp.int32)
            xc = xt[tok].astype(cd)
            h = jax.nn.silu(kops.grouped_matmul(xc, w_gate, gs)) * \
                kops.grouped_matmul(xc, w_up, gs)
            y = kops.grouped_matmul(h.astype(cd), w_down, gs)
            y = jnp.where(valid[:, None], y * wt[:, None].astype(y.dtype), 0)
            return out.at[tok].add(y.astype(out.dtype))

        out = jax.lax.fori_loop(0, (total + C - 1) // C, one_pass,
                                jnp.zeros((n, d), jnp.float32))
    if p.shared is not None:
        with jax.named_scope("shared_expert"):
            out = out + swiglu(p.shared, xt.astype(cd), cd)
    return out.astype(x.dtype).reshape(B, T, d), aux
