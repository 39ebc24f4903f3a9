"""Shared model substrate: config, norms, RoPE, initializers.

All models are pure-JAX pytree-parameter functions (no flax), built
scan-over-layers so compile time is O(1) in depth — essential for the
61-layer / 512-device dry-runs on a single-core CPU host.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                  # dense | moe | ssm | hybrid | encdec | vlm
    n_layers: int
    d_model: int
    n_heads: int                 # query heads (0 for attention-free)
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0            # 0 => d_model // n_heads
    # --- MoE ---
    n_experts: int = 0
    n_shared_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    moe_groups: int = 1        # dispatch groups == number of batch shards
    moe_impl: str = "gspmd"    # "gspmd" (grouped dispatch) | "ep" (a2a)
    moe_pad_experts: int = 0   # EP: experts padded to a multiple of ep_size
    # --- SSM (mamba2 / SSD) ---
    ssm_state: int = 0
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_chunk: int = 256
    conv_kernel: int = 4
    # --- hybrid (zamba2) ---
    attn_every: int = 0          # shared attention block every k ssm layers
    # --- enc-dec (whisper backbone) ---
    n_enc_layers: int = 0
    enc_len: int = 1500          # audio frame positions (stub frontend)
    # --- vlm (llava backbone) ---
    n_patches: int = 0           # image patch positions (stub frontend)
    # --- common ---
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    param_dtype: Any = jnp.bfloat16
    compute_dtype: Any = jnp.bfloat16
    # remat policy: "none" | "block" (checkpoint each layer in the scan)
    remat: str = "block"

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def d_inner(self) -> int:           # mamba2 inner width
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    def reduced(self, **overrides) -> "ArchConfig":
        """Tiny same-family config for CPU smoke tests."""
        base = dict(
            n_layers=min(self.n_layers, 2 if self.attn_every == 0 else
                         2 * self.attn_every),
            d_model=128, d_ff=256 if self.d_ff else 0,
            n_heads=4 if self.n_heads else 0,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads else 0,
            vocab_size=512, head_dim=32 if self.n_heads else 0,
            n_experts=min(self.n_experts, 4), top_k=min(self.top_k, 2),
            moe_groups=1, moe_impl="gspmd", moe_pad_experts=0,
            n_shared_experts=min(self.n_shared_experts, 1),
            ssm_state=min(self.ssm_state, 16), ssm_head_dim=32,
            ssm_chunk=16,
            n_enc_layers=min(self.n_enc_layers, 2), enc_len=24,
            n_patches=min(self.n_patches, 16),
            param_dtype=jnp.float32, compute_dtype=jnp.float32,
            remat="none",
        )
        if self.attn_every:
            base["attn_every"] = 2
            base["n_layers"] = 4
        base.update(overrides)
        return replace(self, **base)


# ---------------------------------------------------------------------------
def rmsnorm(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * w.astype(jnp.float32)).astype(dt)


def rope_angles(positions: jax.Array, head_dim: int, theta: float,
                ) -> tuple[jax.Array, jax.Array]:
    """(sin, cos) of shape (..., head_dim/2) for given integer positions."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                      dtype=jnp.float32) / head_dim))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: (..., T, H, hd); sin/cos: (..., T, hd/2) broadcast over heads."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    s, c = sin[..., None, :], cos[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c],
                           axis=-1).astype(x.dtype)


def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    fan_in = shape[in_axis]
    return (jax.random.normal(key, shape) / np.sqrt(fan_in)).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


def embed_lookup(table: jax.Array, tokens: jax.Array, dtype) -> jax.Array:
    """Rows ``table[tokens]`` in ``dtype``: (..., T) ids -> (..., T, d).

    Under a mesh with Explicit axes (``jax.make_mesh``'s default) the
    gather's output sharding cannot be inferred from a vocab-sharded table,
    so it is given: laid out like the ids, the embedding dim replicated.
    Callers pin the activation layout afterwards with ``constrain``."""
    table = table.astype(dtype)
    sharding = jax.typeof(table).sharding
    if jax.sharding.AxisType.Explicit not in sharding.mesh.axis_types:
        return table[tokens]
    spec = tuple(jax.typeof(tokens).sharding.spec)
    spec += (None,) * (tokens.ndim + 1 - len(spec))
    out = jax.sharding.NamedSharding(sharding.mesh,
                                     jax.sharding.PartitionSpec(*spec))
    return table.at[tokens].get(out_sharding=out)


def split_keys(key, n: int):
    return list(jax.random.split(key, n))


def stacked(init_fn, n_layers: int, key):
    """Initialize per-layer params stacked on axis 0 (for lax.scan)."""
    keys = jax.random.split(key, n_layers)
    return jax.vmap(init_fn)(keys)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: jax.Array | None = None) -> jax.Array:
    """Mean token NLL in fp32. labels: int32, mask: optional {0,1}."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None],
                               axis=-1).squeeze(-1)
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def count_params(params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
