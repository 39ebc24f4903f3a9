"""Cell builders: (architecture x shape x mesh) -> jitted, shard-annotated
step functions + ShapeDtypeStruct inputs, ready to .lower().compile().

No jax device-state mutation happens at import — dryrun.py sets XLA_FLAGS
for the 512-device host platform BEFORE importing this module.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, cell_runnable, get_config, input_specs
from repro.distributed import logical
from repro.distributed import sharding as shd
from repro.models.common import ArchConfig
from repro.models.transformer import build_model
from repro.train.optimizer import OptimizerConfig, build_optimizer
from repro.train.train_step import build_train_step

#: momentum-light optimizer for the HBM-bound giants (see DESIGN.md)
OPT_FOR_ARCH = {
    "kimi-k2-1t-a32b": OptimizerConfig(name="adafactor", momentum=False),
    "llava-next-34b": OptimizerConfig(name="adamw", moment_dtype=jnp.bfloat16),
}


def active_params(cfg: ArchConfig) -> float:
    """Per-token active parameter count (MoE: top_k + shared experts)."""
    d, hd = cfg.d_model, cfg.hd
    attn = d * hd * (2 * cfg.n_heads + 2 * cfg.n_kv_heads)
    if cfg.family == "mla_moe":
        h, ql, kl = cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank
        mla = (d * ql + ql * h * cfg.qk_head_dim
               + d * (kl + cfg.qk_rope_dim)
               + kl * h * (cfg.qk_nope_dim + cfg.v_head_dim)
               + h * cfg.v_head_dim * d)
        dense = 3 * d * cfg.d_ff
        moe = 3 * d * cfg.expert_ff * (cfg.top_k + cfg.n_shared_experts) \
            + d * cfg.n_experts
        n_moe = cfg.n_layers - cfg.n_dense_layers
        return cfg.n_layers * mla + cfg.n_dense_layers * dense \
            + n_moe * moe + 2 * cfg.vocab_size * d
    if cfg.family == "moe":
        ffn = 3 * d * cfg.d_ff * (cfg.top_k + cfg.n_shared_experts)
        ffn += d * cfg.n_experts                    # router
    elif cfg.family == "ssm":
        attn = 0
        din = cfg.d_inner
        ffn = d * (2 * din + 2 * cfg.ssm_state + cfg.ssm_heads) + din * d
    elif cfg.family == "hybrid":
        din = cfg.d_inner
        mamba = d * (2 * din + 2 * cfg.ssm_state + cfg.ssm_heads) + din * d
        shared = (attn + 3 * d * cfg.d_ff) / cfg.attn_every  # amortized
        return cfg.n_layers * (mamba + shared) + 2 * cfg.vocab_size * d
    else:
        ffn = 3 * d * cfg.d_ff
    layers = cfg.n_layers + cfg.n_enc_layers
    return layers * (attn + ffn) + 2 * cfg.vocab_size * d


@dataclass
class Cell:
    arch: str
    shape: str
    cfg: ArchConfig
    kind: str
    jitted: Any                 # jitted fn, ready to .lower(*args)
    args: tuple                 # ShapeDtypeStructs
    tokens_processed: float     # per step (for MODEL_FLOPS)
    n_active: float
    min_bytes: float = 0.0      # HBM-traffic floor (roofline denominator)


def _tree_bytes(tree) -> float:
    return float(sum(math.prod(x.shape) * x.dtype.itemsize
                     for x in jax.tree.leaves(tree)))


def vmem_kernel_bytes(cfg: ArchConfig, kind: str, B: int, S: int) -> float:
    """HBM bytes the Pallas kernels keep in VMEM on the TPU target, which
    the XLA-CPU lowering necessarily writes out (score/prob blocks of the
    chunked attention; SSD intra-chunk decay/CB matrices). Subtracting this
    from the measured HLO traffic gives the kernel-adjusted memory term —
    reported SEPARATELY from the raw baseline (EXPERIMENTS.md §Perf).

    Accounting: attention fwd materializes scores+probs (2 fp32 tensors);
    backward recomputes them and forms dP (3 more) -> ~5 x B*Hq*Tq*Tk*4 per
    layer for train, 2 x for inference. SSD analogous on (nc, H+1, Q, Q).
    """
    total = 0.0
    mult = 5.0 if kind == "train" else 2.0
    if cfg.n_heads:
        layers = cfg.n_layers + cfg.n_enc_layers
        if cfg.family == "hybrid":
            layers = cfg.n_layers // cfg.attn_every
        if cfg.family == "encdec":
            total = mult * 4.0 * B * cfg.n_heads * (
                cfg.n_layers * (S * S + S * cfg.enc_len)
                + cfg.n_enc_layers * cfg.enc_len * cfg.enc_len)
        else:
            seq = S + (cfg.n_patches if cfg.family == "vlm" else 0)
            total = mult * 4.0 * B * cfg.n_heads * layers * seq * seq
    if cfg.family in ("ssm", "hybrid"):
        Q = cfg.ssm_chunk
        nc = max(1, S // Q)
        total += mult * 4.0 * B * cfg.n_layers * nc * Q * Q * (
            cfg.ssm_heads + 1)
    if kind == "decode":
        total = 0.0     # decode kernels stream the cache; nothing to adjust
    return total


def min_step_bytes(kind: str, *, param_bytes: float, cache_bytes: float,
                   tokens: float, d_model: int, n_layers: int) -> float:
    """Minimum HBM traffic per step (the memory-roofline floor):
      train   : params fwd-read + bwd-read + grad write + opt update r/w
                (~5x params) + per-layer activation in/out (fwd+bwd)
      prefill : params read + KV-cache write + activations
      decode  : params read (every step reads all weights) + cache read
    """
    act = 4.0 * tokens * d_model * n_layers * 2.0    # bf16 in+out, fwd+bwd
    if kind == "train":
        return 5.0 * param_bytes + act
    if kind == "prefill":
        return param_bytes + cache_bytes + act / 2.0
    return param_bytes + cache_bytes


def _shaped(tree):
    """eval_shape result -> plain ShapeDtypeStruct tree (drop weak types)."""
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        tree)


def build_cell(arch: str, shape_id: str, mesh, *, grad_accum: int = 1,
               remat: str | None = None, extra_cfg: dict | None = None,
               ) -> Cell:
    from dataclasses import replace
    cfg = get_config(arch)
    logical.install(mesh)     # activation-sharding policy for trace time
    if cfg.family in ("moe", "mla_moe"):
        import math as _m
        shards = _m.prod(mesh.shape[a] for a in mesh.axis_names
                         if a in ("pod", "data"))
        cfg = replace(cfg, moe_groups=shards)
        if cfg.moe_impl == "ep":
            from repro.models.moe_ep import pad_experts
            cfg = replace(cfg, moe_pad_experts=pad_experts(cfg, mesh))
    if remat is not None or extra_cfg:
        over = dict(extra_cfg or {})
        if remat is not None:
            over["remat"] = remat
        cfg = replace(cfg, **over)
    shape = SHAPES[shape_id]
    ok, why = cell_runnable(cfg, shape)
    if not ok:
        raise ValueError(f"cell {arch}x{shape_id} skipped: {why}")
    model = build_model(cfg)
    pspecs = shd.param_specs(cfg, mesh)
    bspec_in = input_specs(arch, shape_id)
    if extra_cfg:   # reflect config overrides that change input widths
        pass
    bspecs = shd.batch_specs(cfg, mesh, bspec_in)
    n_active = active_params(cfg)
    B, S = shape.global_batch, shape.seq_len

    params_shape = jax.eval_shape(model.init, jax.random.key(0))
    pspecs = shd.fit_tree(mesh, pspecs, params_shape)
    param_bytes = _tree_bytes(params_shape)

    if shape.kind == "train":
        opt_cfg = OPT_FOR_ARCH.get(arch, OptimizerConfig())
        opt = build_optimizer(opt_cfg)
        step = build_train_step(model, opt, grad_accum=grad_accum)
        opt_shape = jax.eval_shape(opt.init, params_shape)
        state_shape = _shaped({"params": params_shape, "opt": opt_shape})
        ospecs = shd.opt_state_specs(opt_cfg.name, pspecs, params_shape)
        state_specs = {"params": pspecs, "opt": ospecs}
        bspecs = {k: shd.fit_spec(mesh, v, bspec_in[k].shape)
                  for k, v in bspecs.items()}
        in_sh = (shd.to_named(mesh, state_specs),
                 shd.to_named(mesh, bspecs))
        out_sh = (shd.to_named(mesh, state_specs),
                  {"loss": NamedSharding(mesh, P()),
                   "grad_norm": NamedSharding(mesh, P())})
        jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=(0,))
        return Cell(arch, shape_id, cfg, "train", jitted,
                    (state_shape, bspec_in), tokens_processed=B * S,
                    n_active=n_active,
                    min_bytes=min_step_bytes(
                        "train", param_bytes=param_bytes, cache_bytes=0.0,
                        tokens=B * S, d_model=cfg.d_model,
                        n_layers=cfg.n_layers + cfg.n_enc_layers))

    cspecs = shd.cache_specs(cfg, mesh)
    if shape.kind == "prefill":
        cache_shape = _shaped(jax.eval_shape(
            lambda: model.init_cache(B, S)))
        cspecs = shd.fit_tree(mesh, cspecs, cache_shape)
        def prefill(params, batch, cache):
            return model.prefill(params, batch, cache)
        bspecs = {k: shd.fit_spec(mesh, v, bspec_in[k].shape)
                  for k, v in bspecs.items()}
        logits_spec = shd.fit_spec(
            mesh, P(shd.batch_axes(mesh), None, "model"),
            (B, 1, cfg.vocab_size))
        in_sh = (shd.to_named(mesh, pspecs), shd.to_named(mesh, bspecs),
                 shd.to_named(mesh, cspecs))
        out_sh = (NamedSharding(mesh, logits_spec),
                  shd.to_named(mesh, cspecs))
        jitted = jax.jit(prefill, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=(2,))
        return Cell(arch, shape_id, cfg, "prefill", jitted,
                    (_shaped(params_shape), bspec_in, cache_shape),
                    tokens_processed=B * S, n_active=n_active,
                    min_bytes=min_step_bytes(
                        "prefill", param_bytes=param_bytes,
                        cache_bytes=_tree_bytes(cache_shape),
                        tokens=B * S, d_model=cfg.d_model,
                        n_layers=cfg.n_layers + cfg.n_enc_layers))

    # decode: one token against a populated cache of length S
    cache_shape = _shaped(jax.eval_shape(lambda: model.init_cache(B, S)))
    cspecs = shd.fit_tree(mesh, cspecs, cache_shape)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)

    def decode(params, tokens, cache):
        return model.decode(params, tokens, cache)

    tok_spec = shd.fit_spec(mesh, P(shd.batch_axes(mesh), None), (B, 1))
    logits_spec = shd.fit_spec(
        mesh, P(shd.batch_axes(mesh), None, "model"), (B, 1, cfg.vocab_size))
    in_sh = (shd.to_named(mesh, pspecs),
             NamedSharding(mesh, tok_spec),
             shd.to_named(mesh, cspecs))
    out_sh = (NamedSharding(mesh, logits_spec),
              shd.to_named(mesh, cspecs))
    jitted = jax.jit(decode, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=(2,))
    return Cell(arch, shape_id, cfg, "decode", jitted,
                (_shaped(params_shape), tok, cache_shape),
                tokens_processed=B, n_active=n_active,
                min_bytes=min_step_bytes(
                    "decode", param_bytes=param_bytes,
                    cache_bytes=_tree_bytes(cache_shape),
                    tokens=B, d_model=cfg.d_model,
                    n_layers=cfg.n_layers + cfg.n_enc_layers))
