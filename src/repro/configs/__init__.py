"""Architecture registry + input-shape cells.

``get_config(arch_id)`` returns the exact published config;
``input_specs(arch_id, shape_id)`` returns ShapeDtypeStruct stand-ins for
every model input of that (arch x shape) cell — weak-type-correct, shardable,
zero allocation (the dry-run contract).
"""
from __future__ import annotations

from dataclasses import dataclass
from importlib import import_module

import jax
import jax.numpy as jnp

from repro.models.common import ArchConfig

_MODULES = {
    "phi3-mini-3.8b": "phi3_mini_3_8b",
    "minitron-4b": "minitron_4b",
    "smollm-360m": "smollm_360m",
    "smollm-135m": "smollm_135m",
    "whisper-tiny": "whisper_tiny",
    "kimi-k2-1t-a32b": "kimi_k2_1t_a32b",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "zamba2-2.7b": "zamba2_2_7b",
    "llava-next-34b": "llava_next_34b",
    "mamba2-1.3b": "mamba2_1_3b",
    # the paper's own §5 models (extra, not part of the 40-cell table)
    "llama-3.2-1b": "llama3_2_1b",
    "llama-3.1-8b": "llama3_1_8b",
    # one chip's share of kimi-k2-1t-a32b (the chip benchmark's cell)
    "kimi-k2-chip": "kimi_k2_chip",
}

#: the 10 assigned architectures (40-cell table rows)
ASSIGNED = [k for k in _MODULES
            if not k.startswith("llama") and k != "kimi-k2-chip"]


def get_config(arch_id: str) -> ArchConfig:
    mod = import_module(f"repro.configs.{_MODULES[arch_id]}")
    return mod.CONFIG


@dataclass(frozen=True)
class ShapeCell:
    name: str
    kind: str          # train | prefill | decode
    seq_len: int
    global_batch: int
    subquadratic_only: bool = False


SHAPES = {
    "train_4k": ShapeCell("train_4k", "train", 4096, 256),
    "prefill_32k": ShapeCell("prefill_32k", "prefill", 32768, 32),
    "decode_32k": ShapeCell("decode_32k", "decode", 32768, 128),
    "long_500k": ShapeCell("long_500k", "decode", 524288, 1,
                           subquadratic_only=True),
}

#: families with O(1)-state decode (eligible for long_500k)
SUBQUADRATIC_FAMILIES = {"ssm", "hybrid"}


def cell_runnable(cfg: ArchConfig, shape: ShapeCell) -> tuple[bool, str]:
    """(runnable, reason-if-skipped) — see DESIGN.md shape-cell skips."""
    if shape.subquadratic_only and cfg.family not in SUBQUADRATIC_FAMILIES:
        return False, ("full-attention arch: 500k dense-KV decode is "
                       "out of contract (sub-quadratic-only cell)")
    return True, ""


def input_specs(arch_id: str, shape_id: str, *, reduced: bool = False,
                ) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of the cell.
    For train: token batch (+labels); for prefill: request batch; for
    decode: one new token per sequence (KV/state cache is threaded
    separately as ``state_specs``)."""
    cfg = get_config(arch_id)
    if reduced:
        cfg = cfg.reduced()
    shape = SHAPES[shape_id]
    B, S = shape.global_batch, shape.seq_len
    if reduced:
        B, S = 2, min(S, 64)
    tok = jax.ShapeDtypeStruct
    i32 = jnp.int32

    if shape.kind == "train":
        specs = {"tokens": tok((B, S), i32), "labels": tok((B, S), i32)}
        if cfg.family == "encdec":
            specs["frames"] = tok((B, cfg.enc_len, cfg.d_model),
                                  cfg.compute_dtype)
        if cfg.family == "vlm":
            # total sequence = patches + text = S (anyres prefix)
            specs["tokens"] = tok((B, S - cfg.n_patches), i32)
            specs["labels"] = tok((B, S - cfg.n_patches), i32)
            specs["patch_embeds"] = tok((B, cfg.n_patches, cfg.d_model),
                                        cfg.compute_dtype)
        return specs

    if shape.kind == "prefill":
        specs = {"tokens": tok((B, S), i32)}
        if cfg.family == "encdec":
            specs["frames"] = tok((B, cfg.enc_len, cfg.d_model),
                                  cfg.compute_dtype)
        if cfg.family == "vlm":
            specs["tokens"] = tok((B, S - cfg.n_patches), i32)
            specs["patch_embeds"] = tok((B, cfg.n_patches, cfg.d_model),
                                        cfg.compute_dtype)
        return specs

    # decode: one new token against a cache of length S
    return {"tokens": tok((B, 1), i32)}


def cache_specs(arch_id: str, shape_id: str, *, reduced: bool = False) -> dict:
    """ShapeDtypeStructs of the decode-cell cache/state pytree."""
    from repro.models.transformer import build_model
    cfg = get_config(arch_id)
    if reduced:
        cfg = cfg.reduced()
    shape = SHAPES[shape_id]
    B, S = shape.global_batch, shape.seq_len
    if reduced:
        B, S = 2, min(S, 64)
    model = build_model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(B, S))
    return cache
