"""Sharding rules: PartitionSpecs for every param/cache/batch pytree, per
architecture family and mesh.

Strategy (baseline — §Perf iterates on it):
  * TP over "model": attention heads / d_ff / experts / vocab;
  * FSDP over "data": the non-TP matrix dimension of every large weight;
  * batch over ("pod", "data");
  * "pod" additionally FSDP-shards MoE expert weights (the 1T cells are
    HBM-bound on params — see EXPERIMENTS.md §Dry-run);
  * KV caches shard heads over "model" when H_kv >= axis size, else head_dim;
  * SSM states shard heads over "model", batch over data.

Everything returns pytrees OF PartitionSpec with the exact structure of the
corresponding param/cache pytrees (NamedTuples preserved — tree.map over
mixed structures relies on it).
"""
from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.distributed.logical import gspmd
from repro.models.attention import AttnParams
from repro.models.common import ArchConfig
from repro.models.ffn import MLPParams, MoEParams
from repro.models.mamba2 import Mamba2Params
from repro.models.transformer import (DecLayer, DenseLayer, EncLayer,
                                      MoELayer, SSMLayer)


def _axis(mesh, name: str) -> int:
    return mesh.shape[name]


def batch_axes(mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else "data"


# ---------------------------------------------------------------------------
# per-structure specs (leading L axis on stacked layer params is unsharded)
# ---------------------------------------------------------------------------
def attn_specs(l=None) -> AttnParams:
    pre = (l,) if l is not None else ()
    lead = (None,) * len(pre)
    return AttnParams(
        wq=P(*lead, "data", "model"),
        wk=P(*lead, "data", "model"),
        wv=P(*lead, "data", "model"),
        wo=P(*lead, "model", "data"),
    )


def mlp_specs(l=None) -> MLPParams:
    lead = (None,) if l is not None else ()
    return MLPParams(
        w_gate=P(*lead, "data", "model"),
        w_up=P(*lead, "data", "model"),
        w_down=P(*lead, "model", "data"),
    )


def moe_specs(cfg: ArchConfig, mesh, l=None) -> MoEParams:
    lead = (None,) if l is not None else ()
    # experts over model (EP) + Megatron col/row split of each expert's MLP
    # over data: the d-dim contraction stays LOCAL (no weight all-gather —
    # the naive d-over-data layout all-gathered 1.1 TB/step on kimi decode);
    # "pod" additionally shards the expert dim when present and divisible
    # (1T params / 512 chips relief valve).
    if cfg.moe_impl == "ep" and cfg.moe_pad_experts:
        # EP: whole experts sharded over EVERY mesh axis (tokens a2a to them)
        e_axis = tuple(mesh.axis_names)
        return MoEParams(
            router=P(*lead, None, None),
            w_gate=P(*lead, e_axis, None, None),
            w_up=P(*lead, e_axis, None, None),
            w_down=P(*lead, e_axis, None, None),
            shared=mlp_specs(l) if cfg.n_shared_experts else None,
        )
    e_axis: object = "model"
    if "pod" in mesh.axis_names and cfg.n_experts % (
            _axis(mesh, "model") * _axis(mesh, "pod")) == 0:
        e_axis = ("pod", "model")
    # gspmd grouped dispatch: experts over model; when the per-model-shard
    # slab is small (qwen-class), keep d/ff unsharded so the expert einsum
    # is fully local; big models use the EP path instead
    per_shard_gb = (cfg.n_experts / _axis(mesh, "model") * cfg.d_model
                    * cfg.d_ff * 3 * 2 * (cfg.n_layers)) / 1e9
    if per_shard_gb <= 4.0:
        return MoEParams(
            router=P(*lead, None, None),
            w_gate=P(*lead, e_axis, None, None),
            w_up=P(*lead, e_axis, None, None),
            w_down=P(*lead, e_axis, None, None),
            shared=mlp_specs(l) if cfg.n_shared_experts else None,
        )
    return MoEParams(
        router=P(*lead, None, None),
        w_gate=P(*lead, e_axis, "data", None),
        w_up=P(*lead, e_axis, "data", None),
        w_down=P(*lead, e_axis, "data", None),
        shared=mlp_specs(l) if cfg.n_shared_experts else None,
    )


def mamba_specs(cfg: ArchConfig, l=None) -> Mamba2Params:
    lead = (None,) if l is not None else ()
    return Mamba2Params(
        in_proj=P(*lead, "data", "model"),
        conv_w=P(*lead, None, "model"),
        conv_b=P(*lead, "model"),
        dt_bias=P(*lead, None),
        A_log=P(*lead, None),
        D=P(*lead, None),
        norm_w=P(*lead, "model"),
        out_proj=P(*lead, "model", "data"),
    )


def _norm(l=None):
    return P(None, None) if l is not None else P(None)


# ---------------------------------------------------------------------------
def param_specs(cfg: ArchConfig, mesh):
    """Pytree of PartitionSpec matching model.init's structure."""
    fam = cfg.family
    if fam in ("dense", "vlm"):
        specs = {
            "embed": P("model", "data"),
            "layers": DenseLayer(attn=attn_specs(l=0), mlp=mlp_specs(l=0),
                                 norm1=_norm(0), norm2=_norm(0)),
            "final_norm": _norm(),
            "lm_head": P("data", "model"),
        }
        if fam == "vlm":
            specs["patch_proj"] = P("data", "model")
        return specs
    if fam == "moe":
        return {
            "embed": P("model", "data"),
            "layers": MoELayer(attn=attn_specs(l=0),
                               moe=moe_specs(cfg, mesh, l=0),
                               norm1=_norm(0), norm2=_norm(0)),
            "final_norm": _norm(),
            "lm_head": P("data", "model"),
        }
    if fam == "ssm":
        return {
            "embed": P("model", "data"),
            "layers": SSMLayer(mamba=mamba_specs(cfg, l=0), norm=_norm(0)),
            "final_norm": _norm(),
            "lm_head": P("data", "model"),
        }
    if fam == "hybrid":
        # layers have an extra (group, per_group) leading pair
        def g(spec_fn):
            base = spec_fn(cfg, l=0) if spec_fn is mamba_specs else spec_fn(0)
            return jax.tree.map(lambda s: P(None, *s), base,
                                is_leaf=lambda x: isinstance(x, P))
        return {
            "embed": P("model", "data"),
            "layers": SSMLayer(mamba=g(mamba_specs),
                               norm=P(None, None, None)),
            "shared_attn": attn_specs(),
            "shared_mlp": mlp_specs(),
            "shared_norm1": _norm(), "shared_norm2": _norm(),
            "final_norm": _norm(),
            "lm_head": P("data", "model"),
        }
    if fam == "encdec":
        return {
            "embed": P("model", "data"),
            "enc_layers": EncLayer(attn=attn_specs(l=0), mlp=mlp_specs(l=0),
                                   norm1=_norm(0), norm2=_norm(0)),
            "dec_layers": DecLayer(self_attn=attn_specs(l=0),
                                   cross_attn=attn_specs(l=0),
                                   mlp=mlp_specs(l=0), norm1=_norm(0),
                                   norm2=_norm(0), norm3=_norm(0)),
            "enc_norm": _norm(),
            "final_norm": _norm(),
            "lm_head": P("data", "model"),
        }
    raise ValueError(fam)


# ---------------------------------------------------------------------------
def _kv_spec(cfg: ArchConfig, mesh, *, lead: int) -> P:
    """(lead..., B, S, H_kv, hd): shard heads over model if divisible-ish,
    else shard head_dim."""
    m = _axis(mesh, "model")
    pre = (None,) * lead
    b = batch_axes(mesh)
    if cfg.n_kv_heads >= m:
        return P(*pre, b, None, "model", None)
    return P(*pre, b, None, None, "model")


def cache_specs(cfg: ArchConfig, mesh):
    """Pytree of PartitionSpec matching model.init_cache's structure."""
    fam = cfg.family
    b = batch_axes(mesh)
    if fam in ("dense", "vlm", "moe"):
        return {"k": _kv_spec(cfg, mesh, lead=1),
                "v": _kv_spec(cfg, mesh, lead=1),
                "index": P(b)}
    if fam == "ssm":
        return {"state": _mamba_state_spec(cfg, mesh, lead=1),
                "index": P(b)}
    if fam == "hybrid":
        return {"state": _mamba_state_spec(cfg, mesh, lead=2),
                "k": _kv_spec(cfg, mesh, lead=1),
                "v": _kv_spec(cfg, mesh, lead=1),
                "index": P(b)}
    if fam == "encdec":
        return {"k": _kv_spec(cfg, mesh, lead=1),
                "v": _kv_spec(cfg, mesh, lead=1),
                "cross_k": _kv_spec(cfg, mesh, lead=1),
                "cross_v": _kv_spec(cfg, mesh, lead=1),
                "index": P(b)}
    raise ValueError(fam)


def _mamba_state_spec(cfg: ArchConfig, mesh, *, lead: int):
    from repro.models.mamba2 import MambaState
    pre = (None,) * lead
    b = batch_axes(mesh)
    return MambaState(
        conv_tail=P(*pre, b, None, "model"),
        ssm=P(*pre, b, "model", None, None),
    )


# ---------------------------------------------------------------------------
def batch_specs(cfg: ArchConfig, mesh, batch: dict) -> dict:
    """Input batch: shard the leading (global batch) dim."""
    b = batch_axes(mesh)
    out = {}
    for k, v in batch.items():
        if k in ("frames", "patch_embeds"):
            out[k] = P(b, None, None)
        else:
            out[k] = P(b, None)
    return out


def opt_state_specs(opt_name: str, pspecs, params_shape):
    """Optimizer-slot specs derived from param specs.
    adamw: m/v mirror params. adafactor: row drops the last param axis,
    col drops the second-to-last."""
    if opt_name == "adamw":
        return {"m": pspecs, "v": pspecs, "step": P()}

    def slot_spec(spec: P, shape):
        if len(shape) >= 2:
            return {"row": P(*spec[:-1]), "col": P(*spec[:-2], spec[-1])}
        return {"v": spec}

    leaves_s, treedef = jax.tree.flatten(
        pspecs, is_leaf=lambda x: isinstance(x, P))
    leaves_p = treedef.flatten_up_to(params_shape)
    v = jax.tree.unflatten(
        treedef, [slot_spec(s, p.shape) for s, p in zip(leaves_s, leaves_p)])
    return {"v": v, "step": P()}


def _axes_size(mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        import math
        return math.prod(mesh.shape[a] for a in entry)
    return mesh.shape[entry]


def fit_spec(mesh, spec: P, shape) -> P:
    """Drop mesh axes that do not divide the corresponding dim (jit
    in_shardings require exact divisibility; e.g. whisper's vocab 51865 is
    indivisible by any axis -> replicate that dim)."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    fitted = []
    for dim, entry in zip(shape, entries[:len(shape)]):
        if entry is not None and dim % _axes_size(mesh, entry) != 0:
            # try single-axis fallback for multi-axis entries
            if isinstance(entry, (tuple, list)):
                kept = [a for a in entry
                        if dim % mesh.shape[a] == 0]
                entry = tuple(kept[:1]) if kept else None
                if entry and dim % _axes_size(mesh, entry) != 0:
                    entry = None
            else:
                entry = None
        fitted.append(entry)
    return P(*fitted)


def fit_tree(mesh, spec_tree, shape_tree):
    """fit_spec over matching pytrees (NamedTuple structures preserved)."""
    leaves_s, treedef = jax.tree.flatten(
        spec_tree, is_leaf=lambda x: isinstance(x, P))
    leaves_x = treedef.flatten_up_to(shape_tree)
    fitted = [fit_spec(mesh, s, x.shape) for s, x in zip(leaves_s, leaves_x)]
    return jax.tree.unflatten(treedef, fitted)


def to_named(mesh, spec_tree):
    mesh = gspmd(mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
