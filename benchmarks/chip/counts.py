"""Operations and bytes the work needs, computed from shapes.

A count is of what the computation requires, not of what a kernel happens
to read: a decode-attention call needs the valid cache positions of the
slots that hold a request, so a kernel that skips masked blocks is credited
and the count does not go stale when it learns to.

Dimensions come from a configuration file's published keys (the
HuggingFace ``config.json`` names). Model FLOPs follow
``launch/roofline.py``'s ``model_flops_estimate`` (2 * N * tokens for a
forward pass), with N the parameters that take part in matrix products,
plus attention's score and value products.
"""
from __future__ import annotations

from dataclasses import dataclass

BF16 = 2


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int


def dims(config: dict) -> Dims:
    c = config["config"]
    heads = int(c["num_attention_heads"])
    d = int(c["hidden_size"])
    return Dims(layers=int(c["num_hidden_layers"]), d=d, heads=heads,
                kv_heads=int(c["num_key_value_heads"]),
                head_dim=int(c.get("head_dim") or d // heads),
                ff=int(c["intermediate_size"]),
                vocab=int(c["vocab_size"]))


def layer_matmul_params(m: Dims) -> int:
    """q, k, v, o projections and the gated MLP of one layer."""
    attn = m.d * (m.heads + 2 * m.kv_heads) * m.head_dim \
        + m.heads * m.head_dim * m.d
    return attn + 3 * m.d * m.ff


def attention_flops(m: Dims, queries: int, context: int) -> float:
    """Score and value products of ``queries`` tokens against ``context``
    positions each, over every layer."""
    return 4.0 * m.layers * m.heads * m.head_dim * queries * context


def prefill_flops(m: Dims, t: int) -> float:
    """A prompt of ``t`` tokens: every token through the layers, causal
    attention, and the output head for the last position only (the
    program samples the first token from it)."""
    return (2.0 * m.layers * layer_matmul_params(m) * t
            + 4.0 * m.layers * m.heads * m.head_dim * t * (t + 1) / 2
            + 2.0 * m.d * m.vocab)


def decode_flops(m: Dims, lengths: list[int]) -> float:
    """One decode step for the active slots, each attending its cache of
    ``length`` positions (the new token included)."""
    per_tok = 2.0 * (m.layers * layer_matmul_params(m) + m.d * m.vocab)
    return sum(per_tok + attention_flops(m, 1, n) for n in lengths)


def decode_attention_call(m: Dims, lengths: list[int]) -> tuple[float,
                                                                float]:
    """(FLOPs, bytes) one layer's decode-attention call needs: q and the
    output for each active slot, and the valid K and V positions."""
    flops = sum(4.0 * m.heads * m.head_dim * n for n in lengths)
    qo = 2 * len(lengths) * m.heads * m.head_dim * BF16
    kv = sum(2 * n * m.kv_heads * m.head_dim * BF16 for n in lengths)
    return flops, float(qo + kv + 4 * len(lengths))


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float,
                                                                 str]:
    """The roofline bound: the larger of compute and memory time, and
    which of the two it is."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "flops") if tc >= tm else (tm, "bytes")
