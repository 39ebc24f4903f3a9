"""Operations and bytes the work of a latent-attention MoE model needs
(Kimi-K2's block, DeepSeek-V3 form), computed from a configuration file's
published keys, as ``counts.py`` does for a dense model.

Prefill runs MLA non-absorbed: per head q and k of qk_nope + qk_rope and
v of v_head_dim over the prompt. Decode runs it absorbed: q_nope goes into
the latent space through kv_b's key half, attention reads the latent
cache (kv_lora_rank + qk_rope values a position) and kv_b's value half
maps the result back. The held experts' rows are an expectation: each
token picks ``num_experts_per_tok`` of the published ``n_routed_experts``,
so the experts held here see tokens x k x held / routed rows on average
(the program's rows depend on the routing and are not read back).
"""
from __future__ import annotations

from dataclasses import dataclass

from counts import BF16, least_time  # noqa: F401  (least_time for readers)


@dataclass(frozen=True)
class Dims:
    layers: int
    dense_layers: int
    d: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    ff: int
    expert_ff: int
    routed: int          # the router's width, the published expert count
    held: int            # experts held here
    top_k: int
    shared: int
    vocab: int


def dims(config: dict) -> Dims:
    c = config["config"]
    return Dims(layers=int(c["num_hidden_layers"]),
                dense_layers=int(c["first_k_dense_replace"]),
                d=int(c["hidden_size"]), heads=int(c["num_attention_heads"]),
                q_rank=int(c["q_lora_rank"]), kv_rank=int(c["kv_lora_rank"]),
                nope=int(c["qk_nope_head_dim"]),
                rope=int(c["qk_rope_head_dim"]), v=int(c["v_head_dim"]),
                ff=int(c["intermediate_size"]),
                expert_ff=int(c["moe_intermediate_size"]),
                routed=int(config["published"]["n_routed_experts"]),
                held=int(c["n_routed_experts"]),
                top_k=int(c["num_experts_per_tok"]),
                shared=int(c["n_shared_experts"]),
                vocab=int(c["vocab_size"]))


def _q_and_kv_a(m: Dims) -> int:
    """Parameters of q_a, q_b and kv_a (both forms run them)."""
    return (m.d * m.q_rank + m.q_rank * m.heads * (m.nope + m.rope)
            + m.d * (m.kv_rank + m.rope))


def _ffn_flops_per_token(m: Dims) -> float:
    """Every layer's FFN for one token: the dense layers' SwiGLU; the MoE
    layers' router, shared expert and expected held-expert rows."""
    moe = (2 * m.d * m.routed
           + 6 * m.d * m.expert_ff * (m.shared + m.top_k * m.held / m.routed))
    return (m.dense_layers * 6.0 * m.d * m.ff
            + (m.layers - m.dense_layers) * moe)


def expected_held_rows(m: Dims, tokens: int) -> float:
    """Rows the held experts of one MoE layer see for ``tokens`` tokens,
    on average."""
    return tokens * m.top_k * m.held / m.routed


def prefill_flops(m: Dims, t: int) -> float:
    """A prompt of ``t`` tokens, non-absorbed MLA with causal attention,
    the FFNs, and the head for the last position only."""
    proj = _q_and_kv_a(m) + m.kv_rank * m.heads * (m.nope + m.v) \
        + m.heads * m.v * m.d
    attn = 2.0 * m.heads * (m.nope + m.rope + m.v) * t * (t + 1) / 2
    return (m.layers * (2.0 * proj * t + attn)
            + _ffn_flops_per_token(m) * t + 2.0 * m.d * m.vocab)


def decode_flops(m: Dims, lengths: list[int]) -> float:
    """One decode step for the active slots, each attending ``length``
    cached positions (the new token's included), absorbed MLA."""
    proj = _q_and_kv_a(m) + m.heads * m.nope * m.kv_rank \
        + m.heads * m.kv_rank * m.v + m.heads * m.v * m.d
    per_tok = (2.0 * m.layers * proj + _ffn_flops_per_token(m)
               + 2.0 * m.d * m.vocab)
    return sum(per_tok + m.layers * mla_decode_call(m, [n])[0]
               for n in lengths)


def mla_decode_call(m: Dims, lengths: list[int]) -> tuple[float, float]:
    """(FLOPs, bytes) one layer's MLA decode-attention call needs: scores
    over the kv_rank + rope latent and the weighted sum of its kv_rank
    rows, for every head; bytes of the valid latent positions, each
    slot's query and output, and the lengths."""
    width = m.kv_rank + m.rope
    flops = sum(2.0 * m.heads * (width + m.kv_rank) * n for n in lengths)
    cache = sum(n * width * BF16 for n in lengths)
    qo = len(lengths) * m.heads * (width + m.kv_rank) * BF16
    return flops, float(cache + qo + 4 * len(lengths))
