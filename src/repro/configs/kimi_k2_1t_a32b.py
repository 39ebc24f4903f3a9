"""kimi-k2-1t-a32b — Kimi-K2-Instruct (moonshotai), the DeepSeek-V3 block:
[mla_moe] 61L d_model=7168, MLA with 64 heads (q_lora 1536, kv_lora 512,
qk 128 nope + 64 rope, v 128), layer 0 a dense SwiGLU of 18432, then 60
MoE layers of 384 routed experts of 2048 (top-8, sigmoid noaux_tc routing,
normalised, x2.827) and 1 shared expert; YaRN factor 32 over 4096,
rope_theta 50000; vocab 163840, untied
[hf:moonshotai/Kimi-K2-Instruct config.json]. Active ~32B/tok."""
from repro.models.common import ArchConfig

CONFIG = ArchConfig(
    name="kimi-k2-1t-a32b", family="mla_moe",
    n_layers=61, d_model=7168, n_heads=64, n_kv_heads=64,
    d_ff=18432, vocab_size=163840,
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64,
    v_head_dim=128,
    n_dense_layers=1, moe_d_ff=2048, n_experts=384, top_k=8,
    n_shared_experts=1,
    router="sigmoid", router_scale=2.827, router_bias_std=0.01,
    rope_theta=50000.0, rope_factor=32.0, rope_original_max=4096,
    yarn_beta_fast=1.0, yarn_beta_slow=1.0, yarn_mscale=1.0,
    yarn_mscale_all_dim=1.0, norm_eps=1e-6,
    moe_impl="ep",   # a2a expert parallelism (weights never move)
)
