"""kimi-k2-chip — one TPU v5e chip's share of kimi-k2-1t-a32b, as the
model-configs guide cuts it: each MoE layer's 384 experts spread over 48
chips by expert parallelism, 8 a chip (experts 0-7 held here, routed over
all 384, dropless); MLA and the dense layer data-parallel on every chip;
the vocabulary split 8 ways (ids 0-20479 here); 9 layers (the dense layer
and 8 MoE layers), the rest on further pipeline stages. Every width is
the published one."""
from dataclasses import replace

from repro.configs.kimi_k2_1t_a32b import CONFIG as KIMI

CONFIG = replace(KIMI, name="kimi-k2-chip", n_layers=9, vocab_size=20480,
                 expert_lo=0, n_held_experts=8)
