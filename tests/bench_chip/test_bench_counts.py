"""FLOPs and bytes from shapes, for phi3-mini and for SmolLM-360M's
grouped heads, against values worked by hand from their published
widths."""
from __future__ import annotations

import json

import pytest

import bench_chip_util as u
import counts


#: SmolLM-360M's published widths (HF config.json): grouped KV heads
SMOLLM = {"config": {"num_hidden_layers": 32, "hidden_size": 960,
                     "num_attention_heads": 15, "num_key_value_heads": 5,
                     "intermediate_size": 2560, "vocab_size": 49152}}


def dims(name):
    if name == "smollm-360m":
        return counts.dims(SMOLLM)
    return counts.dims(json.loads(
        (u.BENCH / "configs" / f"{name}.json").read_text()))


PEAK = json.loads((u.BENCH / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]


def test_phi3_mini_parameters():
    m = dims("phi3-mini")
    assert (m.layers, m.d, m.heads, m.kv_heads, m.head_dim, m.ff, m.vocab) \
        == (32, 3072, 32, 32, 96, 8192, 32064)
    # q,k,v: 3072 x (32+32+32) x 96; o: 3072 x 3072; MLP 3 x 3072 x 8192
    assert counts.layer_matmul_params(m) == 28_311_552 + 9_437_184 \
        + 75_497_472
    # 32 layers + head = 3.72 G; with the 98.5 M embedding, 3.82 G
    total = 32 * 113_246_208 + 3072 * 32064
    assert counts.decode_flops(m, [1]) == pytest.approx(
        2 * total + 4 * 32 * 32 * 96 * 1)


def test_smollm_360m_parameters():
    m = dims("smollm-360m")
    assert (m.heads, m.kv_heads, m.head_dim) == (15, 5, 64)
    # q: 960 x 960, k and v: 960 x 320 each, o: 960 x 960
    assert counts.layer_matmul_params(m) == 921_600 + 2 * 307_200 \
        + 921_600 + 3 * 960 * 2560
    assert counts.layer_matmul_params(m) == 9_830_400


def test_prefill_flops_phi3_2048():
    m = dims("phi3-mini")
    t = 2048
    want = (2 * 32 * 113_246_208 * t          # projections and MLP
            + 4 * 32 * 32 * 96 * t * (t + 1) / 2   # causal attention
            + 2 * 3072 * 32064)                  # head, last position
    assert counts.prefill_flops(m, t) == pytest.approx(want)
    assert counts.prefill_flops(m, t) / 1e12 == pytest.approx(15.6686, 1e-4)


def test_decode_attention_call_counts_valid_positions_only():
    m = dims("phi3-mini")
    fl, by = counts.decode_attention_call(m, [1000, 2000])
    assert fl == 4 * 32 * 96 * 3000
    # q and out: 2 x 2 slots x 32 heads x 96 x 2 B; K,V: 2 x 3000 x 32 x
    # 96 x 2 B; lengths: 2 x 4 B
    assert by == 24_576 + 36_864_000 + 8
    t, bound = counts.least_time(fl, by, PEAK)
    assert bound == "bytes"
    assert t == pytest.approx(by / 819e9)


def test_decode_attention_smollm_groups_heads():
    m = dims("smollm-360m")
    fl, by = counts.decode_attention_call(m, [256])
    assert fl == 4 * 15 * 64 * 256
    assert by == 2 * 15 * 64 * 2 + 2 * 256 * 5 * 64 * 2 + 4
