"""Kimi-K2-Instruct's block in the program (latent attention with a latent
cache, a leading dense layer, the experts held here behind sigmoid
noaux_tc routing) against its plain reference ``reference/mla_moe.py``,
and the harness on a tiny cell of it. Tiny widths on the CPU; the weights
are the program's own, made again by the reference from the seed."""
from __future__ import annotations

import copy
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_chip_util as u
from program import program_config, use_checkout
from reference import mla_moe

use_checkout(u.REPO)
SEED = 2**31 + 91
KIMI = json.loads((u.BENCH / "configs" / "kimi-k2.json").read_text())


def tiny_kimi(*, held: int = 4, lo: int = 0, experts: int = 32) -> dict:
    """The cell's configuration file at tiny widths: 1 dense + 2 MoE
    layers, ``held`` of ``experts`` experts from ``lo``, YaRN past 16
    positions; the program is cut to match (``program_overrides``)."""
    cfg = copy.deepcopy(KIMI)
    cfg["program_overrides"] = {
        "n_layers": 3, "d_model": 64, "n_heads": 4, "q_lora_rank": 32,
        "kv_lora_rank": 32, "qk_nope_dim": 16, "qk_rope_dim": 8,
        "v_head_dim": 16, "d_ff": 128, "moe_d_ff": 32, "n_experts": experts,
        "expert_lo": lo, "n_held_experts": held, "vocab_size": 256,
        "rope_original_max": 16}
    cfg["config"].update({
        "num_hidden_layers": 3, "hidden_size": 64, "num_attention_heads": 4,
        "q_lora_rank": 32, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 128,
        "moe_intermediate_size": 32, "n_routed_experts": held,
        "vocab_size": 256})
    cfg["config"]["rope_scaling"] = {**cfg["config"]["rope_scaling"],
                                     "original_max_position_embeddings": 16}
    cfg["published"] = {**cfg["published"], "n_routed_experts": experts}
    cfg["assumed"] = {**cfg["assumed"], "first_held_expert": lo}
    # at this width on the CPU the program reads 0.000-0.032 and the fp8
    # control 0.24-1.32 (six seeds), routing ties left out; planted
    # faults read far above
    cfg["check"] = {"logit_gap_limit": 0.1, "sample_requests": 4}
    return cfg


def program(cfg_file: dict, f32: bool = False):
    from repro.models.transformer import build_model
    cfg = program_config(cfg_file, mla_moe)
    if f32:
        cfg = dataclasses.replace(cfg, param_dtype=jnp.float32,
                                  compute_dtype=jnp.float32)
    model = build_model(cfg)
    return cfg, model, jax.jit(model.init)(jax.random.key(SEED))


def as_served(params):
    """float32 params holding the served bfloat16 values; the router and
    its bias stay float32, as the program keeps them."""
    out = jax.tree.map(lambda x: x.astype(jnp.bfloat16).astype(jnp.float32),
                       params)
    moe = params["layers"].ffn
    out["layers"] = out["layers"]._replace(
        ffn=out["layers"].ffn._replace(router=moe.router, bias=moe.bias))
    return out


def test_weights_are_the_programs_bit_for_bit():
    cfg_file = tiny_kimi()
    _, _, params = program(cfg_file)
    w = mla_moe.Weights(cfg_file, SEED)
    f32 = lambda x: np.asarray(x.astype(jnp.float32))  # noqa: E731
    np.testing.assert_array_equal(w.embed(), f32(params["embed"]))
    np.testing.assert_array_equal(w.head(), f32(params["lm_head"]))
    dense = w.layer(0)
    lp = jax.tree.map(lambda x: x[0], params["dense_layers"])
    for name in ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo"):
        np.testing.assert_array_equal(dense[name],
                                      f32(getattr(lp.attn, name)))
    np.testing.assert_array_equal(dense["mlp"]["down"], f32(lp.ffn.w_down))
    for i in (1, 2):
        moe = w.layer(i)
        lp = jax.tree.map(lambda x, i=i: x[i - 1], params["layers"])
        np.testing.assert_array_equal(moe["wkv_b"], f32(lp.attn.wkv_b))
        np.testing.assert_array_equal(moe["router"], np.asarray(lp.ffn.router))
        np.testing.assert_array_equal(moe["bias"], np.asarray(lp.ffn.bias))
        for j, e in enumerate(moe["experts"]):
            np.testing.assert_array_equal(e["gate"], f32(lp.ffn.w_gate[j]))
            np.testing.assert_array_equal(e["down"], f32(lp.ffn.w_down[j]))
        np.testing.assert_array_equal(moe["shared"]["up"],
                                      f32(lp.ffn.shared.w_up))


def test_prefill_then_decode_through_the_latent_cache_is_the_full_forward():
    """The program's prefill of 20 tokens, then 20 absorbed decode steps
    through the latent cache, to position 39, past the tiny
    ``original_max_position_embeddings`` of 16 (so YaRN's ramp and its
    divided frequencies are both in use), give the reference's full
    forward's logits at every position, float32 both ways."""
    cfg_file = tiny_kimi()
    _, model, params = program(cfg_file, f32=True)
    params = as_served(params)
    toks = np.random.default_rng(5).integers(0, 256, (2, 40), np.int32)
    want = mla_moe.logits_at(cfg_file, SEED, list(toks))
    cache = model.init_cache(2, 64)
    logits, cache = model.prefill(params, {"tokens": jnp.asarray(toks[:, :20])},
                                  cache)
    got = [logits[:, 0]]
    decode = jax.jit(model.decode)
    for t in range(20, 40):
        logits, cache = decode(params, jnp.asarray(toks[:, t:t + 1]), cache)
        got.append(logits[:, 0])
    got = np.stack([np.asarray(g) for g in got], axis=1)       # (2, 21, V)
    for b in range(2):
        np.testing.assert_allclose(got[b], np.asarray(want[b][19:]),
                                   atol=2e-4, rtol=2e-4)
    assert int(cache["index"][0]) == 40


def _moe_inputs(cfg_file, T=64):
    """The program's first MoE layer (its share of experts, as the
    configuration file holds them) and a batch of inputs to it."""
    cfg, _, params = program(cfg_file)
    p = jax.tree.map(lambda a: a[0], params["layers"]).ffn
    x = jax.random.normal(jax.random.key(7), (T, cfg.d_model), jnp.float32)
    return cfg, p, x


def _reference_layer(cfg_file, bias=None):
    w = mla_moe.Weights(cfg_file, SEED)
    lw = w.layer(1)
    if bias is not None:
        lw = {**lw, "bias": bias}
    return w.m, lw


def _as_f32(cfg, p):
    cfg = dataclasses.replace(cfg, param_dtype=jnp.float32,
                              compute_dtype=jnp.float32)
    return cfg, jax.tree.map(lambda a: a.astype(jnp.float32), p)


def test_shares_of_disjoint_experts_sum_to_the_uncut_layer():
    """model-configs §4: eight chips' shares of 4 experts each, summed
    with the shared expert counted once, equal the reference's layer
    holding all 32."""
    from repro.models.ffn import held_experts_block, swiglu
    total, shared = 0.0, None
    for lo in range(0, 32, 4):
        cfg, p, x = _moe_inputs(tiny_kimi(lo=lo, held=4))
        cfg, p = _as_f32(cfg, p)
        total = total + held_experts_block(p, x[None], cfg)[0][0]
        shared = swiglu(p.shared, x, jnp.float32)
    total = total - 7 * shared
    m, lw = _reference_layer(tiny_kimi(held=32))
    want = mla_moe._moe(m, lw, x, False)[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_routing_forced_onto_one_held_expert_drops_no_token(monkeypatch):
    """A bias that sends every token to held expert 1 loses none of them
    (a capacity of 1.25 x the even share would keep a fifth of them),
    also when the pairs take several passes of the grouped matmul."""
    from repro.models import ffn
    monkeypatch.setattr(ffn, "HELD_CHUNK", 24)
    cfg, p, x = _moe_inputs(tiny_kimi(), T=96)
    cfg, p = _as_f32(cfg, p)
    bias = jnp.zeros((32,)).at[1].set(100.0)
    p = p._replace(bias=bias)
    got = ffn.held_experts_block(p, x[None], cfg)[0][0]
    m, lw = _reference_layer(tiny_kimi(), bias=bias)
    want = mla_moe._moe(m, lw, x, False)[0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)
    alone = ffn.swiglu(p.shared, x, jnp.float32)
    assert float(jnp.min(jnp.abs(got - alone).max(axis=-1))) > 1e-3


def test_engine_gives_each_request_the_same_tokens_batched_as_alone():
    """The latent cache's slot splice: four requests batched in four
    slots get the tokens each gets alone."""
    from repro.serve.engine import Request, ServingEngine
    _, model, params = program(tiny_kimi(), f32=True)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 256, n).astype(np.int32)
               for n in (9, 23, 17, 30)]

    def serve(ps):
        eng = ServingEngine(model, params, n_slots=4, max_len=64)
        done = eng.run([Request(p, max_new_tokens=12) for p in ps])
        return {len(r.prompt): r.generated for r in done}

    batched = serve(prompts)
    for p in prompts:
        assert serve([p])[len(p)] == batched[len(p)]


def test_the_files_top_level_repeats_its_config():
    """The source's keys stand at the file's top level as its config.json
    gives them, and again under ``config``, which the harness reads: the
    two agree key for key, the cut keys at their cut values."""
    c = KIMI["config"]
    assert {k: KIMI.get(k) for k in c} == c
    assert {k: c[k] for k in KIMI["reduced"]} == {
        "num_hidden_layers": 9, "n_routed_experts": 8, "vocab_size": 20480}


def test_program_settings_are_the_configuration_files():
    """What the width check (integers) does not read: the program's
    settings in floats and names, and its router's width, equal the
    configuration file's published values."""
    from repro.configs import get_config
    cfg = get_config(KIMI["registry_id"])
    c, y = KIMI["config"], KIMI["config"]["rope_scaling"]
    assert cfg.family == "mla_moe" and cfg.n_held_experts == 8
    assert cfg.n_experts == KIMI["published"]["n_routed_experts"] == 384
    assert cfg.expert_lo == KIMI["assumed"]["first_held_expert"]
    assert cfg.router_bias_std == KIMI["assumed"]["router_bias_std"]
    assert (cfg.router, c["scoring_func"], c["topk_method"]) == \
        ("sigmoid", "sigmoid", "noaux_tc")
    assert c["norm_topk_prob"] and c["n_group"] == c["topk_group"] == 1
    assert cfg.router_scale == c["routed_scaling_factor"]
    assert cfg.rope_theta == c["rope_theta"]
    assert cfg.norm_eps == c["rms_norm_eps"]
    assert y["type"] == "yarn"
    assert (cfg.rope_factor, cfg.rope_original_max, cfg.yarn_beta_fast,
            cfg.yarn_beta_slow, cfg.yarn_mscale, cfg.yarn_mscale_all_dim) \
        == (y["factor"], y["original_max_position_embeddings"],
            y["beta_fast"], y["beta_slow"], y["mscale"], y["mscale_all_dim"])
    assert not c["tie_word_embeddings"] and c["hidden_act"] == "silu"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = u.checkout(tmp_path_factory.mktemp("kimi"))
    u.add_cell(root, "tiny-kimi", tiny_kimi())
    return root


@pytest.mark.parametrize("fault", ["", "token", "stale", "half"])
def test_tiny_cell_is_correct_and_each_fault_is_caught(root, fault):
    res = u.run(root, "tiny-kimi.engine", seconds=1.0, fault=fault)
    gap = res["checks"][-1]
    assert gap["name"] == "logit_gap_max"
    assert res["correct"] == (not fault) == gap["ok"], res["checks"]
    assert res["attempted"] > 0


@pytest.mark.parametrize("seed", [12, 2**31 + 7])
def test_control_in_the_tiny_cells_program_place_is_not_correct(root, seed):
    logs = []
    res = u.run(root, "tiny-kimi.engine", seconds=1.0, seed=seed,
                control=True, log=logs.append)
    gap = res["checks"][-1]
    assert not res["correct"] and not gap["ok"]
    read = json.loads(next(x for x in logs if x.startswith("gaps "))[5:])
    assert read["gap_max"] <= gap["limit"] < read["control_gap_max"]
