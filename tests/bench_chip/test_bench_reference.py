"""The float32 reference: it makes the program's weights again from the
seed, bit for bit, computes the program's function, and its fp8 control
reads wider gaps than the bfloat16 program on the same prompts and served
tokens, so that a run with the control in the program's place is not
correct (the control test, at a width a test run holds)."""
from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_chip_util as u
from program import program_config, use_checkout
from reference import dense
from reference.dense import Weights, gaps, logits_at

use_checkout(u.REPO)
SEED = 2**31 + 77


def program(cfg_file: dict, f32: bool = False):
    from repro.models.transformer import build_model
    cfg = program_config(cfg_file, dense)
    if f32:
        cfg = dataclasses.replace(cfg, param_dtype=jnp.float32,
                                  compute_dtype=jnp.float32)
    model = build_model(cfg)
    return model, jax.jit(model.init)(jax.random.key(SEED))


def test_weights_are_the_programs_bit_for_bit():
    _, params = program(u.TINY)
    w = Weights(u.TINY, SEED)
    np.testing.assert_array_equal(
        w.embed(), params["embed"].astype(jnp.float32))
    np.testing.assert_array_equal(
        w.head(), params["lm_head"].astype(jnp.float32))
    for i in range(u.TINY["config"]["num_hidden_layers"]):
        lw = w.layer(i)
        lp = jax.tree.map(lambda x: x[i], params["layers"])
        for ours, theirs in ((lw["wq"], lp.attn.wq), (lw["wo"], lp.attn.wo),
                             (lw["w_gate"], lp.mlp.w_gate),
                             (lw["w_down"], lp.mlp.w_down)):
            np.testing.assert_array_equal(ours, theirs.astype(jnp.float32))


def test_reference_computes_the_programs_function():
    """At float32 both ways (the program's weights rounded to bfloat16
    as served), the program's prefill and the reference agree on the last
    position's logits to rounding."""
    model, params = program(u.TINY, f32=True)
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16).astype(jnp.float32), params)
    toks = np.random.default_rng(0).integers(0, 256, (1, 40), np.int32)
    cache = model.init_cache(1, 64)
    last, _ = model.prefill(params, {"tokens": jnp.asarray(toks)}, cache)
    ref = logits_at(u.TINY, SEED, [toks[0]])[0][-1]
    np.testing.assert_allclose(np.asarray(last[0, -1]), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


CONTROL_LIMIT = u.TINY["check"]["logit_gap_limit"]


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_fp8_control_fails_where_the_program_passes(seed):
    from repro.serve.engine import Request, ServingEngine
    model, params = program(u.TINY)
    eng = ServingEngine(model, params, n_slots=4, max_len=256)
    rng = np.random.default_rng(seed)
    reqs = [Request(rng.integers(0, 256, n).astype(np.int32),
                    max_new_tokens=48) for n in (16, 40, 64, 96)]
    done = eng.run(reqs)
    items = [{"prompt": r.prompt.tolist(), "served": r.generated}
             for r in done]
    out = gaps(u.TINY, SEED, items, control=True)
    assert out["served_tokens"] == 4 * 48
    assert out["gap_max"] <= CONTROL_LIMIT < out["control_gap_max"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return u.checkout(tmp_path_factory.mktemp("control"))


@pytest.mark.parametrize("seed", [11, 12, 2**31 + 5])
def test_control_in_the_programs_place_is_not_correct(root, seed):
    """The harness's own check, with the fp8 control in the program's
    place, fails; the same run's program passes it."""
    logs = []
    res = u.run(root, "tiny.engine", seconds=1.0, seed=seed, control=True,
                log=logs.append)
    gap = res["checks"][-1]
    assert gap["name"] == "logit_gap_max" and not gap["ok"]
    assert not res["correct"]
    line = next(x for x in logs if x.startswith("gaps "))
    read = json.loads(line[len("gaps "):])
    assert read["gap_max"] <= gap["limit"] < gap["value"]
    assert gap["value"] == read["control_gap_max"]
