"""Distribution-layer tests. Sharded execution needs >1 device, so these
spawn subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=8
(the main pytest process keeps its single real device)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str) -> dict:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
def test_fit_spec_divisibility():
    from repro.distributed.sharding import fit_spec
    from repro.launch.mesh import make_test_mesh

    class FakeMesh:
        shape = {"data": 4, "model": 2}

    m = FakeMesh()
    assert fit_spec(m, P("data", "model"), (8, 4)) == P("data", "model")
    assert fit_spec(m, P("data", "model"), (7, 4)) == P(None, "model")
    assert fit_spec(m, P("model", None), (51865, 4)) == P(None, None)
    # multi-axis falls back to a single axis that divides
    assert fit_spec(m, P(("data", "model"), None), (6, 4)) == \
        P(("model",), None)
    assert fit_spec(m, P(("data", "model"),), (4,)) == P(("data",))


def test_sharded_train_step_runs():
    """2x(4 data, 2 model) mesh: a real sharded train step executes and the
    loss matches the single-device step bit-for-bit (GSPMD correctness)."""
    res = run_sub(textwrap.dedent("""
        import json, jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.distributed import sharding as shd
        from repro.distributed import logical
        from repro.models.transformer import build_model
        from repro.train.optimizer import OptimizerConfig, build_optimizer
        from repro.train.train_step import build_train_step, init_train_state
        from repro.train.data import DataConfig, SyntheticLM

        cfg = get_config("smollm-135m").reduced(
            n_layers=2, d_model=64, vocab_size=256, d_ff=128,
            n_heads=4, n_kv_heads=2, head_dim=16)
        model = build_model(cfg)
        opt = build_optimizer(OptimizerConfig(peak_lr=1e-3))
        data = SyntheticLM(DataConfig(256, 32, 8))

        # single-device reference
        state = init_train_state(model, opt, jax.random.key(0))
        step = jax.jit(build_train_step(model, opt))
        sref = jax.tree.map(jnp.copy, state)
        for i in range(3):
            sref, mref = step(sref, data.batch(i))

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        logical.install(mesh)
        pspecs = shd.fit_tree(mesh, shd.param_specs(cfg, mesh),
                              jax.eval_shape(model.init, jax.random.key(0)))
        sspecs = {"params": pspecs,
                  "opt": shd.opt_state_specs("adamw", pspecs, state["params"])}
        sh = shd.to_named(mesh, sspecs)
        state = jax.device_put(state, sh)
        with mesh:
            jstep = jax.jit(build_train_step(model, opt), in_shardings=(sh,
                jax.tree.map(lambda _: None, {"tokens":0,"labels":0,
                                              "loss_mask":0})),
                donate_argnums=())
            for i in range(3):
                state, m = jstep(state, data.batch(i))
        print(json.dumps({"loss_sharded": float(m["loss"]),
                          "loss_ref": float(mref["loss"])}))
    """))
    np.testing.assert_allclose(res["loss_sharded"], res["loss_ref"],
                               rtol=1e-5)


def test_embed_lookup_under_explicit_axes():
    """The one embedding gather of every family, on a vocab-sharded table
    typed by ``jax.make_mesh``'s Explicit axes, equals the plain gather and
    is laid out like its ids."""
    res = run_sub(textwrap.dedent("""
        import json, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.common import embed_lookup

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        table = jax.random.normal(jax.random.key(0), (256, 64))
        ids = jax.random.randint(jax.random.key(1), (8, 32), 0, 256)
        t = jax.device_put(table, NamedSharding(mesh, P("model", "data")))
        i = jax.device_put(ids, NamedSharding(mesh, P("data", None)))
        out = jax.jit(lambda t, i: embed_lookup(t, i, jnp.float32))(t, i)
        want = np.asarray(table)[np.asarray(ids)]
        print(json.dumps({"err": float(np.max(np.abs(np.asarray(out) - want))),
                          "spec": [str(a) for a in out.sharding.spec]}))
    """))
    assert res["err"] == 0.0
    assert res["spec"] == ["data", "None", "None"]


def test_moe_ep_matches_gspmd():
    """a2a expert parallelism == grouped GSPMD dispatch, same tokens."""
    res = run_sub(textwrap.dedent("""
        import json, jax, jax.numpy as jnp
        from dataclasses import replace
        from repro.configs import get_config
        from repro.distributed import logical
        from repro.models.ffn import init_moe, moe_block

        cfg = get_config("qwen2-moe-a2.7b").reduced(
            d_model=32, d_ff=16, n_experts=8, top_k=2, n_shared_experts=0,
            capacity_factor=8.0)   # high capacity => no drops => comparable
        mesh = jax.make_mesh((4, 2), ("data", "model"))

        cfg_ep = replace(cfg, moe_impl="ep", moe_pad_experts=8, moe_groups=1)
        cfg_g  = replace(cfg, moe_impl="gspmd", moe_groups=8)
        p = init_moe(jax.random.key(0), cfg_ep)   # E_pad == E == 8
        x = jax.random.normal(jax.random.key(1), (8, 4, 32), jnp.float32)

        # reference: no mesh -> grouped gspmd single-device
        logical.clear()
        ref, aux_ref = moe_block(p, x, cfg_g)

        logical.install(mesh)
        with mesh:
            out, aux = jax.jit(
                lambda p, x: moe_block(p, x, cfg_ep))(p, x)
        err = float(jnp.max(jnp.abs(out - ref)))
        print(json.dumps({"err": err, "aux": float(aux),
                          "aux_ref": float(aux_ref)}))
    """))
    assert res["err"] < 2e-5, f"EP diverged from dense dispatch: {res}"
    np.testing.assert_allclose(res["aux"], res["aux_ref"], rtol=1e-4)


def test_pipeline_matches_sequential():
    """GPipe over 4 stages == sequential layer stack."""
    res = run_sub(textwrap.dedent("""
        import json, jax, jax.numpy as jnp
        from repro.distributed.pipeline import pipeline, split_stages

        mesh = jax.make_mesh((4,), ("stage",))
        L, D = 8, 16

        def layer(w, x):
            return jnp.tanh(x @ w)

        ws = jax.random.normal(jax.random.key(0), (L, D, D)) * 0.5

        def stage_fn(stage_w, x):     # stage_w: (L/S, D, D)
            def body(x, w):
                return layer(w, x), None
            x, _ = jax.lax.scan(body, x, stage_w)
            return x

        x = jax.random.normal(jax.random.key(1), (6, 4, D))  # 6 microbatches
        want = x
        for i in range(L):
            want = layer(ws[i], want)

        run = pipeline(stage_fn, mesh, n_microbatches=6)
        got = jax.jit(run)(split_stages(ws, 4), x)
        err = float(jnp.max(jnp.abs(got - want)))
        print(json.dumps({"err": err}))
    """))
    assert res["err"] < 1e-5


def test_grad_compression_error_feedback():
    """int8 EF compression: biased per step, unbiased over time (residual
    carries the error), and compressed tensors round-trip within int8 step."""
    from repro.distributed.compression import (dequantize_int8,
                                               make_error_feedback_compressor,
                                               quantize_int8)
    x = jax.random.normal(jax.random.key(0), (256,)) * 3.0
    q, s = quantize_int8(x)
    assert q.dtype == jnp.int8
    np.testing.assert_allclose(np.asarray(dequantize_int8(q, s)),
                               np.asarray(x), atol=float(s) * 0.51)
    comp = make_error_feedback_compressor()
    g = {"w": jnp.ones((64,)) * 0.3333}
    total = jnp.zeros((64,))
    resid = None
    for _ in range(50):
        cg, resid = comp(g, resid)
        total = total + cg["w"]
    # over 50 steps the accumulated compressed signal ~= accumulated true
    np.testing.assert_allclose(np.asarray(total) / 50.0, 0.3333, rtol=1e-3)


def test_production_mesh_shapes():
    from repro.launch.mesh import make_production_mesh
    # shape arithmetic only — actual construction needs 512 devices, which
    # the dry-run subprocess provides; here verify the contract
    import inspect
    src = inspect.getsource(make_production_mesh)
    assert "(2, 16, 16)" in src and "(16, 16)" in src
    assert '"pod", "data", "model"' in src
