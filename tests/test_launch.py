"""Launch-layer tests: cell definitions for all 40 (arch x shape) cells,
input_specs contracts, the training driver's converge/checkpoint/resume path,
and the serving driver."""
import jax
import numpy as np
import pytest

from repro.configs import (ASSIGNED, SHAPES, cell_runnable, get_config,
                           input_specs)


def test_forty_cells_enumerate():
    cells = [(a, s) for a in ASSIGNED for s in SHAPES]
    assert len(cells) == 40
    runnable = [c for c in cells
                if cell_runnable(get_config(c[0]), SHAPES[c[1]])[0]]
    skipped = [c for c in cells if c not in runnable]
    assert len(runnable) == 32
    # exactly the 8 full-attention long_500k cells are skipped
    assert all(s == "long_500k" for _, s in skipped)
    assert {a for a, _ in skipped} == set(ASSIGNED) - {"mamba2-1.3b",
                                                       "zamba2-2.7b"}


@pytest.mark.parametrize("arch", ASSIGNED)
@pytest.mark.parametrize("shape", list(SHAPES))
def test_input_specs_shapes(arch, shape):
    cfg = get_config(arch)
    ok, why = cell_runnable(cfg, SHAPES[shape])
    if not ok:
        assert "sub-quadratic" in why
        return
    specs = input_specs(arch, shape)
    cell = SHAPES[shape]
    assert specs["tokens"].dtype == np.int32 or \
        str(specs["tokens"].dtype) == "int32"
    B = cell.global_batch
    assert specs["tokens"].shape[0] == B
    if cell.kind == "train":
        assert "labels" in specs
        if cfg.family == "vlm":
            # patches + text == assigned seq_len
            assert (specs["tokens"].shape[1] + cfg.n_patches
                    == cell.seq_len)
        else:
            assert specs["tokens"].shape[1] == cell.seq_len
    if cell.kind == "decode":
        assert specs["tokens"].shape == (B, 1)
    if cfg.family == "encdec" and cell.kind != "decode":
        assert specs["frames"].shape == (B, cfg.enc_len, cfg.d_model)
    # zero device allocation: everything is a ShapeDtypeStruct
    for v in specs.values():
        assert isinstance(v, jax.ShapeDtypeStruct)


def test_train_driver_converges_and_resumes(tmp_path):
    from repro.launch.train import main as train_main
    cas = str(tmp_path / "cas")
    r1 = train_main(["--reduced", "--steps", "60", "--ckpt-every", "30",
                     "--cas", cas, "--run-name", "t", "--batch", "4",
                     "--seq", "32", "--log-every", "0", "--lr", "5e-3"])
    assert r1["final_loss"] < r1["first_loss"]
    assert r1["manifest"]
    # resume from the checkpoint and keep training
    r2 = train_main(["--reduced", "--steps", "70", "--cas", cas,
                     "--run-name", "t", "--resume", r1["manifest"],
                     "--batch", "4", "--seq", "32", "--log-every", "0",
                     "--ckpt-every", "0", "--lr", "5e-3"])
    assert np.isfinite(r2["final_loss"])


def test_serve_driver(capsys):
    from repro.launch.serve import main as serve_main
    r = serve_main(["--reduced", "--requests", "5", "--max-new", "4",
                    "--slots", "2", "--max-len", "64"])
    assert r["requests"] == 5
    # engine counts decode-step tokens; the first token comes from prefill
    assert r["tokens_generated"] >= 5 * (4 - 1)
    assert len(r["tenants"]) > 1          # multi-tenant interleave


def test_active_params_sane():
    from repro.launch.build import active_params
    # kimi active ~32B/token (MLA, one dense layer, 8 + 1 of 384 experts
    # in 60 MoE layers), total ~1T: active must be FAR below total
    cfg = get_config("kimi-k2-1t-a32b")
    a = active_params(cfg)
    assert 30e9 < a < 35e9
    # dense: active == total order
    smol = active_params(get_config("smollm-135m"))
    assert 1e8 < smol < 3e8
