"""A configuration names its own reference module, and that module names
the widths the program is checked on, so an architecture that is not dense
is added as files alone: a configuration, a reference module, a mix, and
BENCHMARK.json entries. Names or widths that do not hold are refused with
a message before the window. Tiny widths on the CPU, the chip check
skipped."""
from __future__ import annotations

import hashlib
import json
from types import SimpleNamespace

import pytest

import bench_chip_util as u
from program import checked_widths, program_config
from reference import dense
import suite
from suite import RunError

#: a second architecture's reference, as a later change would add one: its
#: configuration holds its widths in keys of its own, which this module
#: checks and maps onto the dense reference's; each call is recorded beside
#: it
TINY_ALT_SRC = '''"""Test architecture: its own width keys, the dense forward."""
from pathlib import Path

from reference import dense

KEYS = {"layers": "num_hidden_layers", "width": "hidden_size",
        "q_heads": "num_attention_heads", "kv_groups": "num_key_value_heads",
        "head_width": "head_dim", "mlp_width": "intermediate_size",
        "tokens": "vocab_size", "eps": "rms_norm_eps", "theta": "rope_theta"}
WIDTHS = {k: dense.WIDTHS[v] for k, v in KEYS.items() if v in dense.WIDTHS}


def published(c):
    return c


def gaps(config, seed, items, *, control=False):
    with open(Path(__file__).with_name("tiny_alt.calls"), "a") as f:
        f.write(f"{seed} {len(items)} {int(control)}\\n")
    c = config["config"]
    dense_config = {**config,
                    "config": {KEYS[k]: v for k, v in c.items()}}
    return dense.gaps(dense_config, seed, items, control=control)
'''

TINY_ALT = {
    **{k: v for k, v in u.TINY.items() if k != "config"},
    "reference": "tiny_alt",
    "config": {"layers": 2, "width": 64, "q_heads": 4, "kv_groups": 2,
               "head_width": 16, "mlp_width": 128, "tokens": 256,
               "eps": 1e-05, "theta": 500000.0},
}


def _digests(bench):
    return {p.relative_to(bench): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(bench.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_second_architecture_added_as_files_alone(tmp_path):
    root = u.checkout(tmp_path)
    bench = root / "benchmarks" / "chip"
    before = _digests(bench)
    cell = u.add_cell(root, "tiny-alt", TINY_ALT,
                      references={"tiny_alt": TINY_ALT_SRC})
    seed = 2**31 + 4242
    res = u.run(root, cell, seconds=1.0, seed=seed)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0
    calls = (bench / "reference" / "tiny_alt.calls").read_text().split()
    assert calls == [str(seed), "4", "0"]
    after = _digests(bench)
    assert {k: after[k] for k in before} == before
    # the keys of its own are the ones checked, none derived
    ref = suite.reference(suite.load_cell(cell, root, bench))
    assert set(checked_widths(TINY_ALT, ref)) == set(TINY_ALT["config"]) \
        - {"eps", "theta"}


@pytest.fixture
def no_setup(monkeypatch):
    """Set-up (JAX's device init) raises: an error that comes before it is
    the one seen."""
    import jax

    def devices(*_a, **_k):
        raise AssertionError("set-up began")
    monkeypatch.setattr(jax, "devices", devices)


def test_unknown_reference_fails_before_set_up(tmp_path, no_setup):
    root = u.checkout(tmp_path)
    cell = u.add_cell(root, "tiny-nope", {**u.TINY, "reference": "nope"})
    with pytest.raises(RunError, match=r"tiny-nope\.json.*'nope'.*nope\.py"):
        u.run(root, cell, seconds=1.0)


def test_configuration_without_a_reference_fails_before_set_up(
        tmp_path, no_setup):
    root = u.checkout(tmp_path)
    config = {k: v for k, v in u.TINY.items() if k != "reference"}
    cell = u.add_cell(root, "tiny-unnamed", config)
    with pytest.raises(RunError, match=r"tiny-unnamed\.json names no ref"):
        u.run(root, cell, seconds=1.0)


def _tiny(*, drop=(), **overrides):
    config = json.loads(json.dumps(u.TINY))
    for k in drop:
        del config["config"][k]
    config["program_overrides"].update(overrides)
    return config


def _reference(widths):
    """A reference module that checks ``widths`` and derives nothing."""
    return SimpleNamespace(WIDTHS=widths, published=lambda c: c)


def test_widths_key_missing_from_config_is_refused():
    ref = _reference({"hidden_size": "d_model",
                      "moe_intermediate_size": "d_ff"})
    with pytest.raises(RunError, match="no 'moe_intermediate_size'"):
        program_config(_tiny(), ref)


def test_widths_mismatch_names_the_field():
    with pytest.raises(RunError, match=r"runs d_ff=256.*"
                                       r"intermediate_size=128"):
        program_config(_tiny(d_ff=256), dense)


def test_widths_map_checks_exactly_the_keys_it_lists():
    """A field the reference's map leaves out is not checked; the same
    program fails the dense map, which lists it."""
    ref = _reference({"hidden_size": "d_model"})
    assert set(checked_widths(_tiny(), ref)) == {"hidden_size"}
    cfg = program_config(_tiny(d_ff=256), ref)
    assert cfg.d_ff == 256 and cfg.d_model == 64
    with pytest.raises(RunError, match="d_ff"):
        program_config(_tiny(d_ff=256), dense)


def test_widths_map_derives_no_head_width():
    """Only the reference's ``published`` derives: one that derives
    nothing refuses a configuration without ``head_dim``."""
    ref = _reference({"head_dim": "hd"})
    with pytest.raises(RunError, match="no 'head_dim'"):
        program_config(_tiny(drop=("head_dim",)), ref)


def test_default_widths_derive_the_head_width_as_before():
    config = _tiny(drop=("head_dim",))
    assert checked_widths(config, dense)["head_dim"] == ("hd", 16)
    assert program_config(config, dense).hd == 16
    with pytest.raises(RunError, match=r"runs hd=32.*head_dim=16"):
        program_config(_tiny(drop=("head_dim",), head_dim=32), dense)


@pytest.mark.parametrize("key", [k for k in dense.WIDTHS if k != "head_dim"])
def test_default_widths_refuse_a_missing_key_with_a_message(key):
    with pytest.raises(RunError, match=f"no '{key}'"):
        program_config(_tiny(drop=(key,)), dense)


def test_a_width_that_is_not_a_whole_number_is_refused():
    with pytest.raises(RunError, match="not a whole number"):
        program_config(_tiny(), _reference({"rope_theta": "rope_theta"}))


def test_a_cut_depth_is_checked_at_its_cut_value():
    """A configuration cut in depth lists ``num_hidden_layers`` in
    ``reduced`` and is still checked on it: the program runs the cut
    depth or is refused."""
    config = _tiny(n_layers=1)
    config["config"]["num_hidden_layers"] = 1
    config["reduced"] = ["num_hidden_layers"]
    assert checked_widths(config, dense)["num_hidden_layers"] == \
        ("n_layers", 1)
    assert program_config(config, dense).n_layers == 1
    config["program_overrides"]["n_layers"] = 2
    with pytest.raises(RunError, match=r"runs n_layers=2.*"
                                       r"num_hidden_layers=1"):
        program_config(config, dense)


@pytest.mark.parametrize("metric", ["engine_mfu",
                                    "decode_attention_roofline"])
def test_dense_readers_count_from_the_cells_configuration(metric):
    """The dense readers count from the run's own configuration: a share
    for a dense one, and a loud failure, not a silent gap, where a metric
    is listed for a cell whose configuration has no dense keys."""
    step = {"prompts": [16], "lengths": [16, 0, 0, 0]}
    rec = {"kind": "engine", "config": u.TINY,
           "peak": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
           "steps": [step], "window_s": 1.0, "traced_steps": [0, 1],
           "trace": {"kernels": {"decode_attention": {"calls": 2,
                                                      "s": 1e-3}}}}
    assert suite.reader(metric)(rec) > 0
    with pytest.raises(KeyError):
        suite.reader(metric)({**rec, "config": TINY_ALT})
