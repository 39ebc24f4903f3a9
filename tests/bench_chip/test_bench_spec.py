"""BENCHMARK.json as the harness reads it: every cell finds its
configuration, traffic mix and readers by name, and a configuration, a mix
and a per-layer metric added as files alone are found and run."""
from __future__ import annotations

import json
import re
from types import SimpleNamespace

import pytest

import bench_chip_util as u
import suite
from program import checked_widths

SPEC = json.loads((u.REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_keys_and_names_keep_to_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"] for m in SPEC["end_to_end"]} >= {"setup_s"}
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_finds_its_files(cell):
    c = suite.load_cell(cell, u.REPO)
    assert c.traffic["driver"] == "engine"
    assert (u.BENCH / "drivers" / f"{c.traffic['driver']}.py").is_file()
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(suite.reader(m["name"]))
        moved = [e for e in c.end_to_end if e["name"] == m["moves"]]
        assert moved, f"{m['name']} moves a metric {cell} does not report"


#: a width by the contract's rule, never cut whatever the architecture:
#: hidden, intermediate, latent, state and projection sizes, head sizes
#: and counts, keys ending in _dim or _rank, expansion factors, experts per
#: token. Depth, expert count and vocabulary are scale, and may be cut.
WIDTH = re.compile(r"(^|_)(dim|rank|hidden_size|intermediate_size|"
                   r"latent_size|state_size|proj\w*_size|head_size|heads|"
                   r"expand|expansion_factor|experts_per_tok)$|^d_")


def holds_published_widths(entry: dict, cfg: dict) -> None:
    """A configuration file as BENCHMARK.json lists it: the same cuts and
    source, no width cut, a reference module that is there, and every key
    that module checks present (a cut key at its cut value)."""
    assert cfg["reduced"] == entry["reduced"]
    assert cfg["source"] == entry["source"]
    assert not [k for k in entry["reduced"] if WIDTH.search(k)]
    assert (u.BENCH / "reference" / f"{cfg['reference']}.py").is_file()
    ref = suite.reference(SimpleNamespace(
        config=cfg, config_file=entry["file"], bench_dir=u.BENCH))
    assert set(checked_widths(cfg, ref)) == set(ref.WIDTHS)


def test_configuration_files_hold_their_published_widths():
    for c in SPEC["configs"]:
        holds_published_widths(c, json.loads((u.REPO / c["file"]).read_text()))


def test_a_depth_cut_configuration_holds_its_published_widths():
    """Cut to fewer layers, a configuration names ``num_hidden_layers`` in
    ``reduced`` and is still checked on it."""
    cfg = json.loads(json.dumps(u.TINY))
    cfg["config"]["num_hidden_layers"] = 1
    cfg["reduced"] = ["num_hidden_layers"]
    cfg["source"] = "https://example.org/tiny-cut"
    entry = {"file": "tiny-cut.json", "source": cfg["source"],
             "reduced": cfg["reduced"]}
    holds_published_widths(entry, cfg)
    with pytest.raises(AssertionError):
        holds_published_widths({**entry, "reduced": ["hidden_size"]},
                               {**cfg, "reduced": ["hidden_size"]})


@pytest.mark.parametrize("key,width", [
    # the next configuration's widths (Kimi-K2-Instruct) ...
    ("hidden_size", True), ("intermediate_size", True),
    ("moe_intermediate_size", True), ("num_attention_heads", True),
    ("num_key_value_heads", True), ("head_dim", True),
    ("q_lora_rank", True), ("kv_lora_rank", True),
    ("qk_nope_head_dim", True), ("qk_rope_head_dim", True),
    ("v_head_dim", True), ("num_experts_per_tok", True),
    ("ssm_state_size", True), ("expand", True), ("d_state", True),
    # ... and the scale it is cut in
    ("num_hidden_layers", False), ("n_routed_experts", False),
    ("vocab_size", False), ("max_position_embeddings", False),
])
def test_widths_are_told_from_scale_by_name(key, width):
    assert bool(WIDTH.search(key)) == width


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_refused():
    assert suite.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        suite.peaks("cpu")


READER = '''"""Steps the engine window held (a test's dummy metric)."""


def read(rec):
    return float(len(rec["steps"])) if rec.get("kind") == "engine" else None
'''


def test_a_configuration_mix_and_metric_added_as_files_alone(tmp_path):
    root = u.checkout(tmp_path, metric_src=READER)
    cell = suite.load_cell("tiny.engine", root,
                           root / "benchmarks" / "chip")
    assert cell.config["registry_id"] == "llama-3.2-1b"
    assert cell.traffic["max_len"] == 256
    res = u.run(root, "tiny.engine", trace=True, seconds=1.0)
    assert res["correct"], res["checks"]
    # the new reader found something; the device share needs a chip
    assert res["metrics"]["steps_seen"]["value"] > 0
    assert res["metrics"]["decode_step_ms"]["value"] > 0
    assert "device_idle_share.tiny" not in res["metrics"]
