"""BENCHMARK.json as the harness reads it: every cell finds its
configuration, traffic mix and readers by name, and a configuration, a mix
and a per-layer metric added as files alone are found and run."""
from __future__ import annotations

import json
import re

import pytest

import bench_chip_util as u
import suite

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


def test_configuration_files_hold_their_published_widths():
    for c in SPEC["configs"]:
        cfg = json.loads((u.REPO / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        assert cfg["source"] == c["source"]
        widths = {"hidden_size", "intermediate_size", "num_attention_heads",
                  "num_key_value_heads", "head_dim"}
        assert not widths & set(c["reduced"])


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
