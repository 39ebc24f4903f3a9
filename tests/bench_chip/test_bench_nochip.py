"""Without a TPU a run exits non-zero and prints no result; so does a run
in a directory that holds the benchmark's files and not the program."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_chip_util as u

SPEC = json.loads((u.REPO / "BENCHMARK.json").read_text())


def run(cwd, cell):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", cell,
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_no_tpu_exits_two_without_a_result(cell):
    p = run(u.REPO, cell)
    assert p.returncode == 2, p.stderr[-2000:]
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(u.REPO / "BENCHMARK.json", tmp_path)
    for rel in SPEC["paths"]:
        shutil.copytree(u.REPO / rel, tmp_path / rel,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for cell in [w["name"] for w in SPEC["workloads"]]:
        p = run(tmp_path, cell)
        assert p.returncode != 0
        assert not any(line.startswith("{")
                       for line in p.stdout.splitlines())
