"""The JAX worker lane on the CPU, at a reduced width: the served path a
``worker_main.py --arch`` process runs on the chip, and what keeps the chip
to one process (the control plane and the smoke's parent load no JAX).

The chip itself is exercised by ``chip_smoke.py``; the compile rehearsal for
it is tests/test_tpu_compile.py.
"""
from __future__ import annotations

import json
import os
import pickle
import shutil
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.core.cas import CAS
from repro.core.cost_model import (DEVICE_CLASSES, DEVICE_KINDS,
                                   device_class_for_kind)
from repro.core.dag import OperatorSpec, OpType
from repro.core.jax_executor import JaxExecutor
from repro.core.transport import LeaseTransport
from repro.core.worker import DispatchBatch, ExecutionGroup, Worker
from repro.fabric import FabricService
from repro.fabric.api import FabricAPI
from repro.fabric.http import FabricHTTPServer, RemoteAPI
from repro.fabric.service import TERMINAL_STATUSES
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT / "scripts"))
import worker_main as wm                                      # noqa: E402

ARCH = "llama-3.2-1b"


def tiny_cfg():
    return get_config(ARCH).reduced(n_layers=2, d_model=64, vocab_size=256,
                                    d_ff=128)


@pytest.fixture(scope="module")
def executor():
    return JaxExecutor(tiny_cfg(), seed=0)


def _batch(op_type: OpType, model_id: str | None) -> DispatchBatch:
    spec = OperatorSpec(name="op", op_type=op_type, model_id=model_id,
                        inputs=[])
    group = ExecutionGroup(h_task="t", h_exec="e", spec=spec,
                           input_hashes=("in-a", "in-b"))
    return DispatchBatch(batch_id=0, h_exec="e", groups=[group],
                         worker_id="w", admitted_at=0.0)


def _shell() -> Worker:
    return Worker("w", DEVICE_CLASSES["tpu-v5e"], now=0.0)


# ---------------------------------------------------------------------------
def test_lane_serves_batch_eval_including_its_aggregate(executor, capsys):
    """A lane leases a batch-eval job's operators over HTTP and runs them
    with JAX; the aggregate op runs without a CAS handle, as it does in a
    worker process."""
    cas = CAS()
    svc = FabricService(seed=7, cas=cas,
                        transport=LeaseTransport(lease_ttl_s=10.0))
    with FabricHTTPServer(FabricAPI(svc), auto_pump=True) as server:
        client = RemoteAPI(server.url, timeout_s=10.0)
        lane = wm.WorkerProcess(server.url, "jl", "tpu-v5e",
                                executor=executor, poll_s=0.2)
        assert lane.register() == 200
        code, job = client.handle("POST", "/workflows", {
            "template": "batch-eval",
            "params": {"tenant": "acme", "model": ARCH}})
        assert code == 201, job
        jid = job["job_id"]
        deadline = time.monotonic() + 120.0
        while client.handle("GET", f"/jobs/{jid}")[1]["status"] \
                not in TERMINAL_STATUSES:
            assert time.monotonic() < deadline, "job never went terminal"
            code, out = client.handle("POST", "/worker/lease", {
                "worker_id": lane.worker_id, "wait_s": 0.2})
            if code == 200 and out.get("lease"):
                lane.run_one(out["lease"])
        assert client.handle("GET", f"/jobs/{jid}")[1]["status"] \
            == "completed"
        rows = client.handle("GET", f"/jobs/{jid}/lineage")[1]["lineage"]

    assert {r["op"] for r in rows} == {"eval_0", "eval_1", "eval_2",
                                       "report"}
    for row in rows:
        out = pickle.loads(cas.get_bytes(row["output_hash"]))
        if row["op"] == "report":
            assert out == {"op": "aggregate",
                           "inputs": list(row["input_hashes"])}
        else:
            assert len(out["tokens"]) == executor.gen_tokens
            assert all(0 <= t < executor.cfg.vocab_size
                       for t in out["tokens"])
    batches = [json.loads(ln[len("batch "):])
               for ln in capsys.readouterr().out.splitlines()
               if ln.startswith("batch {")]
    assert batches and all(b["failure"] is None for b in batches)
    assert all(b["devices"] == [executor.device.id] for b in batches)
    # each line carries what the batch added to the engine's counters
    for b in batches:
        eng = b["engine"]
        assert set(eng) == set(executor.engine.stats())
        if b["op_type"] == "aggregate":
            assert eng["prefills"] == eng["host_reads"] == 0
        else:
            assert eng["prefills"] == b["groups"]
            assert eng["tokens_generated"] + eng["prefills"] \
                == b["groups"] * executor.gen_tokens
            assert eng["host_reads"] >= b["groups"] * executor.gen_tokens


def test_served_batch_reports_no_load_time(executor):
    """The lane's weights are resident from construction, so even a shell
    that is cold for the model loads nothing."""
    shell = _shell()
    spec = _batch(OpType.GENERATE, ARCH).groups[0].spec
    assert not shell.is_hot_for(spec.h_model)
    result = executor.execute(_batch(OpType.GENERATE, ARCH), shell, None)
    assert not result.failed and len(result.outputs) == 1
    assert result.load_s == 0.0


def test_op_for_another_model_fails_visibly(executor):
    result = executor.execute(_batch(OpType.GENERATE, "llama-3.1-8b"),
                              _shell(), None)
    assert result.failed and result.outputs == []
    assert result.failure.startswith("model_mismatch")
    assert "llama-3.1-8b" in result.failure


def test_training_that_does_not_fit_the_device_fails_visibly(
        executor, monkeypatch):
    """A chip reports its memory; full-width AdamW state does not fit."""
    class SmallChip:
        id = 0

        def memory_stats(self):
            return {"bytes_limit": 1 << 20}

    monkeypatch.setattr(executor, "device", SmallChip())
    result = executor.execute(_batch(OpType.SFT, ARCH), _shell(), None)
    assert result.failed
    assert result.failure.startswith("train_state_exceeds_device_memory")


@pytest.mark.parametrize("kind,device_class", sorted(DEVICE_KINDS.items()))
def test_device_kind_maps_to_its_class(kind, device_class):
    assert device_class_for_kind(kind) == device_class


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v5", "NVIDIA H100", ""])
def test_unknown_device_kind_is_refused(kind):
    with pytest.raises(ValueError, match="no device class"):
        device_class_for_kind(kind)


def test_compile_cache_dir_respects_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == tmp_path
    assert jax.config.jax_compilation_cache_dir == before   # JAX's to read


def test_compile_cache_dir_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == ROOT / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == str(path)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def _no_jax(code: str) -> None:
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src")}
    out = subprocess.run(
        [sys.executable, "-c", code + "\nimport sys\n"
         "assert 'jax' not in sys.modules, 'jax was imported'"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr[-2000:]


@pytest.mark.parametrize("module", ["repro.fabric", "repro.core",
                                    "repro.fabric.spec", "repro.core.cas"])
def test_control_plane_loads_no_jax(module):
    _no_jax(f"import {module}")


@pytest.mark.parametrize("script", ["scripts/fabric_cli.py",
                                    "scripts/worker_main.py",
                                    "chip_smoke.py"])
def test_entry_points_load_no_jax_on_import(script):
    _no_jax(f"import runpy; runpy.run_path({str(ROOT / script)!r}, "
            "run_name='not_main')")


def test_chip_smoke_fails_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=env, cwd=ROOT, capture_output=True, text=True,
                         timeout=180)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout
    assert "no TPU" in out.stderr


def test_chip_smoke_fails_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_four_lanes_each_hold_their_own_device():
    """One worker process, one lane thread per device: every lane serves
    model batches with its weights and cache on its own device. The CPU
    devices are advertised as the chip's class so model ops are feasible."""
    code = textwrap.dedent(f"""
        import json, sys, threading, time
        sys.path.insert(0, {str(ROOT / "scripts")!r})
        import worker_main as wm
        from repro.configs import get_config
        from repro.core import cost_model
        from repro.core.cas import CAS
        from repro.core.transport import LeaseTransport
        from repro.fabric import FabricService
        from repro.fabric.api import FabricAPI
        from repro.fabric.http import FabricHTTPServer, RemoteAPI
        from repro.fabric.spec import render_template

        cost_model.DEVICE_KINDS["cpu"] = "tpu-v5e"
        cfg = get_config("{ARCH}").reduced(n_layers=2, d_model=64,
                                           vocab_size=256, d_ff=128)
        svc = FabricService(seed=7, cas=CAS(),
                            transport=LeaseTransport(lease_ttl_s=30.0))
        with FabricHTTPServer(FabricAPI(svc), auto_pump=True) as server:
            api = RemoteAPI(server.url, timeout_s=10.0)
            threading.Thread(target=wm.run_jax_lanes, daemon=True,
                             args=(server.url, "q", cfg),
                             kwargs=dict(poll_s=0.2)).start()
            while len(api.handle("GET", "/admin/transport")[1]["lanes"]) < 4:
                time.sleep(0.1)
            ids = [api.handle("POST", "/workflows", {{"spec": render_template(
                "batch-eval", tenant=t, model="{ARCH}", max_batch=1,
                shards=[f"d{{k}}/shard-{{i}}" for k in range(3)])}})[1]["job_id"]
                for i, t in enumerate(("acme", "globex", "initech"))]
            deadline = time.monotonic() + 240
            while any(api.handle("GET", f"/jobs/{{j}}")[1]["status"]
                      != "completed" for j in ids):
                assert time.monotonic() < deadline
                time.sleep(0.2)
    """)
    env = {**os.environ, "PYTHONPATH": str(ROOT / "src"),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.splitlines()
    devices = {d["lane"]: d for d in (json.loads(ln[len("device "):])
               for ln in lines if ln.startswith("device {"))}
    assert sorted(d["device_id"] for d in devices.values()) == [0, 1, 2, 3]
    model_batches = [b for b in (json.loads(ln[len("batch "):])
                     for ln in lines if ln.startswith("batch {"))
                     if b["model_id"]]
    assert {b["lane"] for b in model_batches} == set(devices)
    for b in model_batches:
        assert b["failure"] is None
        assert b["devices"] == [devices[b["lane"]]["device_id"]]
