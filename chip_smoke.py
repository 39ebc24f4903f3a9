#!/usr/bin/env python
"""chip_smoke: serve llama-3.2-1b through the fabric on a TPU, once.

The quickest proof that the system still starts on the chip. It drives the
normal served path: ``fabric_cli.py serve --remote-workers`` leases batches
to one ``worker_main.py --arch llama-3.2-1b`` process, whose JAX lanes (one
per chip) run the model at its published widths with random weights made
from ``--seed``. Jobs go in through ``RemoteAPI``, as a client's would.

    python chip_smoke.py               # one chip: the default
    python chip_smoke.py --chips 4     # four lanes behind one fabric, then
                                       # the same prompts on one chip

One chip: a batch-eval job and an agent-loop job for two tenants, then a
byte-identical resubmission. Every job must complete, every generate/eval
output must hold the expected number of token ids, each below the
vocabulary size, and the resubmission must be served by dedup without
executing anything.

Four chips (``--chips 4``, this phase only): three batch-eval jobs with one
operator per batch, so each of the four lanes is leased work. Every device
must serve at least one batch, and every operator's greedy tokens must equal,
byte for byte, those of a single-chip run of the same prompts through one
``ServingEngine`` on device 0, in a process started after the worker exited.

This process never imports JAX: the chips belong to the worker (and, later,
the reference run). Lines before the last are smoke output, not
measurements. The last line is one JSON object with the device as JAX
reported it; any failure exits non-zero without it.
"""
from __future__ import annotations

import argparse
import json
import os
import pickle
import queue
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "llama-3.2-1b"
#: llama-3.2-1b's published widths (configs/llama3_2_1b.py)
PUBLISHED = {"n_layers": 16, "d_model": 2048, "n_heads": 32,
             "n_kv_heads": 8, "d_ff": 8192, "vocab_size": 128256,
             "head_dim": 64}
GEN_TOKENS = 8          # JaxExecutor's gen_tokens default
MODEL_OPS = ("generate", "eval", "score")
DEADLINE_S = 1000.0     # everything, compiles included, inside 1200 s


class SmokeError(Exception):
    """A phase failed; the message says which and why."""


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class Child:
    """A child process whose merged output is read by a thread, so the
    parent can wait on a line without blocking on the pipe."""

    def __init__(self, name: str, argv: list[str], env: dict) -> None:
        self.name = name
        self.lines: list[str] = []
        self.q: queue.Queue[str | None] = queue.Queue()
        self.proc = subprocess.Popen(
            argv, cwd=ROOT, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True, bufsize=1)
        threading.Thread(target=self._read, daemon=True).start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            line = line.rstrip("\n")
            self.lines.append(line)
            self.q.put(line)
        self.q.put(None)

    def tagged(self, tag: str) -> list[dict]:
        """Every ``<tag> {json}`` line so far."""
        return [json.loads(ln[len(tag) + 1:]) for ln in list(self.lines)
                if ln.startswith(tag + " {")]

    def wait_line(self, pred, deadline: float, what: str) -> str:
        while True:
            left = deadline - time.monotonic()
            if left <= 0:
                raise SmokeError(f"{self.name}: timed out waiting for {what}")
            try:
                line = self.q.get(timeout=min(left, 1.0))
            except queue.Empty:
                continue
            if line is None:
                raise SmokeError(f"{self.name} exited (rc "
                                 f"{self.proc.wait()}) before {what}")
            if pred(line):
                return line

    def stop(self, timeout: float = 20.0) -> int:
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout)
            except subprocess.TimeoutExpired:
                self.proc.kill()
        return self.proc.wait()

    def tail(self, n: int = 40) -> str:
        return "\n".join(self.lines[-n:])


def _env() -> dict:
    src = str(ROOT / "src")
    pp = os.environ.get("PYTHONPATH")
    return {**os.environ, "PYTHONPATH": src + (os.pathsep + pp if pp else "")}


def _wait_jobs(api, job_ids: list[str], worker: Child,
               deadline: float) -> dict[str, dict]:
    from repro.fabric.service import TERMINAL_STATUSES
    jobs: dict[str, dict] = {}
    while len(jobs) < len(job_ids):
        if time.monotonic() > deadline:
            pending = [j for j in job_ids if j not in jobs]
            raise SmokeError(f"jobs not terminal by the deadline: {pending}")
        if worker.proc.poll() is not None:
            raise SmokeError(f"worker exited (rc {worker.proc.returncode}) "
                             "with jobs pending")
        for jid in job_ids:
            if jid in jobs:
                continue
            code, job = api.handle("GET", f"/jobs/{jid}")
            if code == 200 and job["status"] in TERMINAL_STATUSES:
                if job["status"] != "completed":
                    raise SmokeError(f"job {jid} ended {job['status']}")
                jobs[jid] = job
        time.sleep(0.5)
    return jobs


def _submit(api, doc: dict) -> str:
    code, job = api.handle("POST", "/workflows", {"spec": doc})
    if code != 201:
        raise SmokeError(f"submit {doc['name']} for {doc['tenant']}: "
                         f"HTTP {code} {job}")
    return job["job_id"]


def _model_outputs(api, cas, job_id: str, doc: dict) -> list[dict]:
    """The job's lineage rows for model operators, each with the tokens its
    output artifact holds, checked for count and range."""
    op_types = {op["name"]: op["op_type"] for op in doc["ops"]}
    code, lin = api.handle("GET", f"/jobs/{job_id}/lineage")
    if code != 200:
        raise SmokeError(f"lineage {job_id}: HTTP {code} {lin}")
    rows = []
    for row in lin["lineage"]:
        if op_types[row["op"]] not in MODEL_OPS:
            continue
        # an artifact this fabric's own worker wrote into its store
        out = pickle.loads(cas.get_bytes(row["output_hash"]))
        tokens = out["tokens"]
        if len(tokens) != GEN_TOKENS or not all(
                isinstance(t, int) and 0 <= t < PUBLISHED["vocab_size"]
                for t in tokens):
            raise SmokeError(f"{job_id}/{row['op']}: bad tokens {tokens}")
        rows.append({**row, "tokens": tokens})
    return rows


def _docs(chips: int) -> list[dict]:
    from repro.fabric.spec import render_template
    if chips == 1:
        return [render_template("batch-eval", tenant="acme", model=ARCH),
                render_template("agent-loop", tenant="globex", model=ARCH)]
    # one operator per batch and 9 of them ready at once: with at most two
    # slices queued per lane, every one of the four lanes is leased work
    return [render_template(
        "batch-eval", tenant=t, model=ARCH, max_batch=1,
        shards=[f"{ds}/shard-{i}" for ds in ("gsm8k", "mmlu", "truthfulqa")])
        for i, t in enumerate(("acme", "globex", "initech"))]


def run(chips: int, seed: int) -> dict:
    for rel in ("scripts/fabric_cli.py", "scripts/worker_main.py",
                "src/repro"):
        if not (ROOT / rel).exists():
            raise SmokeError(f"{rel} not found beside chip_smoke.py: run it "
                             "from a checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.core.cas import DiskCAS
    from repro.fabric.http import RemoteAPI

    deadline = time.monotonic() + DEADLINE_S
    env = _env()
    store = tempfile.mkdtemp(prefix="chip-smoke-")
    children: list[Child] = []
    try:
        server = Child("server", [
            sys.executable, "scripts/fabric_cli.py", "serve",
            "--remote-workers", "--lease-ttl", "30", "--journal", store],
            env)
        children.append(server)
        line = server.wait_line(lambda ln: "listening on" in ln,
                                time.monotonic() + 60, "the fabric URL")
        url = line.split("listening on", 1)[1].strip()
        log(f"fabric serving at {url}")

        t_start = time.monotonic()
        worker = Child("worker", [
            sys.executable, "scripts/worker_main.py", "--url", url,
            "--arch", ARCH, "--seed", str(seed), "--worker-id", "smoke",
            "--poll-s", "2"], env)
        children.append(worker)
        worker.wait_line(lambda ln: ln.startswith("device {"), deadline,
                         "the lane's device line")
        first = worker.tagged("device")[0]
        if first["platform"] != "tpu":
            raise SmokeError(f"JAX found no TPU (platform "
                             f"{first['platform']!r}, kind {first['kind']!r})")
        count = first["count"]
        if count < chips:
            raise SmokeError(f"--chips {chips} but JAX sees {count} devices")
        while len(worker.tagged("ready")) < count:
            worker.wait_line(lambda ln: ln.startswith("ready {"), deadline,
                             "every lane's weights")
        devices = worker.tagged("device")
        for d in devices:
            log(f"lane {d['lane']}: {d['platform']} {d['kind']} device "
                f"{d['device_id']}, kernel backend {d['kernel_backend']}")
            if d["kernel_backend"] != "pallas":
                raise SmokeError(f"lane {d['lane']} fell back to the "
                                 f"{d['kernel_backend']} kernels")
            widths = {k: d[k] for k in PUBLISHED}
            if d["arch"] != ARCH or widths != PUBLISHED:
                raise SmokeError(f"lane {d['lane']} runs {d['arch']} "
                                 f"{widths}, not the published {PUBLISHED}")
        log(f"{ARCH} at published widths {PUBLISHED}")
        for r in worker.tagged("ready"):
            log(f"lane {r['lane']}: {r['params']} params on a "
                f"{r['device_class']} lane, set-up {r['setup_s']:.1f} s")

        api = RemoteAPI(url, timeout_s=30.0)
        while len(api.handle("GET", "/admin/transport")[1]["lanes"]) < count:
            if time.monotonic() > deadline:
                raise SmokeError("lanes never registered with the fabric")
            time.sleep(0.2)

        cas = DiskCAS(store)
        docs = _docs(chips)
        ids = [_submit(api, doc) for doc in docs]
        _wait_jobs(api, ids, worker, deadline)
        log(f"{len(ids)} jobs completed, {time.monotonic() - t_start:.1f} s "
            "after the worker started (compiles included)")
        rows = [r for jid, doc in zip(ids, docs)
                for r in _model_outputs(api, cas, jid, doc)]
        executed = [r for r in rows if r["executed"]]
        log(f"{len(rows)} model outputs hold {GEN_TOKENS} token ids each, "
            f"all below {PUBLISHED['vocab_size']}")

        if chips == 1:
            served = len(worker.tagged("batch"))
            rid = _submit(api, docs[0])
            _wait_jobs(api, [rid], worker, deadline)
            code, lin = api.handle("GET", f"/jobs/{rid}/lineage")
            if code != 200 or any(r["executed"] for r in lin["lineage"]):
                raise SmokeError(f"resubmission {rid} executed work: {lin}")
            time.sleep(1.0)
            if len(worker.tagged("batch")) != served:
                raise SmokeError("the worker ran batches for the "
                                 "resubmission")
            log(f"resubmission {rid}: all {len(lin['lineage'])} ops served "
                "by dedup, no batch executed")

        batches = worker.tagged("batch")
        lane_device = {d["lane"]: d["device_id"] for d in devices}
        per_lane: dict[str, int] = {}
        for b in batches:
            lane = b["lane"].split("~")[0]
            if b["failure"]:
                raise SmokeError(f"batch failed: {b}")
            if b["model_id"] and b["devices"] != [lane_device[lane]]:
                raise SmokeError(f"lane {lane} keeps its state on devices "
                                 f"{b['devices']}, not "
                                 f"{lane_device[lane]}")
            per_lane[lane] = per_lane.get(lane, 0) + 1
            log(f"batch {b['batch_id']} on {lane}: {b['op']} "
                f"({b['op_type']}, {b['groups']} group(s)"
                f"{', speculative' if b['speculative'] else ''}) "
                f"wall {b['wall_s']:.3f} s")
        log(f"batches per lane: {per_lane}")

        if chips > 1:
            model_lanes = {b["lane"].split("~")[0] for b in batches
                           if b["model_id"]}
            idle = sorted(set(lane_device) - model_lanes)
            if idle:
                raise SmokeError(f"lanes served no model batch: {idle}")
            if worker.stop() not in (0, -signal.SIGTERM):
                raise SmokeError("worker did not exit cleanly")
            _compare_single_chip(executed, seed, env, deadline)
        return {"platform": first["platform"], "kind": first["kind"],
                "count": count}
    except SmokeError:
        for c in children:
            print(f"--- {c.name} output (tail) ---\n{c.tail()}",
                  file=sys.stderr)
        raise
    finally:
        for c in reversed(children):
            c.stop()
        shutil.rmtree(store, ignore_errors=True)


def _compare_single_chip(executed: list[dict], seed: int, env: dict,
                         deadline: float) -> None:
    """Run every executed operator's prompt on one chip in a fresh process
    and require the same greedy tokens."""
    with tempfile.TemporaryDirectory(prefix="chip-smoke-ref-") as tmp:
        src, dst = Path(tmp, "ops.json"), Path(tmp, "tokens.json")
        src.write_text(json.dumps(
            [{"op": r["op"], "input_hashes": r["input_hashes"]}
             for r in executed]))
        ref = Child("reference", [sys.executable, str(ROOT / "chip_smoke.py"),
                                  "--seed", str(seed), "--reference",
                                  str(src), str(dst)], env)
        try:
            left = max(1.0, deadline - time.monotonic())
            rc = ref.proc.wait(timeout=left)
        except subprocess.TimeoutExpired:
            raise SmokeError("single-chip reference timed out") from None
        finally:
            ref.stop()
        if rc != 0:
            raise SmokeError(f"single-chip reference failed (rc {rc}):\n"
                             f"{ref.tail()}")
        want = json.loads(dst.read_text())
    for r, tokens in zip(executed, want):
        if r["tokens"] != tokens:
            raise SmokeError(f"{r['op']}: four-lane tokens {r['tokens']} != "
                             f"single-chip {tokens}")
    log(f"{len(executed)} operators: tokens byte-equal to one ServingEngine "
        "on device 0")


def reference(src: str, dst: str, seed: int) -> None:
    """The comparison run of ``--chips 4``: each operator's prompt alone
    through one ServingEngine on device 0, as each lane ran it."""
    import jax

    from repro.configs import get_config
    from repro.core.jax_executor import JaxExecutor
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve.engine import Request

    enable_compile_cache()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"reference: no TPU (platform {device.platform!r})")
    ex = JaxExecutor(get_config(ARCH), device=device, seed=seed)
    out = []
    with jax.default_device(device):
        for op in json.loads(Path(src).read_text()):
            req = Request(ex.prompt_for(tuple(op["input_hashes"])),
                          max_new_tokens=ex.gen_tokens, temperature=0.0)
            out.append(ex.engine.run([req])[0].generated)
    Path(dst).write_text(json.dumps(out))


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="chip_smoke", description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-lane phase and its "
                         "single-chip comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights")
    ap.add_argument("--reference", nargs=2, metavar=("OPS", "OUT"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.reference:
        sys.path.insert(0, str(ROOT / "src"))
        reference(*args.reference, seed=args.seed)
        return 0

    def _sigterm(signum, frame):
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _sigterm)
    try:
        device = run(args.chips, args.seed)
    except SmokeError as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    except KeyboardInterrupt:
        print("[smoke] interrupted", file=sys.stderr, flush=True)
        return 130
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
