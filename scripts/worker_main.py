#!/usr/bin/env python
"""worker_main: one out-of-process FlowMesh worker lane (DESIGN.md §13).

Registers with a fabric served with ``--remote-workers``, long-polls
``POST /worker/lease`` for dispatched batches, executes them with the local
executor while a background thread heartbeats the lease, and reports the
result to ``POST /worker/complete``. A fenced or revoked lease means the
control plane moved on — the result is dropped and the lane keeps serving.
A *transient* heartbeat failure (503 blip, 409 mid-failover) is NOT a lost
lease: the server-side lease stays live for a full TTL after the last
successful renewal, so the loop keeps retrying inside that budget before
giving the batch up.

``--url`` accepts a comma-separated endpoint list (primary + standbys):
the worker then talks through ``ClusterAPI`` and rides an auto-promotion
without restarting — its writes re-resolve to whichever process owns the
journal epoch.

Without ``--arch`` the lane executes with the virtual-time simulator (the CI
drills' workers). With ``--arch`` the process runs the registry architecture
at its published widths with JAX: one lane per device it sees, each a thread
whose weights, cache and jitted calls live on its own device, advertising
the device class its ``device_kind`` maps to. Weights are random, made from
``--seed``.

    PYTHONPATH=src python scripts/worker_main.py \\
        --url http://127.0.0.1:8123,http://127.0.0.1:8124 \\
        --worker-id w1 --device-class h100-nvl-94g
    PYTHONPATH=src python scripts/worker_main.py \\
        --url http://127.0.0.1:8123 --arch llama-3.2-1b

Each lane prints a ``device {...}`` line before it loads weights, a
``ready {...}`` line after, and one ``batch {...}`` line per batch it served;
a JAX lane's ``batch`` line carries, under ``engine``, what its serving
engine's counters (``ServingEngine.stats()``) added in that batch.
"""
from __future__ import annotations

import argparse
import json
import os
import queue
import sys
import threading
import time
import traceback

from repro.core.cost_model import DEVICE_CLASSES
from repro.core.simulator import SimExecutor
from repro.core.transport import batch_from_wire, result_to_wire
from repro.core.worker import Executor, Worker, WorkerState
from repro.fabric.cluster import ClusterAPI
from repro.fabric.http import RemoteAPI


_OUT = threading.Lock()


def say(text: str, *, err: bool = False) -> None:
    """Print one whole line: JAX lanes are threads of one process, and a
    reader parses their tagged lines."""
    with _OUT:
        print(text, file=sys.stderr if err else sys.stdout, flush=True)


class WorkerProcess:
    def __init__(self, url: str, worker_id: str, device_class: str, *,
                 executor: Executor | None = None, seed: int = 0,
                 poll_s: float = 10.0, slow_ms: float = 0.0,
                 api=None) -> None:
        if api is not None:
            self.api = api              # injected (tests)
        elif "," in url:
            # endpoint list: ride failovers through the cluster client
            self.api = ClusterAPI(url, timeout_s=poll_s + 30.0)
        else:
            self.api = RemoteAPI(url, timeout_s=poll_s + 30.0)
        self.requested_id = worker_id
        self.worker_id = worker_id
        self.device_class = device_class
        self.poll_s = poll_s
        self.slow_ms = slow_ms
        self.heartbeat_s = 1.0          # replaced by the register response
        self.lease_ttl_s = 4.0          # replaced by the register response
        self.executor = executor if executor is not None \
            else SimExecutor(seed=seed)
        #: local lane shell: a persistent ResidentSet across batches keeps
        #: hot/cold behavior on this lane realistic
        self.shell = Worker(worker_id, DEVICE_CLASSES[device_class], now=0.0)
        self.shell.state = WorkerState.ACTIVE
        self.done = 0

    # ---------------------------------------------------------------- wire --
    def register(self) -> int:
        code, out = self.api.handle("POST", "/worker/register", {
            "worker_id": self.requested_id,
            "device_class": self.device_class})
        if code != 200:
            say(f"register: HTTP {code} {out}", err=True)
            return code
        # adopt the assigned id — a crashed predecessor keeps our name
        self.worker_id = out["worker_id"]
        self.shell.worker_id = self.worker_id
        self.heartbeat_s = float(out.get("heartbeat_s") or 1.0)
        self.lease_ttl_s = float(out.get("lease_ttl_s")
                                 or 4.0 * self.heartbeat_s)
        say(f"registered as {self.worker_id} "
            f"(heartbeat {self.heartbeat_s:.2f}s)")
        return code

    def _register_until_ok(self) -> bool:
        backoff = 0.2
        while True:
            code = self.register()
            if code == 200:
                return True
            if code in (409,):   # fenced primary / no remote transport
                return False
            time.sleep(backoff)
            backoff = min(backoff * 2, 5.0)

    def _heartbeat_loop(self, lease_id: str, stop: threading.Event,
                        lost: threading.Event) -> None:
        """Renew the lease until the batch finishes or it is truly gone.

        Only two answers mean the lease is lost: HTTP 410 (fenced — the
        control plane re-granted or expired it) and an explicit
        ``revoked`` (cancellation: abandoning the batch is the ack).
        Everything else — 503 unreachable blip, 5xx, a 409 from a fenced
        primary mid-failover — is transient: the *server-side* lease
        stays live for a full TTL after our last successful renewal, so
        we keep retrying inside that budget instead of discarding a
        fully computed batch on the first hiccup."""
        grace_deadline: float | None = None
        while not stop.wait(self.heartbeat_s):
            code, out = self.api.handle("POST", "/worker/heartbeat", {
                "worker_id": self.worker_id, "lease_id": lease_id})
            ok = code == 200 and isinstance(out, dict) and out.get("ok")
            if ok:
                grace_deadline = None
                continue
            revoked = (code == 200 and isinstance(out, dict)
                       and out.get("revoked"))
            if code == 410 or revoked:
                lost.set()       # revoked or fenced: abandon the batch
                return
            now = time.monotonic()
            if grace_deadline is None:
                grace_deadline = now + self.lease_ttl_s
            if now >= grace_deadline:
                say(f"lease {lease_id}: no successful heartbeat for "
                    f"{self.lease_ttl_s:.1f}s; assuming expired", err=True)
                lost.set()
                return

    # ------------------------------------------------------------- execute --
    def run_one(self, lease: dict) -> None:
        lease_id = lease["lease_id"]
        batch = batch_from_wire(lease["batch"])
        stop, lost = threading.Event(), threading.Event()
        hb = threading.Thread(target=self._heartbeat_loop,
                              args=(lease_id, stop, lost), daemon=True)
        hb.start()
        spec = batch.groups[0].spec
        engine = getattr(self.executor, "engine", None)
        before = engine.stats() if engine is not None else None
        try:
            if self.slow_ms > 0:
                # test/CI hook: hold the batch so a harness can kill -9
                # this process while the lease is live (heartbeats keep
                # renewing it until the kill lands)
                time.sleep(self.slow_ms / 1000.0)
            t0 = time.perf_counter()
            result = self.executor.execute(batch, self.shell, None)
            wall_s = time.perf_counter() - t0
            if spec.model_id and not result.failed:
                self.shell.make_resident(spec.h_model, spec.model_id)
        finally:
            stop.set()
        hb.join()
        say("batch " + json.dumps({
            "lane": self.worker_id, "batch_id": batch.batch_id,
            "op": spec.name, "op_type": spec.op_type.value,
            "model_id": spec.model_id, "groups": len(batch.groups),
            "speculative": batch.speculative, "wall_s": wall_s,
            "failure": result.failure,
            "devices": self.executor.placement(),
            "engine": None if engine is None else
            {k: v - before[k] for k, v in engine.stats().items()}}))
        if lost.is_set():
            say(f"lease {lease_id} revoked/fenced; result dropped")
            return
        # the completion gets the same transient-vs-terminal treatment as
        # heartbeats: an unreachable/fenced primary mid-failover is retried
        # within the TTL budget (ClusterAPI re-resolves underneath us)
        deadline = time.monotonic() + self.lease_ttl_s
        while True:
            code, out = self.api.handle("POST", "/worker/complete", {
                "worker_id": self.worker_id, "lease_id": lease_id,
                "result": result_to_wire(result)})
            if code == 200 and isinstance(out, dict) and out.get("ok"):
                self.done += 1
                return
            if code in (503, 409) and time.monotonic() < deadline:
                time.sleep(min(self.heartbeat_s, 0.5))
                continue
            # 410 = fenced (lease lapsed under us), revoked, or the engine
            # re-dispatched: either way the work is not ours anymore
            say(f"complete {lease_id}: HTTP {code} {out}")
            return

    # ---------------------------------------------------------------- loop --
    def loop(self, max_batches: int | None = None) -> int:
        if not self._register_until_ok():
            return 1
        while max_batches is None or self.done < max_batches:
            code, out = self.api.handle("POST", "/worker/lease", {
                "worker_id": self.worker_id, "wait_s": self.poll_s})
            if code == 200:
                lease = out.get("lease") if isinstance(out, dict) else None
                if lease is not None:
                    self.run_one(lease)
                continue
            if code == 410:
                # lane expired server-side: start over (possibly new id)
                if not self._register_until_ok():
                    return 1
                continue
            if code == 409:
                say(f"fabric refused lane: {out}", err=True)
                return 1
            time.sleep(0.5)      # unreachable/5xx: retry quietly
        say(f"{self.worker_id}: {self.done} batches served")
        return 0


# ------------------------------------------------------------ JAX lanes --
def _jax_lane(url: str, worker_id: str, cfg, device, *, n_devices: int,
              seed: int, poll_s: float, slow_ms: float,
              max_batches: int | None) -> int:
    """One lane on one device, run in its own thread."""
    import jax
    from repro.core.cost_model import device_class_for_kind
    from repro.core.jax_executor import JaxExecutor
    from repro.kernels import ops
    widths = {k: getattr(cfg, k) for k in (
        "n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
        "vocab_size")}
    say("device " + json.dumps({
        "lane": worker_id, "platform": device.platform,
        "kind": device.device_kind, "device_id": device.id,
        "count": n_devices, "kernel_backend": ops._resolve(None),
        "arch": cfg.name, "head_dim": cfg.hd, **widths}))
    device_class = device_class_for_kind(device.device_kind)
    t0 = time.perf_counter()
    executor = JaxExecutor(cfg, device=device, seed=seed)
    jax.block_until_ready(executor.params)
    n_params = sum(x.size for x in jax.tree.leaves(executor.params))
    say("ready " + json.dumps({
        "lane": worker_id, "device_class": device_class,
        "params": n_params, "setup_s": time.perf_counter() - t0}))
    wp = WorkerProcess(url, worker_id, device_class, executor=executor,
                       poll_s=poll_s, slow_ms=slow_ms)
    return wp.loop(max_batches)


def run_jax_lanes(url: str, worker_id: str, cfg, *, seed: int = 0,
                  poll_s: float = 10.0, slow_ms: float = 0.0,
                  max_batches: int | None = None) -> int:
    """One lane per JAX device, all in this process (a chip belongs to one
    process). Returns when every lane has returned, or as soon as one
    fails — the others are daemon threads and end with the process."""
    import jax
    devices = jax.devices()
    done: queue.Queue[int] = queue.Queue()

    def lane(device) -> None:
        rc = 1
        try:
            rc = _jax_lane(url, f"{worker_id}-d{device.id}", cfg, device,
                           n_devices=len(devices), seed=seed, poll_s=poll_s,
                           slow_ms=slow_ms, max_batches=max_batches)
        except Exception:
            say(traceback.format_exc(), err=True)
        finally:
            done.put(rc)

    for device in devices:
        threading.Thread(target=lane, args=(device,), daemon=True,
                         name=f"lane-d{device.id}").start()
    for _ in devices:
        rc = done.get()
        if rc != 0:
            return rc
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="worker_main", description=__doc__)
    ap.add_argument("--url", required=True,
                    help="fabric base URL (serve --remote-workers); a "
                         "comma-separated list enables the cluster client "
                         "(failover-riding)")
    ap.add_argument("--worker-id", default=None,
                    help="requested lane id (default: worker-<pid>); the "
                         "fabric may assign a suffixed one")
    ap.add_argument("--device-class", default=None,
                    choices=sorted(DEVICE_CLASSES),
                    help="device class this simulated lane advertises "
                         "(default h100-nvl-94g); a JAX lane derives its "
                         "own from the device kind")
    ap.add_argument("--arch", default=None,
                    help="run this registry architecture with JAX at its "
                         "published widths, one lane per device (default: "
                         "the virtual-time simulator)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--poll-s", type=float, default=10.0,
                    help="long-poll hold per lease request")
    ap.add_argument("--slow-ms", type=float, default=0.0,
                    help="sleep this long before executing each batch "
                         "(kill -9 harness hook; heartbeats continue)")
    ap.add_argument("--max-batches", type=int, default=None,
                    help="exit after serving N batches")
    args = ap.parse_args(argv)
    if args.arch and args.device_class:
        ap.error("a JAX lane derives its device class from the device kind")
    wid = args.worker_id or f"worker-{os.getpid()}"
    try:
        if args.arch:
            from repro.configs import get_config
            from repro.launch.compile_cache import enable_compile_cache
            enable_compile_cache()
            return run_jax_lanes(args.url, wid, get_config(args.arch),
                                 seed=args.seed, poll_s=args.poll_s,
                                 slow_ms=args.slow_ms,
                                 max_batches=args.max_batches)
        wp = WorkerProcess(args.url, wid, args.device_class or "h100-nvl-94g",
                           seed=args.seed, poll_s=args.poll_s,
                           slow_ms=args.slow_ms)
        return wp.loop(args.max_batches)
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
