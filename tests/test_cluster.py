"""Cluster-aware client, pump resilience, worker retry loops (DESIGN.md §14).

Three surfaces that together make a failover invisible:

  * ``ClusterAPI`` — writes redirect to the current primary (re-resolved on
    409 fenced / unreachable), reads fan out with sticky feed cursors that
    re-pin when their replica dies;
  * the HTTP auto-pump — transient exceptions are survived with bounded
    backoff (a dead pump with a live HTTP surface acknowledges work that
    never progresses), and its health is visible in ``/admin/replication``;
  * ``worker_main.WorkerProcess`` — one failed heartbeat is a blip, not a
    lost lease: the loop retries inside the TTL grace budget and only
    abandons a computed batch on 410/revoked (or budget exhaustion).
"""
from __future__ import annotations

import sys
import threading
import time
from pathlib import Path

import pytest

from repro.core.cas import CAS
from repro.core.dag import OperatorSpec, OpType
from repro.core.journal import HEAD_REF, EventJournal
from repro.core.worker import DispatchBatch, ExecResult, ExecutionGroup
from repro.fabric import (ClusterAPI, FabricAPI, FabricHTTPServer,
                          FabricService, FollowerAPI, FollowerFabric,
                          RemoteAPI)

from harness import (DEVICES, QUOTAS, assert_cursor_contract, build_service,
                     spec_doc)

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "scripts"))
import worker_main as wm                                      # noqa: E402


def _done() -> ExecResult:
    return ExecResult(outputs=[], duration_s=0.0, load_s=0.0)


# ---------------------------------------------------------------------------
# in-process endpoint fakes
# ---------------------------------------------------------------------------
class Flaky:
    """Wrap an in-process handler table as one 'endpoint': counts calls and
    can be switched to a corpse (every request = 503 unreachable, exactly
    what ``RemoteAPI`` returns for a refused connection)."""

    def __init__(self, inner) -> None:
        self.inner = inner
        self.dead = False
        self.calls = 0

    def handle(self, method, path, body=None, headers=None):
        self.calls += 1
        if self.dead:
            return 503, {"error": "unreachable", "detail": ["refused"]}
        return self.inner.handle(method, path, body, headers)


def _pair(cas=None):
    """One primary (+FabricAPI) and one caught-up follower (+FollowerAPI)
    over a shared CAS, each wrapped as a Flaky endpoint."""
    cas = cas if cas is not None else CAS()
    svc = build_service(cas, batch_size=3)
    follower = FollowerFabric(cas, batch_size=3)
    endpoints = {"http://p": Flaky(FabricAPI(svc)),
                 "http://f": Flaky(FollowerAPI(follower))}
    cluster = ClusterAPI("http://p,http://f",
                         make_api=endpoints.__getitem__,
                         sleep=lambda s: None)
    return cas, svc, follower, endpoints, cluster


def _completed_job(svc, follower, tag="j1"):
    job = svc.submit(spec_doc("acme", tag))
    svc.run_until_idle()
    svc.journal.flush()
    follower.catch_up()
    return job["job_id"]


class TestClusterRouting:
    def test_writes_land_on_the_primary_reads_fan_out(self):
        cas, svc, follower, eps, cluster = _pair()
        code, job = cluster.handle("POST", "/workflows",
                                   {"spec": spec_doc("acme", "w1")})
        assert code == 201
        assert cluster.primary_url == "http://p"
        assert cluster.resolutions == 1      # one probe resolved it
        svc.run_until_idle()
        svc.journal.flush()
        follower.catch_up()
        # reads prefer the follower: the cached primary is the fallback,
        # not the default load
        p_before = eps["http://p"].calls
        for _ in range(4):
            code, jobs = cluster.handle("GET", "/jobs")
            assert code == 200 and len(jobs["jobs"]) == 1
        assert eps["http://p"].calls == p_before
        assert eps["http://f"].calls >= 4

    def test_write_rides_a_fenced_primary(self):
        """409 fenced from the cached primary = re-resolve and retry: the
        first write after a takeover lands on the winner, no config
        change, no caller-visible error."""
        cas, svc, follower, eps, cluster = _pair()
        assert cluster.handle("POST", "/workflows",
                              {"spec": spec_doc("acme", "w1")})[0] == 201
        follower.promote()                   # operator failover
        svc.fenced = True                    # what the zombie's pump observes
        code, job = cluster.handle("POST", "/workflows",
                                   {"spec": spec_doc("acme", "w2")})
        assert code == 201, job
        assert cluster.primary_url == "http://f"
        assert cluster.resolutions >= 2

    def test_write_rides_an_unreachable_primary(self):
        cas, svc, follower, eps, cluster = _pair()
        assert cluster.handle("POST", "/workflows",
                              {"spec": spec_doc("acme", "w1")})[0] == 201
        svc.run_until_idle()
        svc.journal.flush()
        eps["http://p"].dead = True          # kill -9
        follower.promote()
        code, job = cluster.handle("POST", "/workflows",
                                   {"spec": spec_doc("acme", "w2")})
        assert code == 201, job
        assert cluster.primary_url == "http://f"

    def test_no_primary_anywhere_is_a_structured_503(self):
        cas, svc, follower, eps, cluster = _pair()
        eps["http://p"].dead = eps["http://f"].dead = True
        naps = []
        cluster._sleep = naps.append
        code, err = cluster.handle("POST", "/workflows",
                                   {"spec": spec_doc("acme", "w")})
        assert code == 503 and err["error"] == "no_primary"
        # bounded: one backoff between each of the write_attempts tries
        assert len(naps) == cluster.write_attempts - 1

    def test_other_409s_are_real_answers_not_retries(self):
        """Only fenced/read_only_follower mean "wrong endpoint" — a quota
        409 from the true primary must come straight back."""
        cas, svc, follower, eps, cluster = _pair()
        for i in range(3):                   # acme: max_active_workflows=3
            assert cluster.handle("POST", "/workflows", {
                "spec": spec_doc("acme", f"w{i}")})[0] == 201
        resolutions = cluster.resolutions
        code, err = cluster.handle("POST", "/workflows",
                                   {"spec": spec_doc("acme", "w4")})
        assert code == 429, err
        assert cluster.resolutions == resolutions    # no re-resolve churn

    def test_replica_404_falls_through_to_the_primary(self):
        """Read-your-writes: a lagging follower answering 404 for a job the
        primary just created is replica lag, not a missing record."""
        cas, svc, follower, eps, cluster = _pair()
        cluster.resolve_primary()
        job = svc.submit(spec_doc("acme", "fresh"))  # not flushed: follower
        jid = job["job_id"]                          # has never seen it
        for _ in range(4):                           # every rr phase
            code, view = cluster.handle("GET", f"/jobs/{jid}")
            assert code == 200 and view["job_id"] == jid
        # a job nobody has is still an honest 404
        code, err = cluster.handle("GET", "/jobs/nope")
        assert code == 404


class TestFeedStickiness:
    def test_cursor_sticks_then_repins_on_replica_death(self):
        cas, svc, follower, eps, cluster = _pair()
        cluster.resolve_primary()
        jid = _completed_job(svc, follower)
        full = svc.events(jid)["events"]
        # page 1 pins the serving replica (the follower: primary is last)
        code, page1 = cluster.handle("GET", f"/jobs/{jid}/events?since=-1&limit=2")
        assert code == 200 and len(page1["events"]) == 2
        pinned = cluster._sticky[jid]
        assert pinned == "http://f"
        served = eps[pinned].calls
        # every subsequent page goes to the pinned replica despite rr
        cursor = page1["cursor"]
        code, page2 = cluster.handle("GET",
                                     f"/jobs/{jid}/events?since={cursor}&limit=2")
        assert code == 200 and eps[pinned].calls == served + 1
        assert page2["events"] == \
            [e for e in full if e["seq"] > cursor][:2]   # windowed resume
        # the pinned replica dies mid-tail: the feed re-pins and the cursor
        # (a global bus seq) resumes gap-free elsewhere
        cursor = page2["cursor"]
        eps["http://f"].dead = True
        code, page3 = cluster.handle("GET",
                                     f"/jobs/{jid}/events?since={cursor}")
        assert code == 200
        assert cluster._sticky[jid] == "http://p"
        assert_cursor_contract(page3, full, since=cursor)
        # no loss, no duplicates across the whole walk
        seqs = [e["seq"] for page in (page1, page2, page3)
                for e in page["events"]]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
        assert seqs == [e["seq"] for e in full]


# ---------------------------------------------------------------------------
# the auto-pump survives transient errors (and reports its health)
# ---------------------------------------------------------------------------
class TestPumpResilience:
    def test_pump_survives_transient_errors(self, monkeypatch):
        cas = CAS()
        svc = build_service(cas, batch_size=3)
        real_pump, fails = svc.pump, {"n": 0}

        def flaky_pump(max_steps=None):
            if fails["n"] < 3:
                fails["n"] += 1
                raise OSError("injected disk hiccup")
            return real_pump(max_steps)

        monkeypatch.setattr(svc, "pump", flaky_pump)
        server = FabricHTTPServer(FabricAPI(svc), pump_interval_s=0.01)
        server.PUMP_BACKOFF_S = 0.005        # keep the injected retries fast
        with server:
            remote = RemoteAPI(server.url, timeout_s=10)
            code, job = remote.handle("POST", "/workflows",
                                      {"spec": spec_doc("acme", "pumped")})
            assert code == 201
            jid = job["job_id"]
            deadline = time.time() + 30
            view = {}
            while time.time() < deadline:
                code, view = remote.handle("GET", f"/jobs/{jid}")
                if code == 200 and view.get("status") == "completed":
                    break
                time.sleep(0.02)
            # the engine kept being driven despite the crashing pump steps
            assert view.get("status") == "completed", view
            assert fails["n"] == 3
            code, repl = remote.handle("GET", "/admin/replication")
            assert code == 200
            assert repl["pump"]["errors"] == 3
            assert repl["pump"]["running"] is True
            assert repl["pump"]["consecutive_errors"] == 0
            assert "disk hiccup" in repl["pump"]["last_error"]
            code, metrics = remote.handle("GET", "/metrics")
            assert code == 200
            assert "fabric_pump_errors_total 3" in metrics
        assert svc.pump_health["running"] is False   # clean stop

    def test_health_surfaces_pump_state(self):
        cas = CAS()
        svc = build_service(cas, batch_size=3)
        assert "pump" not in svc.health()            # no pump thread yet
        server = FabricHTTPServer(FabricAPI(svc), pump_interval_s=0.01)
        with server:
            remote = RemoteAPI(server.url, timeout_s=10)
            deadline = time.time() + 10
            health = {}
            while time.time() < deadline:
                code, health = remote.handle("GET", "/health")
                if code == 200 and "pump" in health:
                    break
                time.sleep(0.01)
            assert health["pump"]["running"] is True
            assert health["pump"]["errors"] == 0


# ---------------------------------------------------------------------------
# worker lease lifecycle: transient vs lost
# ---------------------------------------------------------------------------
class RoutedAPI:
    """Scripted in-process endpoint: per-path response queues, then a
    per-path default (200 ok when unscripted)."""

    def __init__(self) -> None:
        self.scripts: dict[str, list] = {}
        self.defaults: dict[str, tuple] = {}
        self.calls: list[str] = []

    def script(self, path, *responses, default=None):
        self.scripts.setdefault(path, []).extend(responses)
        if default is not None:
            self.defaults[path] = default

    def handle(self, method, path, body=None, headers=None):
        self.calls.append(path)
        queue = self.scripts.get(path)
        if queue:
            return queue.pop(0)
        return self.defaults.get(path, (200, {"ok": True}))


def _worker(api, *, heartbeat_s=0.01, lease_ttl_s=5.0):
    wp = wm.WorkerProcess("http://unused", "w1", "h100-nvl-94g", api=api)
    wp.heartbeat_s = heartbeat_s
    wp.lease_ttl_s = lease_ttl_s
    return wp


def _run_heartbeat(wp, *, hold_s):
    stop, lost = threading.Event(), threading.Event()
    t = threading.Thread(target=wp._heartbeat_loop, args=("L1", stop, lost),
                         daemon=True)
    t.start()
    lost.wait(hold_s)
    stop.set()
    t.join(timeout=10)
    return lost.is_set()


class TestWorkerLeaseRetry:
    def test_transient_blips_do_not_lose_the_lease(self):
        """Regression: one 503 used to abandon a fully computed batch."""
        api = RoutedAPI()
        api.script("/worker/heartbeat",
                   (503, {"error": "unreachable"}),
                   (500, {"error": "internal_error"}),
                   (409, {"error": "fenced"}))     # then default 200 ok
        assert _run_heartbeat(_worker(api), hold_s=0.3) is False
        assert api.calls.count("/worker/heartbeat") >= 4

    def test_persistent_outage_expires_within_the_ttl_budget(self):
        api = RoutedAPI()
        api.defaults["/worker/heartbeat"] = (503, {"error": "unreachable"})
        wp = _worker(api, lease_ttl_s=0.05)
        start = time.monotonic()
        assert _run_heartbeat(wp, hold_s=10.0) is True
        assert time.monotonic() - start < 5.0      # gave up, not forever

    def test_410_and_revoked_lose_immediately(self):
        for resp in ((410, {"error": "fenced_lease"}),
                     (200, {"ok": False, "revoked": True})):
            api = RoutedAPI()
            api.defaults["/worker/heartbeat"] = resp
            assert _run_heartbeat(_worker(api), hold_s=10.0) is True
            assert api.calls.count("/worker/heartbeat") == 1

    def _stub_batch(self, monkeypatch):
        spec = OperatorSpec(name="op", op_type=OpType.TOOL, inputs=[])
        batch = DispatchBatch(batch_id=0, h_exec="e", worker_id="w",
                              admitted_at=0.0, groups=[ExecutionGroup(
                                  h_task="t", h_exec="e", spec=spec,
                                  input_hashes=())])
        monkeypatch.setattr(wm, "batch_from_wire", lambda wire: batch)
        monkeypatch.setattr(wm, "result_to_wire", lambda res: {"stub": True})

    def test_complete_retries_through_a_failover(self, monkeypatch):
        """A 503/409 on /worker/complete mid-failover is retried inside the
        TTL budget (ClusterAPI re-resolves underneath) — the computed
        result is delivered, not dropped."""
        self._stub_batch(monkeypatch)
        api = RoutedAPI()
        api.script("/worker/complete",
                   (503, {"error": "unreachable"}),
                   (409, {"error": "fenced"}))     # then default 200 ok
        wp = _worker(api)
        wp.executor.execute = lambda batch, shell, cb: _done()
        wp.run_one({"lease_id": "L1", "batch": {}})
        assert wp.done == 1
        assert api.calls.count("/worker/complete") == 3

    def test_complete_gives_up_on_410(self, monkeypatch):
        self._stub_batch(monkeypatch)
        api = RoutedAPI()
        api.defaults["/worker/complete"] = (410, {"error": "fenced_lease"})
        wp = _worker(api)
        wp.executor.execute = lambda batch, shell, cb: _done()
        wp.run_one({"lease_id": "L1", "batch": {}})
        assert wp.done == 0
        assert api.calls.count("/worker/complete") == 1

    def test_lost_lease_drops_the_result(self, monkeypatch):
        self._stub_batch(monkeypatch)
        api = RoutedAPI()
        api.defaults["/worker/heartbeat"] = (410, {"error": "fenced_lease"})
        wp = _worker(api)

        def slow_execute(batch, shell, cb):
            time.sleep(0.05)                 # let the heartbeat fire
            return _done()

        wp.executor.execute = slow_execute
        wp.run_one({"lease_id": "L1", "batch": {}})
        assert wp.done == 0
        assert "/worker/complete" not in api.calls

    def test_comma_url_builds_a_cluster_client(self):
        wp = wm.WorkerProcess("http://a:1,http://b:2", "w1", "h100-nvl-94g")
        assert isinstance(wp.api, ClusterAPI)
        assert wp.api.endpoints == ["http://a:1", "http://b:2"]
        assert isinstance(
            wm.WorkerProcess("http://a:1", "w1", "h100-nvl-94g").api,
            RemoteAPI)


# ---------------------------------------------------------------------------
# end to end over real sockets: abrupt primary death, self-promotion,
# the cluster client rides it
# ---------------------------------------------------------------------------
class TestAutoFailoverHTTP:
    def test_client_rides_an_auto_promotion(self):
        cas = CAS()                          # shared store = shared "disk"
        journal = EventJournal(cas, batch_size=3, lease_ttl_s=0.4)
        svc = FabricService(seed=7, cas=cas, device_classes=DEVICES,
                            journal=journal)
        for tenant, quota in QUOTAS.items():
            svc.set_quota(tenant, quota)
        pserver = FabricHTTPServer(FabricAPI(svc),
                                   pump_interval_s=0.01).start()

        follower = FollowerFabric(cas, batch_size=3, auto_promote=True,
                                  lease_ttl_s=0.4)
        fapi = FollowerAPI(follower)
        fserver = FabricHTTPServer(fapi, auto_pump=False,
                                   pump_interval_s=0.01)
        fapi.on_promoted = lambda _svc: fserver.enable_pump()
        fserver.start()
        stop = threading.Event()
        tail = threading.Thread(target=follower.tail_loop,
                                args=(stop, fserver.lock),
                                kwargs={"poll_interval_s": 0.01,
                                        "wake_every_s": 0.05}, daemon=True)
        tail.start()
        try:
            cluster = ClusterAPI(f"{pserver.url},{fserver.url}",
                                 timeout_s=10, retry_backoff_s=0.05,
                                 write_attempts=60)
            code, job1 = cluster.handle("POST", "/workflows",
                                        {"spec": spec_doc("acme", "before")})
            assert code == 201
            jid1 = job1["job_id"]
            # wait until the FOLLOWER serves it completed: only flushed
            # (durable) history reaches a standby, so the kill below
            # cannot lose the job
            deadline = time.time() + 30
            while time.time() < deadline:
                code, view = RemoteAPI(fserver.url).handle(
                    "GET", f"/jobs/{jid1}")
                if code == 200 and view.get("status") == "completed":
                    break
                time.sleep(0.02)
            assert view.get("status") == "completed", view
            # kill -9 the primary: stop its threads and close the socket
            # with NO shutdown flush, no operator action follows
            pserver._stop.set()
            pserver.httpd.shutdown()
            pserver.httpd.server_close()
            # the standby detects the expired lease and elects itself
            deadline = time.time() + 30
            while follower.promoted is None and time.time() < deadline:
                time.sleep(0.02)
            assert follower.promoted is not None
            assert follower.elections_won == 1
            assert cas.ref_entry(HEAD_REF)[1] == 1
            # the same client object keeps working: its next write
            # re-resolves to the new primary
            code, job2 = cluster.handle("POST", "/workflows",
                                        {"spec": spec_doc("acme", "after")})
            assert code == 201, job2
            assert cluster.primary_url == fserver.url
            jid2 = job2["job_id"]
            deadline = time.time() + 30
            while time.time() < deadline:
                code, view = cluster.handle("GET", f"/jobs/{jid2}")
                if code == 200 and view.get("status") == "completed":
                    break
                time.sleep(0.02)
            assert view.get("status") == "completed", view
            # nothing lost, nothing doubled: both jobs, each completed once
            code, jobs = cluster.handle("GET", "/jobs")
            assert code == 200
            by_id = {j["job_id"]: j["status"] for j in jobs["jobs"]}
            assert by_id[jid1] == "completed" and by_id[jid2] == "completed"
            assert len(jobs["jobs"]) == 2
        finally:
            stop.set()
            tail.join(timeout=10)
            fserver.stop()
