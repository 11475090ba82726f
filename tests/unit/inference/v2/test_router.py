"""Replica fleet router: balancing, quarantine, migration, autoscaling.

CPU-safe and fast: each "replica" is a stub daemon subprocess that speaks
the real scheduler HTTP surface (``/health``, ``/generate``,
``/journal/export``, ``/journal/import``, ``/requests/<uid>/stream``)
over the REAL ``RequestJournal`` WAL — so the fleet tests exercise true
process lifecycles, true on-disk journal bytes, and true cross-replica
frame migration, without jax. Tokens are a pure function of (uid, index),
so "byte-exact continuation on a peer" is checkable to the token.

The model-backed migration legs (greedy + sampled + speculative byte
parity through ``/journal/import`` on a real engine) live at the bottom,
gated like the other engine tests.
"""

import http.client
import json
import os
import signal
import socket
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from deepspeed_tpu.inference.v2.router import (MigrationFailed, ReplicaFleet,
                                               create_router_server)
from deepspeed_tpu.utils.fault_injection import get_fault_injector

pytestmark = pytest.mark.faults

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))))

# A stub serving replica: the scheduler HTTP surface over the real WAL.
# Decode emits token(uid, i) = (uid * 31 + i * 7) % 50000 once per TICK —
# deterministic across replicas, so a migrated stream's continuation is
# byte-exact iff the import replayed the journal correctly.
STUB = textwrap.dedent("""
    import itertools, json, os, sys, threading, time
    sys.path.insert(0, sys.argv[2])
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
    from deepspeed_tpu.inference.v2.journal import (RequestJournal,
                                                    entries_from_frames)

    PORT = int(sys.argv[1])
    TICK = float(os.environ.get("STUB_TICK", "0.02"))
    BASE = int(os.environ.get("DS_SERVE_UID_BASE", "0"))
    journal = RequestJournal()  # resolves DS_TPU_JOURNAL_DIR
    lock = threading.Lock()
    reqs = {}   # uid -> dict(tokens=[], max=n, done=bool)
    uid_iter = itertools.count(BASE + 1)
    state = {"migrating": False, "export_depth": 0, "fake_waiting": 0,
             "imported": 0}

    def token(uid, i):
        return (uid * 31 + i * 7) % 50000

    def admit(uid, prompt, params, tokens, journaled):
        with lock:
            reqs[uid] = {"prompt": prompt, "params": params,
                         "tokens": list(tokens),
                         "max": int(params.get("max_new_tokens", 8)),
                         "done": False}
            if not journaled:
                journal.record_admit(uid, prompt, params)
                if tokens:
                    journal.record_progress(uid, tokens, len(tokens),
                                            len(tokens))

    for e in journal.recover():
        admit(e.uid, e.prompt, e.params, e.tokens, journaled=True)

    def decode_loop():
        while True:
            time.sleep(TICK)
            with lock:
                if state["migrating"]:
                    continue
                for uid, r in reqs.items():
                    if r["done"]:
                        continue
                    i = len(r["tokens"])
                    t = token(uid, i)
                    r["tokens"].append(t)
                    journal.record_progress(uid, [t], i + 1, i + 1)
                    if len(r["tokens"]) >= r["max"]:
                        r["done"] = True
                        journal.record_finish(uid)

    threading.Thread(target=decode_loop, daemon=True).start()

    class H(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def _json(self, code, obj, headers=()):
            body = json.dumps(obj).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            for k, v in headers:
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)

        def _stream(self, uid, start):
            self.send_response(200)
            self.send_header("Content-Type", "application/jsonl")
            self.send_header("Transfer-Encoding", "chunked")
            self.send_header("X-DS-Request-Id", str(uid))
            self.end_headers()
            i = start
            while True:
                with lock:
                    r = reqs.get(uid)
                    toks, done = (list(r["tokens"]), r["done"]) if r \\
                        else ([], True)
                while i < len(toks):
                    line = json.dumps({"token": toks[i]}).encode() + b"\\n"
                    self.wfile.write(hex(len(line))[2:].encode() + b"\\r\\n"
                                     + line + b"\\r\\n")
                    i += 1
                if done and i >= len(toks):
                    self.wfile.write(b"0\\r\\n\\r\\n")
                    return
                time.sleep(TICK / 2)

        def do_GET(self):
            if self.path == "/health":
                with lock:
                    live = sum(1 for r in reqs.values() if not r["done"])
                    waiting = state["fake_waiting"]
                    mig = state["migrating"]
                st = {"status": "migrating" if mig else "ok",
                      "waiting": waiting, "live": live,
                      "fused_occupancy": 0.0, "migrating": mig,
                      "journal_export_depth": state["export_depth"],
                      "imported_requests": state["imported"],
                      "stopped": False, "draining": False,
                      "degraded": False}
                self._json(503 if mig else 200, st)
            elif self.path == "/journal/export":
                with lock:
                    state["migrating"] = True
                    frames, depth = journal.export_frames()
                    state["export_depth"] = depth
                self.send_response(200)
                self.send_header("Content-Type", "application/octet-stream")
                self.send_header("Content-Length", str(len(frames)))
                self.send_header("X-DS-Journal-Depth", str(depth))
                self.end_headers()
                self.wfile.write(frames)
            elif self.path.startswith("/requests/"):
                parts = [p for p in self.path.split("?")[0].split("/") if p]
                uid = int(parts[1])
                with lock:
                    known = uid in reqs
                if not known:
                    self._json(404, {"error": "unknown"})
                    return
                if len(parts) > 2 and parts[2] == "stream":
                    q = self.path.split("from_token=")
                    start = int(q[1].split("&")[0]) if len(q) > 1 else 0
                    self._stream(uid, start)
                else:
                    while True:
                        with lock:
                            r = reqs[uid]
                            if r["done"]:
                                self._json(200, {"tokens": r["tokens"]})
                                return
                        time.sleep(TICK / 2)
            else:
                self._json(404, {"error": "not found"})

        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            body = self.rfile.read(n)
            if self.path == "/generate":
                req = json.loads(body)
                uid = next(uid_iter)
                admit(uid, req.get("prompt") or [1], req, [],
                      journaled=False)
                if req.get("stream"):
                    self._stream(uid, 0)
                else:
                    while True:
                        with lock:
                            r = reqs[uid]
                            if r["done"]:
                                self._json(200,
                                           {"uid": uid,
                                            "tokens": r["tokens"]},
                                           headers=(("X-DS-Request-Id",
                                                     str(uid)),))
                                return
                        time.sleep(TICK / 2)
            elif self.path == "/journal/import":
                entries, bad = entries_from_frames(body)
                refused = []
                for e in entries:
                    with lock:
                        collide = e.uid in reqs
                    if collide:
                        refused.append(e.uid)
                        continue
                    admit(e.uid, e.prompt, e.params, e.tokens,
                          journaled=False)
                    with lock:
                        state["imported"] += 1
                self._json(200, {"status": "imported",
                                 "imported": len(entries) - len(refused),
                                 "finished": 0, "refused_uids": refused,
                                 "quarantined_records": bad})
            elif self.path == "/debug/set_waiting":
                with lock:
                    state["fake_waiting"] = int(json.loads(body)["waiting"])
                self._json(200, {"ok": True})
            else:
                self._json(404, {"error": "not found"})

    srv = ThreadingHTTPServer(("127.0.0.1", PORT), H)
    srv.daemon_threads = True
    srv.serve_forever()
""")


def _stub_cmd(tmp_path):
    stub = tmp_path / "stub_replica.py"
    if not stub.exists():
        stub.write_text(STUB)
    return [sys.executable, str(stub), "{port}", REPO]


def _fleet(tmp_path, n=2, tick="0.02", **kw):
    env = {**os.environ, "STUB_TICK": tick, "PYTHONPATH": ""}
    kw.setdefault("probe_interval", 0.1)
    kw.setdefault("probe_timeout", 1.0)
    kw.setdefault("grace_s", 2.0)
    kw.setdefault("migrate_stall_s", 5.0)
    kw.setdefault("retry_after_s", 2.0)
    kw.setdefault("autoscale", False)
    kw.setdefault("max_replicas", 4)
    kw.setdefault("jitter_seed", 0)
    fleet = ReplicaFleet(_stub_cmd(tmp_path), replicas=n,
                         journal_root=str(tmp_path / "fleet"),
                         env=env, **kw).start()
    assert fleet.wait_ready(30), "fleet never became healthy"
    return fleet


def _router(fleet, **kw):
    srv = create_router_server(fleet, port=0, **kw)
    port = srv.server_address[1]
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, port


def _get_json(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", path)
    resp = conn.getresponse()
    out = json.loads(resp.read())
    code = resp.status
    headers = dict(resp.getheaders())
    conn.close()
    return code, out, headers


def _post_json(port, path, body, timeout=60):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", path, json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    out = json.loads(resp.read())
    code = resp.status
    headers = dict(resp.getheaders())
    conn.close()
    return code, out, headers


def _stub_token(uid, i):
    return (uid * 31 + i * 7) % 50000


@pytest.fixture(autouse=True)
def _clean_injector():
    get_fault_injector().reset()
    yield
    get_fault_injector().reset()


# ---------------------------------------------------------------------------
# balancing + health surface
# ---------------------------------------------------------------------------


def test_balanced_submit_and_fleet_health(tmp_path):
    """Submits land on the least-loaded healthy replica; the router's
    /health reports the pool; non-stream bodies round-trip unchanged."""
    fleet = _fleet(tmp_path, n=2)
    srv, port = _router(fleet)
    try:
        code, health, _ = _get_json(port, "/health")
        assert code == 200 and health["status"] == "ok"
        assert health["pool_size"] == 2 and health["healthy"] == 2

        code, out, hdrs = _post_json(
            port, "/generate", {"prompt": [1, 2], "max_new_tokens": 3})
        assert code == 200
        uid = out["uid"]
        assert out["tokens"] == [_stub_token(uid, i) for i in range(3)]
        assert hdrs.get("X-DS-Request-Id") == str(uid)
        # the owner map reflects the admitting replica
        assert fleet.owner_of(uid) is not None

        # distinct strides: a second submit (possibly on the peer) can
        # never collide uids with the first
        code, out2, _ = _post_json(
            port, "/generate", {"prompt": [3], "max_new_tokens": 2})
        assert code == 200 and out2["uid"] != uid
    finally:
        srv.shutdown()
        fleet.stop()


def test_submit_retries_peer_when_replica_refuses(tmp_path):
    """A dead-but-not-yet-reaped replica refuses the TCP connect; the
    router must retry the submit against the peer instead of failing."""
    fleet = _fleet(tmp_path, n=2)
    srv, port = _router(fleet)
    try:
        victim = fleet.pick()
        victim.proc.kill()
        victim.proc.wait()
        code, out, _ = _post_json(
            port, "/generate", {"prompt": [5], "max_new_tokens": 2})
        assert code == 200
        assert out["tokens"] == [_stub_token(out["uid"], i)
                                 for i in range(2)]
    finally:
        srv.shutdown()
        fleet.stop()


# ---------------------------------------------------------------------------
# crash mid-stream -> WAL migration -> byte-exact continuation on the peer
# ---------------------------------------------------------------------------


def test_kill_replica_mid_stream_continues_byte_exact(tmp_path):
    """The acceptance scenario: SIGKILL one replica of a 2-fleet while a
    client is mid-stream THROUGH the router. The dead replica's WAL is
    drained off disk, the peer imports and continues decoding, and the
    client's single chunked stream carries every token exactly once —
    byte-identical to the deterministic reference, zero dropped uids."""
    n_tok = 40
    fleet = _fleet(tmp_path, n=2, tick="0.03")
    srv, port = _router(fleet, reattach_timeout_s=30.0)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("POST", "/generate",
                     json.dumps({"prompt": [9, 9], "max_new_tokens": n_tok,
                                 "stream": True}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        uid = int(resp.getheader("X-DS-Request-Id"))
        owner = fleet.owner_of(uid)
        assert owner is not None

        got, buf = [], b""
        while len(got) < 5:
            chunk = resp.read1(65536)
            assert chunk, "stream ended before the kill"
            buf += chunk
            *lines, buf = buf.split(b"\n")
            got.extend(json.loads(l)["token"] for l in lines if l.strip())
        owner.proc.send_signal(signal.SIGKILL)
        owner.proc.wait()

        while True:
            chunk = resp.read1(65536)
            if not chunk:
                break
            buf += chunk
            *lines, buf = buf.split(b"\n")
            for l in lines:
                if not l.strip():
                    continue
                rec = json.loads(l)
                assert "error" not in rec, f"stream errored: {rec}"
                got.append(rec["token"])
        conn.close()

        ref = [_stub_token(uid, i) for i in range(n_tok)]
        assert got == ref, "migrated stream diverged (gap or duplicate)"
        # the peer owns the uid now; the fleet recorded one crash migration
        new_owner = fleet.owner_of(uid)
        assert new_owner is not None and new_owner is not owner
        assert any(m["mode"] == "crash" and m["migrated"] >= 1
                   for m in fleet.migrations)
        assert fleet.lost_retry_after(uid) is None  # zero dropped uids
    finally:
        srv.shutdown()
        fleet.stop()


def test_scale_down_live_migrates_then_terminates(tmp_path):
    """SIGTERM scale-down drains the victim over /journal/export (live
    migration) and in-flight requests finish on the peer."""
    fleet = _fleet(tmp_path, n=2, tick="0.05")
    srv, port = _router(fleet)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("POST", "/generate",
                     json.dumps({"prompt": [2], "max_new_tokens": 30,
                                 "stream": True}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        uid = int(resp.getheader("X-DS-Request-Id"))
        victim = fleet.owner_of(uid)
        # scale_down picks the LEAST loaded replica; make the peer report
        # a deep queue so the victim is the stream's owner
        peer = next(r for r in fleet.healthy() if r is not victim)
        c2 = http.client.HTTPConnection("127.0.0.1", peer.port, timeout=10)
        c2.request("POST", "/debug/set_waiting",
                   json.dumps({"waiting": 20}))
        c2.getresponse().read()
        c2.close()
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and peer.score() < 20:
            time.sleep(0.05)  # wait for a probe to pick up the depth
        assert fleet.scale_down()
        assert any(m["mode"] == "live" for m in fleet.migrations)
        assert fleet.owner_of(uid) is not victim

        got, buf = [], b""
        while True:
            chunk = resp.read1(65536)
            if not chunk:
                break
            buf += chunk
            *lines, buf = buf.split(b"\n")
            got.extend(json.loads(l)["token"] for l in lines
                       if l.strip() and b"error" not in l)
        conn.close()
        assert got == [_stub_token(uid, i) for i in range(30)]
        assert len(fleet.healthy()) == 1
    finally:
        srv.shutdown()
        fleet.stop()


# ---------------------------------------------------------------------------
# probe-timeout quarantine + re-admission
# ---------------------------------------------------------------------------


def test_probe_timeout_quarantines_then_readmits(tmp_path):
    """router.probe_timeout makes 2 consecutive probes time out: the
    replica is quarantined (no routing, 503 from the router); the next
    healthy probe re-admits it and traffic flows again."""
    fleet = _fleet(tmp_path, n=1, quarantine_after=2, min_replicas=1)
    srv, port = _router(fleet)
    try:
        # configure AFTER the fleet is healthy so the startup probes are
        # not the ones consumed by the fault plan
        get_fault_injector().configure({"faults": [
            {"site": "router.probe_timeout", "nth": 1, "times": 2}]})
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if any(r.state == "quarantined" for r in fleet._pool):
                break
            time.sleep(0.02)
        else:
            pytest.fail("probe-timeout streak never quarantined")
        code, out, hdrs = _get_json(port, "/health")
        assert code == 503 and out["healthy"] == 0
        assert int(hdrs["Retry-After"]) >= 1
        code, out, hdrs = _post_json(
            port, "/generate", {"prompt": [1], "max_new_tokens": 1})
        assert code == 503 and "Retry-After" in hdrs

        # the fault plan is spent -> the next probe succeeds -> re-admit
        assert fleet.wait_ready(20, n=1)
        code, out, _ = _post_json(
            port, "/generate", {"prompt": [1], "max_new_tokens": 1})
        assert code == 200
        assert "router.probe_timeout#1" in get_fault_injector().fired
    finally:
        srv.shutdown()
        fleet.stop()


# ---------------------------------------------------------------------------
# graceful degradation: no healthy peer
# ---------------------------------------------------------------------------


def test_no_peer_migration_degrades_with_retry_after(tmp_path):
    """With zero healthy peers the migration error-finishes the affected
    uids with a Retry-After hint — the router answers 503 instead of
    hanging — and the backfilled replica serves fresh traffic again."""
    fleet = _fleet(tmp_path, n=1, min_replicas=1, tick="0.05")
    srv, port = _router(fleet, reattach_timeout_s=5.0)
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("POST", "/generate",
                     json.dumps({"prompt": [7], "max_new_tokens": 50,
                                 "stream": True}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        uid = int(resp.getheader("X-DS-Request-Id"))
        only = fleet.owner_of(uid)
        time.sleep(0.2)
        only.proc.kill()
        only.proc.wait()

        # the stream must terminate with an in-band error, not hang
        body = resp.read()
        conn.close()
        assert b"error" in body
        # the uid is marked lost with a retry hint
        ra = fleet.lost_retry_after(uid)
        assert ra is not None and ra > 0
        code, out, hdrs = _get_json(port, f"/requests/{uid}")
        assert code == 503 and "Retry-After" in hdrs

        # the pool self-heals (backfill) and fresh submits succeed
        assert fleet.wait_ready(30, n=1)
        code, out, _ = _post_json(
            port, "/generate", {"prompt": [1], "max_new_tokens": 2})
        assert code == 200
    finally:
        srv.shutdown()
        fleet.stop()


def test_migrate_stall_falls_back_instead_of_hanging(tmp_path):
    """router.migrate_stall wedges the drain leg past the stall budget:
    migrate_from must raise MigrationFailed within the budget instead of
    pinning the control loop."""
    get_fault_injector().configure({"faults": [
        {"site": "router.migrate_stall", "nth": 1}]})
    fleet = _fleet(tmp_path, n=2, migrate_stall_s=0.3)
    try:
        victim = fleet.pick()
        t0 = time.monotonic()
        with pytest.raises(MigrationFailed, match="stall"):
            fleet.migrate_from(victim)
        assert time.monotonic() - t0 < 5.0
        assert "router.migrate_stall#1" in get_fault_injector().fired
    finally:
        fleet.stop()


def test_replica_crash_fault_site_kills_at_probe(tmp_path):
    """router.replica_crash SIGKILLs a replica from the probe loop; the
    fleet detects the death and backfills the pool."""
    fleet = _fleet(tmp_path, n=2)
    try:
        pids = {r.proc.pid for r in fleet._pool}
        get_fault_injector().configure({"faults": [
            {"site": "router.replica_crash", "nth": 1}]})
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            alive = {r.proc.pid for r in fleet.healthy()}
            if alive and not (alive <= pids):
                break  # a backfilled (new-pid) replica is healthy
            time.sleep(0.05)
        else:
            pytest.fail("crash-site kill never produced a backfill")
        assert "router.replica_crash#1" in get_fault_injector().fired
        assert fleet.wait_ready(20)
    finally:
        fleet.stop()


# ---------------------------------------------------------------------------
# autoscaling with hysteresis
# ---------------------------------------------------------------------------


def test_autoscale_up_down_with_hysteresis(tmp_path):
    """Sustained queue depth above queue_high grows the pool (to the
    max_replicas ceiling); sustained depth below queue_low shrinks it
    back to min_replicas. A single noisy sample must NOT trigger either
    direction (hysteresis)."""
    # the monitor thread probes from the start but does not evaluate until
    # the test has driven the noisy samples itself: a blip timed by
    # time.sleep() lasts as many evaluations as a loaded host makes it
    fleet = _fleet(tmp_path, n=1, min_replicas=1, max_replicas=2,
                   autoscale=False, queue_high=5.0, queue_low=1.0,
                   probe_interval=0.05, queue_eval_interval=0.05,
                   hysteresis=5, cooldown_s=0.2)
    try:
        def set_waiting(n):
            for r in fleet.healthy():
                conn = http.client.HTTPConnection("127.0.0.1", r.port,
                                                  timeout=10)
                try:
                    conn.request("POST", "/debug/set_waiting",
                                 json.dumps({"waiting": n}))
                    conn.getresponse().read()
                except (ConnectionError, http.client.HTTPException):
                    # the fleet retired this replica between the listing and
                    # the request: scaling down is what is under test
                    pass
                finally:
                    conn.close()

        def probed(pred):
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                if all(pred(r.score()) for r in fleet.healthy()):
                    return True
                time.sleep(0.02)
            return False

        def evaluate():
            fleet._t_eval = float("-inf")  # this evaluation is due
            fleet._autoscale_tick()

        # hot for one evaluation short of a trend, then cold again:
        # hysteresis must hold the pool
        set_waiting(50)
        assert probed(lambda s: s >= fleet.queue_high)
        for _ in range(fleet.hysteresis - 1):
            evaluate()
        assert fleet._hot_streak == fleet.hysteresis - 1
        set_waiting(0)
        assert probed(lambda s: s <= fleet.queue_low)
        evaluate()
        assert fleet._hot_streak == 0
        assert len(fleet._pool) == 1, "a hot blip caused a scale"
        fleet.autoscale = True  # from here the monitor evaluates, each 0.05 s

        # sustained hot -> scale up to the ceiling
        set_waiting(50)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if len(fleet.healthy()) >= 2:
                break
            set_waiting(50)  # keep new + old replicas reporting hot
            time.sleep(0.05)
        else:
            pytest.fail("sustained queue depth never scaled up")
        assert len(fleet._pool) == 2 <= fleet.max_replicas

        # sustained cold -> scale down to the floor (live migration path)
        set_waiting(0)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if len(fleet._pool) <= 1:
                break
            set_waiting(0)
            time.sleep(0.05)
        else:
            pytest.fail("idle fleet never scaled down")
        assert fleet.wait_ready(10, n=1)
    finally:
        fleet.stop()
