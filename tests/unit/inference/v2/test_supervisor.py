"""ServingSupervisor: budgeted warm restarts + readiness gating.

CPU-safe and jax-free: the supervised "daemon" is a tiny python child
script, so these tests exercise the real subprocess lifecycle (launch,
crash, relaunch with DS_SERVE_RESTART_COUNT, budget exhaustion, SIGTERM
grace) in milliseconds.
"""

import http.server
import os
import subprocess
import sys
import textwrap
import threading
import time

import pytest

from deepspeed_tpu.inference.v2.supervisor import (ServingSupervisor,
                                                   _wait_ready)

pytestmark = pytest.mark.faults

# crashes until DS_SERVE_RESTART_COUNT reaches FAIL_UNTIL, then exits 0;
# each generation appends its restart count to a shared log file
CHILD = textwrap.dedent("""
    import os, sys
    n = int(os.environ.get("DS_SERVE_RESTART_COUNT", "0"))
    with open(sys.argv[1], "a") as f:
        f.write(f"{n}\\n")
    sys.exit(0 if n >= int(sys.argv[2]) else 7)
""")


def _run(tmp_path, fail_until, max_restarts):
    child = tmp_path / "child.py"
    child.write_text(CHILD)
    log = tmp_path / "gens.log"
    sup = ServingSupervisor(
        [sys.executable, str(child), str(log), str(fail_until)],
        max_restarts=max_restarts, monitor_interval=0.02,
        restart_backoff=0.01,
        env={**os.environ, "PYTHONPATH": ""})
    rc = sup.run()
    gens = [int(x) for x in log.read_text().split()]
    return rc, gens, sup


def test_relaunches_until_clean_exit(tmp_path):
    """Two crashes, then success: each generation sees an incremented
    DS_SERVE_RESTART_COUNT (what stats/restart_count reports), and the
    supervisor returns the clean exit."""
    rc, gens, sup = _run(tmp_path, fail_until=2, max_restarts=5)
    assert rc == 0
    assert gens == [0, 1, 2]
    assert sup.restarts == 2
    assert len(sup.history) == 3


def test_restart_budget_exhaustion_returns_last_rc(tmp_path):
    """A daemon that never comes up stops consuming restarts at the
    budget; the child's real exit code surfaces."""
    rc, gens, sup = _run(tmp_path, fail_until=99, max_restarts=2)
    assert rc == 7
    assert gens == [0, 1, 2]  # initial launch + 2 restarts, then give up
    assert sup.restarts == 3  # the 3rd failure broke the budget


def test_wait_ready_accepts_any_http_answer():
    """200 is ready; a closed port polls until timeout (False)."""

    class H(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            self.send_response(200)
            self.end_headers()
            self.wfile.write(b"{}")

        def log_message(self, *a):
            pass

    httpd = http.server.HTTPServer(("127.0.0.1", 0), H)
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        assert _wait_ready(f"http://127.0.0.1:{port}/health", timeout_s=10)
    finally:
        httpd.shutdown()
    # nothing listens here anymore → not ready, returns (not raises)
    assert not _wait_ready(f"http://127.0.0.1:{port}/health", timeout_s=0.3,
                           poll_s=0.05)


def test_wait_ready_bails_when_child_dies():
    """A child that dies before binding its port must not pin the
    supervisor for the whole ready timeout."""
    proc = subprocess.Popen([sys.executable, "-c", "import sys; sys.exit(3)"])
    t0 = time.monotonic()
    assert not _wait_ready("http://127.0.0.1:1/health", timeout_s=30,
                           proc=proc, poll_s=0.05)
    assert time.monotonic() - t0 < 10


def test_teardown_sends_sigterm_then_kills(tmp_path):
    """Supervisor teardown gives the daemon its SIGTERM-handoff window,
    escalating to SIGKILL only after the grace period."""
    child = tmp_path / "stubborn.py"
    child.write_text(textwrap.dedent("""
        import signal, time
        signal.signal(signal.SIGTERM, signal.SIG_IGN)
        time.sleep(600)
    """))
    sup = ServingSupervisor([sys.executable, str(child)], grace_s=0.2,
                            env={**os.environ, "PYTHONPATH": ""})
    proc = sup._launch()
    time.sleep(0.3)  # let it install the handler
    t0 = time.monotonic()
    sup._terminate(proc)
    assert proc.poll() is not None
    assert 0.1 < time.monotonic() - t0 < 10


def test_budget_resets_after_healthy_uptime(tmp_path):
    """A long-lived daemon must not spend its lifetime budget on unrelated
    crashes far apart: after budget_reset_after_s of healthy uptime the
    restart counter forgets old crashes. Here every generation outlives
    the reset window, so 5 sequential crashes survive a budget of 2 —
    without the reset the run would die at the 3rd launch."""
    child = tmp_path / "child.py"
    log = tmp_path / "gens.log"
    # each generation: log (restart_count, budget_remaining), stay up past
    # the reset window, then crash — until 5 generations have run
    child.write_text(textwrap.dedent("""
        import os, sys, time
        path = sys.argv[1]
        with open(path, "a") as f:
            f.write(os.environ["DS_SERVE_RESTART_COUNT"] + " "
                    + os.environ["DS_SERVE_RESTART_BUDGET_REMAINING"] + "\\n")
        n = len(open(path).read().splitlines())
        time.sleep(0.25)
        sys.exit(0 if n >= 5 else 7)
    """))
    sup = ServingSupervisor(
        [sys.executable, str(child), str(log)],
        max_restarts=2, monitor_interval=0.02, restart_backoff=0.01,
        budget_reset_after_s=0.1, backoff_jitter="none",
        env={**os.environ, "PYTHONPATH": ""})
    assert sup.run() == 0
    lines = [tuple(map(int, ln.split())) for ln in
             log.read_text().splitlines()]
    assert len(lines) == 5
    # every relaunch happened with a reset budget: restart_count 1, one
    # restart left of the 2 — never the exhaustion staircase
    assert lines[0] == (0, 2)
    assert all(ln == (1, 1) for ln in lines[1:])


def test_budget_still_exhausts_on_crash_loop(tmp_path):
    """The reset must NOT forgive a tight crash loop: generations dying
    inside the healthy-uptime window consume the budget as before."""
    rc, gens, sup = _run(tmp_path, fail_until=99, max_restarts=2)
    assert rc == 7
    assert gens == [0, 1, 2]
    assert sup.restarts == 3
    assert sup.budget_remaining == 0


def test_relaunch_backoff_full_jitter_is_seeded(tmp_path):
    """With jitter_seed set, two identically-configured supervisors pick
    the identical (bounded) jittered relaunch delays."""
    import random

    from deepspeed_tpu.utils.retry import backoff_delay

    a = ServingSupervisor(["true"], restart_backoff=0.2, max_backoff=1.0,
                          jitter_seed=3)
    b = ServingSupervisor(["true"], restart_backoff=0.2, max_backoff=1.0,
                          jitter_seed=3)
    da = [backoff_delay(i, 0.2, 1.0, jitter=a.backoff_jitter, rng=a._rng)
          for i in range(5)]
    db = [backoff_delay(i, 0.2, 1.0, jitter=b.backoff_jitter, rng=b._rng)
          for i in range(5)]
    assert da == db
    assert all(0.0 <= d <= min(1.0, 0.2 * 2 ** i) for i, d in enumerate(da))


# ---------------------------------------------------------------------------
# full-stack acceptance: SIGKILL a real daemon process mid-decode
# ---------------------------------------------------------------------------


def _free_port():
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_daemon(repo, port, env):
    return subprocess.Popen(
        [sys.executable, os.path.join(repo, "bin", "ds_serve"),
         "--durable", "--port", str(port), "--kv-blocks", "96"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


@pytest.mark.slow
def test_sigkill_mid_decode_stream_resumes_bit_identical(tmp_path):
    """The ISSUE acceptance scenario with real processes: SIGKILL the
    serving daemon while a fixed-seed sampled request is streaming; after
    a warm restart (next generation over the same journal dir) the client
    re-attaches by uid at its own offset and the concatenated stream is
    byte-identical to an uninterrupted daemon's."""
    import http.client
    import json

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))))
    env = {**os.environ,
           "JAX_PLATFORMS": "cpu",
           "DS_TPU_JOURNAL_DIR": str(tmp_path / "journal")}
    # enough decode budget that the kill reliably lands MID-decode (the
    # scheduler decodes independently of how fast the client reads)
    n_tok = 256
    body = {"prompt": list(range(40, 60)), "max_new_tokens": n_tok,
            "temperature": 0.9, "top_k": 20, "seed": 11, "stream": True}

    # uninterrupted reference from its own daemon + pristine journal dir
    ref_env = {**env, "DS_TPU_JOURNAL_DIR": str(tmp_path / "journal_ref")}
    port = _free_port()
    ref_proc = _spawn_daemon(repo, port, ref_env)
    try:
        assert _wait_ready(f"http://127.0.0.1:{port}/health", 300,
                           proc=ref_proc)
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
        conn.request("POST", "/generate", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        ref = [json.loads(l)["token"]
               for l in resp.read().decode().splitlines() if l.strip()]
        conn.close()
    finally:
        ref_proc.kill()
        ref_proc.wait()
    assert len(ref) == n_tok

    # generation 1: stream a few tokens, then SIGKILL the daemon
    port = _free_port()
    proc = _spawn_daemon(repo, port, env)
    got, uid = [], None
    try:
        assert _wait_ready(f"http://127.0.0.1:{port}/health", 300, proc=proc)
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
        conn.request("POST", "/generate", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        uid = int(resp.getheader("X-DS-Request-Id"))
        buf = b""
        while len(got) < 5:
            chunk = resp.read1(65536)
            if not chunk:
                break
            buf += chunk
            *lines, buf = buf.split(b"\n")
            got.extend(json.loads(l)["token"] for l in lines if l.strip())
        proc.kill()  # SIGKILL: no handoff, the WAL alone must carry it
        proc.wait()
        conn.close()
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    assert uid is not None and 0 < len(got) < n_tok

    # generation 2: warm restart over the same journal; re-attach by uid
    port = _free_port()
    env2 = {**env, "DS_SERVE_RESTART_COUNT": "1"}
    proc2 = _spawn_daemon(repo, port, env2)
    try:
        assert _wait_ready(f"http://127.0.0.1:{port}/health", 300,
                           proc=proc2)
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
        conn.request("GET", f"/requests/{uid}/stream?from_token={len(got)}")
        resp = conn.getresponse()
        assert resp.status == 200
        rest = [json.loads(l)["token"]
                for l in resp.read().decode().splitlines() if l.strip()]
        conn.close()

        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
        conn.request("GET", "/health")
        health = json.loads(conn.getresponse().read())
        conn.close()
        assert health["replayed_requests"] >= 1
        assert health["restart_count"] == 1
    finally:
        proc2.kill()
        proc2.wait()

    assert got + rest == ref, "resumed stream diverged from uninterrupted run"
