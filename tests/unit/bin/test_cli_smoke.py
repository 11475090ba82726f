"""bin/ CLI smoke tests (reference exposes deepspeed/ds/ds_report/ds_bench/
ds_elastic as user-facing entry points; each must run end-to-end from a
shell, not just import)."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))


def _run(args, timeout=240, extra_env=None):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
                "XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    env.update(extra_env or {})
    return subprocess.run([sys.executable, os.path.join(REPO, "bin", args[0])]
                          + args[1:], env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_ds_report_lists_every_registered_op():
    r = _run(["ds_report"])
    assert r.returncode == 0, r.stderr[-1500:]
    for op in ("flash_attention", "fused_adam", "quantizer_int8",
               "quantizer_fp6", "aio", "paged_attention"):
        assert op in r.stdout, f"{op} missing from ds_report:\n{r.stdout}"
    assert "OKAY" in r.stdout


def test_ds_elastic_prints_valid_worlds(tmp_path):
    cfg = {"elasticity": {"enabled": True, "max_train_batch_size": 32,
                          "micro_batch_sizes": [1, 2, 4], "min_gpus": 1,
                          "max_gpus": 8, "version": 0.1}}
    p = tmp_path / "ds.json"
    p.write_text(json.dumps(cfg))
    r = _run(["ds_elastic", "-c", str(p)])
    assert r.returncode == 0, r.stderr[-1500:]
    assert "global batch" in r.stdout and "valid chip counts" in r.stdout
    r2 = _run(["ds_elastic", "-c", str(p), "-w", "8"])
    assert r2.returncode == 0 and "micro batch" in r2.stdout


def test_ds_bench_runs_collective_sweep():
    r = _run(["ds_bench", "--op", "all_reduce", "--maxsize", "16",
              "--trials", "1"], timeout=300)
    assert r.returncode == 0, (r.stdout[-800:], r.stderr[-1500:])
    assert "busbw" in r.stdout and "latency" in r.stdout
    # measured rows exist with positive latency; busbw is printed rounded to
    # 2dp and can legitimately show 0.00 on a heavily loaded CI box, so only
    # require it non-negative
    rows = [l.split() for l in r.stdout.splitlines()
            if l.strip() and l.split()[0].isdigit()]
    assert rows and all(float(r_[1]) > 0 for r_ in rows)
    assert all(float(r_[2]) >= 0 for r_ in rows)


def test_deepspeed_launcher_runs_local_script(tmp_path):
    """bin/deepspeed single-node path: launches the script as a local process
    with the rendezvous env set (reference bin/deepspeed semantics)."""
    script = tmp_path / "train_stub.py"
    script.write_text(
        "import os, json\n"
        "print(json.dumps({k: os.environ.get(k) for k in\n"
        "      ('RANK', 'WORLD_SIZE', 'MASTER_ADDR')}))\n")
    hostfile = tmp_path / "hostfile"  # hermetic: never read /job/hostfile
    hostfile.write_text("localhost slots=1\n")
    r = _run(["deepspeed", "-H", str(hostfile), str(script)])
    assert r.returncode == 0, r.stderr[-1500:]
    envs = json.loads([l for l in r.stdout.splitlines() if l.startswith("{")][-1])
    assert envs["RANK"] == "0" and envs["WORLD_SIZE"] == "1"
    assert envs["MASTER_ADDR"]


def test_deepspeed_launcher_dry_run_multinode(tmp_path):
    hostfile = tmp_path / "hostfile"
    hostfile.write_text("worker-1 slots=1\nworker-2 slots=1\n")
    r = _run(["deepspeed", "-H", str(hostfile), "--dry_run", "train.py"])
    assert r.returncode == 0, r.stderr[-1500:]
    assert "worker-1" in r.stdout and "worker-2" in r.stdout
    assert "ssh" in r.stdout


def test_ds_and_dsr_are_launcher_aliases():
    for cli in ("ds", "dsr"):
        r = _run([cli, "--help"])
        assert r.returncode == 0 and "hostfile" in r.stdout.lower(), cli


def test_ds_ssh_fans_out_with_stub_ssh(tmp_path):
    """ds_ssh runs the command on every hostfile host; a PATH-stubbed ssh
    records the invocations (no network in CI)."""
    hostfile = tmp_path / "hostfile"
    hostfile.write_text("nodeA slots=1\nnodeB slots=1\n")
    stub_dir = tmp_path / "stub"
    stub_dir.mkdir()
    log = tmp_path / "ssh.log"
    stub = stub_dir / "ssh"
    stub.write_text(f"#!/bin/sh\necho \"$@\" >> {log}\n")
    stub.chmod(0o755)
    r = _run(["ds_ssh", "-f", str(hostfile), "uptime"], timeout=120,
             extra_env={"PATH": f"{stub_dir}:{os.environ.get('PATH', '')}"})
    assert r.returncode == 0, r.stderr[-1500:]
    logged = log.read_text()
    assert "nodeA" in logged and "nodeB" in logged and "uptime" in logged


def test_ds_nvme_bench_small_run(tmp_path):
    r = _run(["ds_nvme_bench", "--size_gb", "0.01",
              "--path", str(tmp_path / "scratch.bin"), "--iters", "1"])
    assert r.returncode == 0, r.stderr[-1500:]
    line = [l for l in r.stdout.splitlines() if l.startswith("{")][-1]
    doc = json.loads(line)
    assert doc["metric"] == "nvme_to_hbm_read"
    assert doc["pipelined_gbps"] > 0 and doc["serial_gbps"] > 0


def test_launcher_own_hostname_is_local_and_env_unconditional(tmp_path):
    """A one-line hostfile naming THIS machine execs locally (no ssh-to-self),
    and stale RANK/WORLD_SIZE from the calling shell are overwritten."""
    import socket
    script = tmp_path / "stub.py"
    script.write_text(
        "import os, json\n"
        "print(json.dumps([os.environ['RANK'], os.environ['WORLD_SIZE']]))\n")
    hostfile = tmp_path / "hostfile"
    hostfile.write_text(f"{socket.gethostname()} slots=1\n")
    r = _run(["deepspeed", "-H", str(hostfile), str(script)],
             extra_env={"RANK": "2", "WORLD_SIZE": "4"})  # stale shell env
    assert r.returncode == 0, r.stderr[-1500:]
    line = [l for l in r.stdout.splitlines() if l.startswith("[")][-1]
    assert json.loads(line) == ["0", "1"]
