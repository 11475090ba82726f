"""Two-process multi-host smoke test.

Parity target: reference ``tests/unit/launcher/`` + the multi-node
rendezvous contract (``launcher/runner.py:399`` → per-node env →
``comm/comm.py:619 init_distributed``). Here: two REAL OS processes on the
CPU backend rendezvous through ``jax.distributed.initialize`` driven
entirely by the env the launcher exports, then run a cross-process
collective — the first coverage of the multi-host code path.
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

from deepspeed_tpu.launcher.runner import build_commands

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))

CHILD = textwrap.dedent("""
    import jax
    import numpy as np
    import deepspeed_tpu.comm as dist

    ctx = dist.init_distributed()
    assert jax.process_count() == 2, jax.process_count()
    from jax.experimental import multihost_utils
    ids = multihost_utils.process_allgather(np.array([jax.process_index()]))
    assert sorted(np.asarray(ids).ravel().tolist()) == [0, 1], ids
    print("SMOKE_OK", jax.process_index(), flush=True)
""")


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_rendezvous_and_collective(tmp_path):
    script = tmp_path / "child.py"
    script.write_text(CHILD)
    port = _free_port()
    # exactly the env contract build_commands emits for each process id
    exports = {
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO,
        # keep each child at 1 local device: 2 procs x 1 device total
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
    }
    cmds = build_commands(["localhost", "localhost"], "127.0.0.1", port,
                          str(script), [], exports)
    assert len(cmds) == 2 and all(c[0] == "bash" for c in cmds)
    env = dict(os.environ)
    procs = [subprocess.Popen(c, env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True) for c in cmds]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("rendezvous hung")
        outs.append(out)
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"proc {pid} failed:\n{out[-2000:]}"
        assert f"SMOKE_OK {pid}" in out, out[-2000:]


def test_launcher_env_contract():
    """The env build_commands injects must be exactly what init_distributed
    consumes (a prefix mismatch here means multi-host never rendezvous)."""
    cmds = build_commands(["localhost", "localhost"], "10.0.0.1", 1234,
                          "t.py", [], {})
    for pid, cmd in enumerate(cmds):
        line = cmd[-1]
        assert "JAX_COORDINATOR_ADDRESS=10.0.0.1:1234" in line
        assert "JAX_NUM_PROCESSES=2" in line
        assert f"JAX_PROCESS_ID={pid}" in line
