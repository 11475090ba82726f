"""Test harness.

Replicates the reference's in-process multi-"rank" testing
(``tests/unit/common.py:129 DistributedExec``) the TPU way: instead of forking
N processes over torch.distributed, every process that imports this file (the
pytest process, and each xdist worker) becomes an eight-device XLA CPU process
before JAX creates its backend, and tests run SPMD over a Mesh of those
devices — the same code path a real pod uses (single-controller SPMD). A
``world_size(n)`` test therefore runs under the plain tier-1 command; a count
already forced in ``XLA_FLAGS`` (a developer's, or a subprocess test's own)
wins. A test that needs one device, or a batch that eight do not divide, pins
its mesh (``MeshContext.create(..., devices=jax.devices()[:1])``).
"""

import pytest


def pytest_configure(config):
    config.addinivalue_line("markers", "world_size(n): devices required for this test")
    config.addinivalue_line("markers", "tpu: requires real TPU hardware")
    config.addinivalue_line("markers", "slow: long-running test")
    # faults: fast, CPU-only fault-injection resilience tests (torn writes,
    # SIGTERM autosave, NaN rollback). NOT excluded from the tier-1
    # selection (`-m 'not slow'`) — they run in the standard verify pass;
    # the marker exists so `-m faults` can run just the resilience suite.
    config.addinivalue_line(
        "markers", "faults: fault-injection resilience test (CPU-only, fast)")


import importlib  # noqa: E402

from deepspeed_tpu.utils.hostdev import ensure_host_devices  # noqa: E402

ensure_host_devices(8)  # before the first device query below or in a test

import jax  # noqa: E402

# the tests run on a CPU: Pallas kernels a test asks for run interpreted.
# The package itself never derives this from the platform. (import_module:
# ``deepspeed_tpu.ops.registry`` the attribute is the OpRegistry instance.)
importlib.import_module(
    "deepspeed_tpu.ops.registry").INTERPRET_KERNELS = True


@pytest.fixture(autouse=True)
def _reset_global_mesh():
    """Each test gets a fresh global mesh context."""
    yield
    from deepspeed_tpu.comm import reset_mesh_context
    reset_mesh_context()


@pytest.fixture(autouse=True)
def _reset_fault_injector():
    """The fault injector is process-global; a plan configured by one test
    must never leak into the next."""
    yield
    from deepspeed_tpu.utils.fault_injection import get_fault_injector
    get_fault_injector().reset()


@pytest.fixture(autouse=True)
def _hermetic_journal_dir(tmp_path, monkeypatch):
    """Every test resolves the serving request journal to its own tmp dir:
    a durable-serving test must never replay requests journaled by a
    previous test (or by a developer's live daemon), and no test may leave
    journal segments in the user's ~/.cache."""
    monkeypatch.setenv("DS_TPU_JOURNAL_DIR", str(tmp_path / "journal"))


@pytest.fixture
def devices():
    return jax.devices()


@pytest.fixture
def force_host_devices():
    """Env factory for SUBPROCESS tests that need their own forced
    virtual-device count: returns ``build(n, extra=...) -> env dict``
    (``utils/hostdev``'s recipe, so mesh tests and serving e2e tests do
    not hand-roll the env edits)."""
    from deepspeed_tpu.utils.hostdev import force_host_devices_env

    def _build(n: int, extra=None):
        return force_host_devices_env(n, extra=extra)

    return _build


def pytest_runtest_setup(item):
    ws_marks = list(item.iter_markers(name="world_size"))
    if ws_marks:
        n = ws_marks[0].args[0]
        if jax.device_count() < n:
            pytest.skip(f"needs {n} devices, have {jax.device_count()}")
