"""Forced host-device environments for multi-device tests.

JAX reads ``XLA_FLAGS`` when it creates its backend, so a process that
wants N virtual CPU devices (``--xla_force_host_platform_device_count``)
must have the flag in its environment before its first device query: a
subprocess gets an environment built by ``force_host_devices_env``; a
process that has not touched a device yet (``tests/conftest.py``) calls
``ensure_host_devices``. This module owns the flag's spelling.
"""

import os
from typing import Dict, Optional

_FORCE_FLAG = "--xla_force_host_platform_device_count"


def force_host_devices_env(n: int,
                           base_env: Optional[Dict[str, str]] = None,
                           extra: Optional[Dict[str, str]] = None
                           ) -> Dict[str, str]:
    """Subprocess environment exposing ``n`` virtual CPU devices.

    Pins ``JAX_PLATFORMS=cpu``, forces the host device count (replacing
    any prior force flag in ``XLA_FLAGS``), and disables x64. ``base_env``
    defaults to ``os.environ``; ``extra`` entries are merged last (callers
    add PYTHONPATH etc.).
    """
    env = dict(os.environ if base_env is None else base_env)
    env["JAX_PLATFORMS"] = "cpu"
    kept = [f for f in env.get("XLA_FLAGS", "").split()
            if not f.startswith(_FORCE_FLAG)]
    env["XLA_FLAGS"] = " ".join([f"{_FORCE_FLAG}={int(n)}"] + kept)
    env["JAX_ENABLE_X64"] = "0"
    if extra:
        env.update(extra)
    return env


def ensure_host_devices(n: int) -> None:
    """Make THIS process a CPU process of ``n`` virtual devices, unless
    its ``XLA_FLAGS`` already force a count (a developer's, or a parent
    test's for its subprocess: theirs wins). Only has an effect before
    JAX creates its backend."""
    flags = os.environ.get("XLA_FLAGS", "").split()
    if not any(f.startswith(_FORCE_FLAG) for f in flags):
        os.environ["XLA_FLAGS"] = " ".join(flags + [f"{_FORCE_FLAG}={int(n)}"])
