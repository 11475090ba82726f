"""Forced host-device environments for multi-device tests and benches.

JAX pins its backend at first import, so a process that wants N virtual
CPU devices (``--xla_force_host_platform_device_count``) must set the
environment BEFORE the interpreter imports jax — i.e. in a subprocess.
Every mesh test / TP bench used to hand-roll the same env edits; this is
the one canonical builder.
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
