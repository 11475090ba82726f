"""Op registry + compatibility report.

Analog of reference ``op_builder/builder.py`` (``OpBuilder.is_compatible``,
``ds_report`` CLI): ops register themselves with a name, the backend they
use on this platform ("pallas" | "xla"), and whether the fast path is
available. There is no JIT compilation of extensions — Pallas kernels compile
through XLA at trace time — so "installed" vs "compatible" collapses to one
availability probe.
"""

import functools
from typing import Callable, Dict, NamedTuple, Optional

import jax


class OpInfo(NamedTuple):
    name: str
    backend: str  # "pallas" or "xla"
    compatible: bool
    reason: str


class OpRegistry:

    def __init__(self):
        self._ops: Dict[str, OpInfo] = {}

    def register(self, name: str, backend: str, compatible: bool, reason: str = ""):
        self._ops[name] = OpInfo(name, backend, compatible, reason)

    def report(self) -> Dict[str, OpInfo]:
        return dict(self._ops)

    def __contains__(self, name):
        return name in self._ops


registry = OpRegistry()


@functools.cache
def on_tpu() -> bool:
    """Canonical is-this-a-TPU probe — EVERY fast-path gate must use this.
    Answered from the first device's platform alone. A backend that fails
    to initialise raises here: a TPU host whose chip did not come up must
    not be served from its CPU."""
    return jax.devices()[0].platform == "tpu"


# Pallas interpret mode is a numerics tool for tests on a CPU. Only
# tests/conftest.py turns it on; the package never derives it from the
# platform, so a kernel asked for where no TPU is attached fails to lower
# instead of being emulated.
INTERPRET_KERNELS = False


def interpret_kernels() -> bool:
    return INTERPRET_KERNELS


def use_pallas(force: Optional[bool] = None) -> bool:
    """Fast-path decision: pallas on real TPU; XLA elsewhere unless forced
    (tests force interpret mode)."""
    if force is not None:
        return force
    return on_tpu()


def compatible_ops():
    return [o.name for o in registry.report().values() if o.compatible]


def op_report() -> str:
    """ds_report-style compatibility matrix (reference bin/ds_report)."""
    lines = ["-" * 60, "deepspeed_tpu op compatibility report",
             f"backend: {jax.default_backend()}", "-" * 60,
             f"{'op':<30}{'impl':<10}{'compatible'}"]
    for info in registry.report().values():
        lines.append(f"{info.name:<30}{info.backend:<10}{info.compatible}"
                     + (f"  [{info.reason}]" if info.reason else ""))
    return "\n".join(lines)
