"""TPU accelerator (the concrete device seam).

Reference: ``accelerator/cuda_accelerator.py`` shape, implemented over jax:
memory stats from the PJRT allocator, synchronize as block-until-ready on a
trivial computation, "pinned" host staging as page-aligned numpy (what our
AIO layer consumes), op lookup through the op registry."""

import os
from typing import Optional

import numpy as np

from .abstract_accelerator import DeepSpeedAccelerator


class _PinnedArray(np.ndarray):
    """ndarray subclass so the aligned view can carry its base allocation."""


class TPU_Accelerator(DeepSpeedAccelerator):

    def __init__(self):
        super().__init__()
        self._name = "tpu"
        self._communication_backend_name = "xla"
        self._seed = 0

    def _jax(self):
        import jax
        return jax

    def _device(self, device_index=None):
        devs = self._jax().local_devices()
        return devs[device_index or 0]

    # ---- device ----
    def device_name(self, device_index=None):
        return "tpu" if device_index is None else f"tpu:{device_index}"

    def device_count(self):
        return self._jax().device_count()

    def current_device(self):
        return 0

    def current_device_name(self):
        plat = self._jax().default_backend()
        return f"{plat}:0"

    def is_available(self):
        # a backend that fails to initialise raises: its absence is not hidden
        return len(self._jax().devices()) > 0

    def synchronize(self, device_index=None):
        jax = self._jax()
        jax.block_until_ready(jax.device_put(np.zeros(1), self._device(device_index)))

    # ---- RNG ----
    def manual_seed(self, seed):
        self._seed = int(seed)
        return self._jax().random.PRNGKey(self._seed)

    def initial_seed(self):
        return self._seed

    # ---- memory ----
    def _stats(self, device_index=None) -> dict:
        try:
            return self._device(device_index).memory_stats() or {}
        except Exception:
            return {}

    def memory_allocated(self, device_index=None):
        return self._stats(device_index).get("bytes_in_use", 0)

    def total_memory(self, device_index=None):
        return self._stats(device_index).get("bytes_limit", 0)

    def available_memory(self, device_index=None):
        s = self._stats(device_index)
        return s.get("bytes_limit", 0) - s.get("bytes_in_use", 0)

    def memory_stats(self, device_index=None):
        return self._stats(device_index)

    def peak_bf16_flops(self, device_index=None) -> float:
        """Per-chip bf16 peak for MFU accounting, keyed on device_kind.
        Published peaks: v4 275, v5e 197, v5p 459, v6e (Trillium) 918
        TFLOP/s. A TPU whose kind is not in the table is an error, not a
        default. Table order matters: 'v5 lite' must match before the bare
        'v5' (plain 'TPU v5' is how v5p can report)."""
        dev = self._device(device_index)
        if getattr(dev, "platform", "") != "tpu":
            # no chip, no kind to key on: the ABC's default
            return super().peak_bf16_flops(device_index)
        kind = (getattr(dev, "device_kind", "") or "").lower()
        table = {"v6": 918e12, "v5p": 459e12, "v5 lite": 197e12,
                 "v5e": 197e12, "v5": 459e12, "v4": 275e12}
        for key, peak in table.items():
            if key in kind:
                return peak
        raise ValueError(f"unknown TPU device_kind {kind!r}: no published "
                         f"bf16 peak to account MFU against")

    # ---- dtypes ----
    def is_bf16_supported(self):
        return True

    def is_fp16_supported(self):
        return True  # supported, but bf16 is the native fast path

    def supported_dtypes(self):
        import jax.numpy as jnp
        return [jnp.float32, jnp.bfloat16, jnp.float16, jnp.int8]

    # ---- pinned host memory (AIO staging) ----
    def pin_memory(self, tensor, align_bytes=4096):
        """Page-aligned host copy (what O_DIRECT AIO wants)."""
        from ..ops.aio import aligned_empty  # one owner of the alignment trick
        arr = np.asarray(tensor)
        aligned = aligned_empty(arr.nbytes, align_bytes).view(
            arr.dtype).reshape(arr.shape).view(_PinnedArray)
        aligned[...] = arr
        return aligned

    def is_pinned(self, tensor):
        return isinstance(tensor, _PinnedArray) or (
            hasattr(tensor, "ctypes") and tensor.ctypes.data % 4096 == 0)

    # ---- ops ----
    def create_op_builder(self, op_name):
        return self.get_op_builder(op_name)

    def get_op_builder(self, op_name):
        from ..ops.registry import registry
        report = registry.report()
        return report.get(op_name)

    def op_report(self):
        from ..ops.registry import op_report
        return op_report()
