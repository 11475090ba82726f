"""Least work of the flash attention kernels, from the shapes the device
trace itself shows, and their share of the roofline.

A device event is named by its HLO instruction: ``%flash_fwd.3 = (bf16[8,4,
4096,128]{...}, f32[8,4,4096,1]{...}) custom-call(...``. The kernels lay
their operands out as ``[rows * kv_heads, group, seq, d]`` (``flash_fwd``
and ``flash_dq`` write such an array first) and ``[rows * kv_heads, seq,
d]`` (``flash_dkdv`` writes dK and dV), so rows, heads, sequence and head
size are read off the event and only the window and the group size come from
the configuration.

Forward: QK^T and PV, ``2 * d`` FLOPs each per (head, query, key) pair:
``4 * heads * d * mean_keys_per_query(seq, window) * seq * rows``. Backward:
four matmuls (dP and dQ in ``flash_dq``, dV and dK in ``flash_dkdv``), so
each backward call needs at least one forward's count; the kernels' second
pass over the scores is recomputation and is not counted, so the share can
only be understated. At these shapes the bound is compute: one chip's
sequence of 4,096 tokens moves 84 MB of q, k, v and o (0.10 ms at 819 GB/s)
against 1.375e11 FLOP (0.70 ms at 197 TFLOP/s).
"""

import re
from typing import Optional

from benchmark import device, flops

_SHAPE = re.compile(r"\w+\[([\d,]+)\]")


def call_flops(hlo: str, config: dict) -> Optional[float]:
    """Least FLOPs of one call of the flash kernel whose event reads
    ``hlo``; ``None`` when the shape is not one the kernels write."""
    m = _SHAPE.search(hlo.split(" = ", 1)[-1])
    if m is None:
        return None
    dims = [int(x) for x in m.group(1).split(",")]
    group = config["num_attention_heads"] // config["num_key_value_heads"]
    if len(dims) == 4:                  # [rows * kv_heads, group, seq, d]
        bkv, g, seq, d = dims
    elif len(dims) == 3:                # [rows * kv_heads, seq, d]
        (bkv, seq, d), g = dims, group
    else:
        return None
    keys = flops.mean_keys_per_query(seq, config.get("sliding_window"))
    return 4.0 * (bkv * g) * d * keys * seq


def roofline_pct(run: dict, prefixes) -> Optional[float]:
    """Least time over measured time of the traced custom calls whose
    instruction name starts with one of ``prefixes``, in percent."""
    trace = run.get("trace")
    if not trace:
        return None
    least = seconds = 0.0
    for name, k in trace.get("kernels", {}).items():
        if not name.startswith(tuple(prefixes)):
            continue
        need = call_flops(k["hlo"], run["config"])
        if need is None:
            return None
        least += need * k["count"]
        seconds += k["seconds"]
    if not seconds:
        return None
    peak = device.load_peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * least / peak / seconds
