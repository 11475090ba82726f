"""Least work of the block-diffusion attention kernels, from the shapes the
device trace itself shows, and their share of the roofline.

A device event is named by its HLO instruction: ``%bdattn_fwd.3 = (bf16[8,2,
8,8192,128]{...}, f32[8,2,8,8192,1]{...}) custom-call(...``. Both kernels
write first an array laid out ``[rows * kv_heads, 2 copies, group, L, d]``
(the forward its output, the backward dQ), so rows, heads, the ``L`` data
tokens of a sequence and the head size are read off the event; only the
block length comes from the configuration (``block_length``).

Forward: QK^T and PV, ``2 * d`` FLOPs each per live (head, query, key) pair,
of which the mask leaves ``L^2 + L * B`` a sequence and head (``sdar_cost.
live_pairs``): ``4 * heads * d * (L^2 + L * B) * rows``. Backward: four
matmuls (dV, dP, dK, dQ) = twice that; the kernel's second pass over the
scores is recomputation and is not counted, so the share can only be
understated. The bound is compute: a sequence of 2 x 8,192 positions moves
0.3 GB of q, k, v and o (0.4 ms at 819 GB/s) against 1.1e12 FLOP (5.6 ms at
197 TFLOP/s). A program without these kernels shows no such event, and
every function here then returns None.
"""

import re
from typing import Optional

from benchmark import device
from benchmark.sdar_cost import live_pairs

FWD, BWD = "%bdattn_fwd", "%bdattn_bwd"
_SHAPE = re.compile(r"\w+\[([\d,]+)\]")


def call_flops(hlo: str, config: dict, backward: bool) -> Optional[float]:
    """Least FLOPs of one call of the kernel whose event reads ``hlo``;
    ``None`` when the shape is not the one the kernels write."""
    m = _SHAPE.search(hlo.split(" = ", 1)[-1])
    dims = [int(x) for x in m.group(1).split(",")] if m else []
    if len(dims) != 5 or dims[1] != 2 or "block_length" not in config:
        return None
    bkv, _, group, seq, d = dims
    forward = 4.0 * (bkv * group) * d * live_pairs(seq, int(config["block_length"]))
    return 2.0 * forward if backward else forward


def traced(run: dict, prefixes) -> Optional[dict]:
    """The traced custom calls whose instruction name starts with one of
    ``prefixes``: their ``calls``, ``seconds`` and least ``flops``; ``None``
    when none matched (a CPU rehearsal, a program without the kernels)."""
    trace = run.get("trace")
    if not trace:
        return None
    out = {"calls": 0, "seconds": 0.0, "flops": 0.0}
    for name, k in trace.get("kernels", {}).items():
        if not name.startswith(tuple(prefixes)):
            continue
        need = call_flops(k["hlo"], run.get("config", {}), name.startswith(BWD))
        if need is None:
            return None
        out["calls"] += k["count"]
        out["seconds"] += k["seconds"]
        out["flops"] += need * k["count"]
    return out if out["seconds"] else None


def roofline_pct(run: dict, prefix: str) -> Optional[float]:
    """Least time over measured time of those calls, in percent."""
    found = traced(run, (prefix, ))
    if found is None:
        return None
    peak = device.load_peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * found["flops"] / peak / found["seconds"]


def kernel_ms_per_step(run: dict) -> Optional[float]:
    """Device time of every ``%bdattn_*`` call a traced step and chip, a
    recomputed forward included."""
    found = traced(run, (FWD, BWD))
    if found is None or not run.get("trace_steps"):
        return None
    return 1e3 * found["seconds"] / run["device"]["count"] / run["trace_steps"]
