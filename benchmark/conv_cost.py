"""Least bytes of the gated short-convolution kernels, from the shapes the
device trace itself shows, and their share of the memory roofline.

A device event is named by its HLO instruction: ``%short_conv_fwd.3 =
bf16[4,8192,2048]{...} custom-call(...`` writes ``y [batch, seq, C]`` and has
read ``B | C | u`` ``[batch, seq, 3C]``: four values a channel and token.
``%short_conv_bwd.2 = (bf16[4,8192,6144]{...}, f32[...]) custom-call(...``
writes the gradient of ``B | C | u`` first and has read ``B | C | u`` and
``dy``: seven values. The taps and their gradient (``[L, C]``) are left out,
as are the kernel's second reads at block edges and its partial sums of the
taps' gradient: the share can only be understated. The bound is memory: 2 to
3 FLOP a byte against the chip's ridge of 240.
"""

import re
from typing import Optional

from benchmark import device

PREFIXES = ("%short_conv_fwd", "%short_conv_bwd")
_RESULT = re.compile(r"(\w+)\[([\d,]+)\]")
_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4}


def call_bytes(hlo: str) -> Optional[float]:
    """Least bytes one call of the kernel whose event reads ``hlo`` moves;
    ``None`` when the result is not one these kernels write."""
    name, _, rest = hlo.partition(" = ")
    m = _RESULT.search(rest)
    if m is None or m.group(1) not in _ITEMSIZE:
        return None
    dims = [int(x) for x in m.group(2).split(",")]
    if len(dims) != 3:
        return None
    values = dims[0] * dims[1] * dims[2]     # of the first array written
    if name.startswith("%short_conv_fwd"):   # [b, s, C]: 3C read, C written
        return 4.0 * values * _ITEMSIZE[m.group(1)]
    if name.startswith("%short_conv_bwd") and dims[2] % 3 == 0:
        # [b, s, 3C] written; 3C and C read
        return (7.0 / 3.0) * values * _ITEMSIZE[m.group(1)]
    return None


def traced_conv(run: dict) -> Optional[dict]:
    """The short-convolution calls in the run's device trace: their
    ``calls``, ``seconds`` and least ``bytes``; ``None`` when none matched
    (a CPU rehearsal, a program without the kernel)."""
    trace = run.get("trace")
    if not trace:
        return None
    out = {"calls": 0, "seconds": 0.0, "bytes": 0.0}
    for name, k in trace.get("kernels", {}).items():
        if not name.startswith(PREFIXES):
            continue
        need = call_bytes(k["hlo"])
        if need is None:
            continue
        out["calls"] += k["count"]
        out["seconds"] += k["seconds"]
        out["bytes"] += need * k["count"]
    return out if out["seconds"] else None


def roofline_pct(run: dict) -> Optional[float]:
    conv = traced_conv(run)
    if conv is None:
        return None
    peak = device.load_peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * conv["bytes"] / peak / conv["seconds"]
