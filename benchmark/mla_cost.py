"""Least work of the latent-attention kernels, from the shapes the device
trace itself shows and the configuration's head widths, and their share of
the roofline.

A device event is named by its HLO instruction: ``%mla_fwd.3 = (bf16[64,1,
8192,128]{...}, f32[64,1,8192,1]{...}) custom-call(...``. Every kernel's
first result is laid out ``[rows * kv_heads, group, seq, width]`` (the
forward's output at ``v_head_dim``, ``mla_bwd_dq``'s dQ at the q/k width,
``mla_bwd`` / ``mla_bwd_dkdv``'s dK as ``[rows * kv_heads, seq, width]``),
so rows, heads and the sequence are read off the event; the two widths come
from the configuration (``qk_nope_head_dim + qk_rope_head_dim``,
``v_head_dim``): ONE width off a result would be wrong either way (128 off
the forward's output, 192 off dQ, where a pair's work is ``2 * (192 +
128)``), which is why ``flash_cost.py`` does not read these calls.

A live (head, query, key) pair under the causal mask, of which a sequence has
``seq * (seq + 1) / 2``: forward QK^T at ``d_qk`` and PV at ``d_v``, ``2 *
(d_qk + d_v)`` FLOP; backward dV and dP at ``d_v``, dQ and dK at ``d_qk``,
``4 * (d_qk + d_v)``. The work is counted BY THE KERNEL'S NAME: ``mla_bwd``
all four matmuls, ``mla_bwd_dq`` dP and dQ (``2 * (d_qk + d_v)``),
``mla_bwd_dkdv`` dV, dP and dK less dP credited to dq (``2 * (d_qk +
d_v)``): a pair together ``4 * (d_qk + d_v)``. The recomputed scores of a
backward and a recomputed forward's calls add time and no work, so a share
can only be understated. The bound is compute: a sequence of 8,192 positions
and 16 heads moves 168 MB of q, k, v and o (0.2 ms at 819 GB/s) against
3.44e11 FLOP forward (1.75 ms at 197 TFLOP/s). A program without these
kernels shows no such event, and every function here then returns None.
"""

import re
from typing import Optional

from benchmark import ssd_cost

FWD, BWD = "%mla_fwd", "%mla_bwd"
# matmuls of width d_qk + d_v a live pair, by the kernel's name (longest first)
_PAIR_MATMULS = (("%mla_bwd_dkdv", 2), ("%mla_bwd_dq", 2), ("%mla_bwd", 4),
                 ("%mla_fwd", 2))
_SHAPE = re.compile(r"\w+\[([\d,]+)\]")


def call_flops(name: str, hlo: str, config: dict) -> Optional[float]:
    """Least FLOPs of one call of the kernel ``name`` whose event reads
    ``hlo``; ``None`` when the shape is not one the kernels write or the
    configuration has no latent widths."""
    m = _SHAPE.search(hlo.split(" = ", 1)[-1])
    dims = [int(x) for x in m.group(1).split(",")] if m else []
    try:
        widths = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
                  + config["v_head_dim"])
    except KeyError:
        return None
    if len(dims) == 4:          # [rows * kv_heads, group, seq, width]
        heads, seq = dims[0] * dims[1], dims[2]
    elif len(dims) == 3:        # dK: [rows * kv_heads, seq, width], group 1
        heads, seq = dims[0], dims[1]
    else:
        return None
    matmuls = next(n for prefix, n in _PAIR_MATMULS if name.startswith(prefix))
    return float(matmuls) * widths * heads * (seq * (seq + 1) // 2)


def traced(run: dict, prefixes) -> Optional[dict]:
    """The traced custom calls whose instruction name starts with one of
    ``prefixes``: their ``calls``, ``seconds`` and ``least`` seconds at the
    bf16 peak (``ssd_cost``'s walk over a trace's kernels, shared as it is: an
    event's text begins with its instruction's name); ``None`` when none
    matched (a CPU rehearsal, a program without the kernels)."""
    config = run.get("config", {})

    def least_of(hlo, peaks):
        need = call_flops(hlo.split(" ", 1)[0], hlo, config)
        return None if need is None else need / peaks["bf16_flops_per_s"]
    return ssd_cost._traced(run, prefixes, least_of)


def roofline_pct(run: dict, prefix: str) -> Optional[float]:
    """Least time over measured time of those calls, in percent."""
    return ssd_cost.roofline_pct(traced(run, (prefix, )))


def kernel_ms_per_step(run: dict) -> Optional[float]:
    """Device time of every ``%mla_*`` call a traced step and chip."""
    found = traced(run, (FWD, BWD))
    if found is None or not run.get("trace_steps"):
        return None
    return 1e3 * found["seconds"] / run["device"]["count"] / run["trace_steps"]
