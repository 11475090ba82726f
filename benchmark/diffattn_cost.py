"""Least work of a step's differential attention calls, from the
configuration's layers and the traced step's length, and their share of the
roofline.

Differential attention (``models/llama.py::LlamaAttention._differential``)
runs as ONE call of the two-width attention kernels a layer: the query heads
ordered ``[even | odd]`` against keys ``head_dim`` wide and the pair's values
``2 * head_dim`` wide, so in a program whose attention layers are all
differential every ``%mla_*`` event is one of them (``%mla_fwd*`` forward;
``%mla_bwd*``, or the pair ``%mla_bwd_dq*`` + ``%mla_bwd_dkdv*``, backward).
The events of a windowed and of a full layer have the same shapes, so the
work is not read off an event: it is the step's, from ``layer_types`` (a
``sliding_attention`` layer under ``sliding_window``, a ``full_attention`` or
``cross_attention`` layer causal), the sequence off the event (``[rows * kv
heads, group, seq, width]``) and the traced steps.

A live (query, key) pair of a PAIR of query heads, forward: two softmax maps,
each ``Q K^T`` at ``head_dim`` and ``P V`` at ``2 * head_dim``: ``2 * 2 *
(head_dim + 2 * head_dim)`` FLOP (768 at 64 | 128). Backward: the four
matmuls of each map (dV and dP at the values' width, dQ and dK at the keys'),
twice the forward, as ``mla_cost.py`` and ``flash_cost.py`` count theirs: the
scores the kernels make again are recomputation and are not credited (the
kernel's own five matmuls would be 2.33 forwards at these widths), so a share
can only be understated. Live pairs a sequence: ``seq * mean keys a query``
(``flops.mean_keys_per_query``: 504 under the 512 window at 16,384 tokens,
8,192.5 causal: 1 to 16.3, where whole key blocks stand 32 to 1). The least
time is the larger of those FLOPs over the bf16 peak and the bytes of q, k, v
and the pairs' output (their gradients too in the backward) over the HBM
bandwidth: compute at these sizes (a windowed layer forward: 127 GFLOP, 0.64
ms, against 0.25 GB, 0.31 ms).

``by_call`` gives the forward calls apart, ordered by their time: the
windowed layer's is the shortest.
"""

import re
from typing import Optional

from benchmark import device, flops

FWD, BWD = "%mla_fwd", "%mla_bwd"
ALL = "%mla_"
_SHAPE = re.compile(r"\w+\[([\d,]+)\]")
WINDOWED, CAUSAL = ("sliding_attention", ), ("full_attention", "cross_attention")


def _events(run: dict, prefix: str):
    trace = run.get("trace") or {}
    return {name: k for name, k in trace.get("kernels", {}).items()
            if name.startswith(prefix)}


def _rows_and_seq(events: dict, config: dict):
    """(rows, seq) off a forward or fused-backward event's first result,
    ``[rows * kv heads, group, seq, width]``; None when no event shows it."""
    for k in events.values():
        m = _SHAPE.search(k["hlo"].split(" = ", 1)[-1])
        dims = [int(x) for x in m.group(1).split(",")] if m else []
        if len(dims) == 4:
            return dims[0] // int(config["num_key_value_heads"]), dims[2]
    return None


def layer_flops(config: dict, kind: str, rows: int, seq: int) -> float:
    """Forward FLOPs of one differential attention layer of ``kind``."""
    d = int(config["hidden_size"]) // int(config["num_attention_heads"])
    pairs = int(config["num_attention_heads"]) // 2
    window = int(config["sliding_window"]) if kind in WINDOWED else None
    live = rows * seq * flops.mean_keys_per_query(seq, window)
    return 2.0 * 2.0 * (d + 2 * d) * pairs * live


def layer_bytes(config: dict, rows: int, seq: int, itemsize: int = 2) -> float:
    """Forward bytes of one layer's q, k, v and output, whatever its mask."""
    heads, kv = int(config["num_attention_heads"]), int(config["num_key_value_heads"])
    d = int(config["hidden_size"]) // heads
    return float(itemsize * rows * seq * d * (2 * heads + 2 * kv))


def step_least_seconds(run: dict, backward: bool) -> Optional[float]:
    """Least seconds of one step's differential attention, forward or
    backward, over its layers; None where the trace shows no such call or
    the configuration names no such layer."""
    config = run.get("config", {})
    kinds = [k for k in config.get("layer_types", ()) if k in WINDOWED + CAUSAL]
    found = _rows_and_seq(_events(run, FWD), config) if kinds else None
    if not found:
        return None
    peaks = device.load_peaks(run["device"]["kind"])
    times = 2.0 if backward else 1.0
    return sum(max(times * layer_flops(config, kind, *found) / peaks["bf16_flops_per_s"],
                   times * layer_bytes(config, *found) / peaks["hbm_bytes_per_s"])
               for kind in kinds)


def roofline_pct(run: dict, prefix: str) -> Optional[float]:
    """Least time of the traced steps' calls under ``prefix`` over the time
    the device trace gives them, in percent."""
    least = step_least_seconds(run, backward=prefix == BWD)
    seconds = sum(k["seconds"] for k in _events(run, prefix).values())
    if least is None or not seconds or not run.get("trace_steps"):
        return None
    return 100.0 * least * run["trace_steps"] / seconds


def kernel_ms_per_step(run: dict) -> Optional[float]:
    """Device time of every ``%mla_*`` call a traced step."""
    seconds = sum(k["seconds"] for k in _events(run, ALL).values())
    if not seconds or not run.get("trace_steps"):
        return None
    return 1e3 * seconds / run["trace_steps"]


def by_call(run: dict, prefix: str = FWD) -> list:
    """[(instruction, milliseconds a call)] of the calls under ``prefix``,
    the shortest first: the windowed layer's, then the causal ones."""
    return sorted(((name, 1e3 * k["seconds"] / k["count"])
                   for name, k in _events(run, prefix).items()), key=lambda kv: kv[1])
