"""Least work of the learned-sparse-attention kernels (``%dsa_index*``,
``%dsa_fwd*``, ``%dsa_bwd_dq*``, ``%dsa_bwd_dkdv*``), from the shapes the
device trace itself shows and the configuration's ``sa_config``, and their
share of the roofline.

A device event is named by its HLO instruction: ``%dsa_fwd.3 = (bf16[1,4,8,
32768,128]{...}, f32[1,4,8,32768,1]{...}, ...) custom-call(...``. The first
result gives the call's shape: ``dsa_index`` its thresholds ``[rows, seq,
1]``; ``dsa_fwd`` its output and ``dsa_bwd_dq`` its dQ ``[rows, kv_heads,
group, seq, head_dim]``; ``dsa_bwd_dkdv`` its dK ``[rows, kv_heads, seq,
head_dim]`` (the query heads are then the configuration's).

- ``dsa_index``: ``2 * indexer heads * indexer width`` FLOP a CAUSAL (query,
  key) pair, of which a sequence has ``seq * (seq + 1) / 2``: every one of
  them has to be scored before any can be left out. The ReLU, the weighted
  sum of the heads and the selection are not counted.
- ``dsa_fwd``: ``4 * heads * head_dim`` FLOP a CHOSEN pair (QK^T and PV), of
  which a sequence has ``sum_t min(t + 1, topk)``; the backward twice that,
  each kernel of the pair credited by its name with its own two matmuls
  (``dsa_bwd_dq``: dP and dQ; ``dsa_bwd_dkdv``: dV and dK). The same count
  whatever implements the call: a kernel that sweeps every causal pair under
  the mask reads at most ``chosen / causal`` of its peak (an eighth at
  32,768), and the scores it makes again, the indexer's too, add time and no
  work. Or, if larger, the bytes of q, k, v and o once over the HBM peak.

A program without these kernels shows no such event, and every function here
then returns None.
"""

import re
from typing import Optional

from benchmark import ssd_cost

INDEX, FWD, BWD = "%dsa_index", "%dsa_fwd", "%dsa_bwd"
_SHAPE = re.compile(r"(\w+)\[([\d,]+)\]")
_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4}


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def chosen_pairs(seq: int, topk: int) -> int:
    """``sum_{t < seq} min(t + 1, topk)``."""
    k = min(topk, seq)
    return k * (k + 1) // 2 + (seq - k) * k


def call_cost(name: str, hlo: str, config: dict) -> Optional[dict]:
    """Least ``flops`` and ``bytes`` of one call of the kernel ``name`` whose
    event reads ``hlo``; ``None`` when the shape is not one the kernels
    write or the configuration has no ``sa_config``."""
    m = _SHAPE.search(hlo.split(" = ", 1)[-1])
    sa = config.get("sa_config")
    if not m or not sa:
        return None
    itemsize = _ITEMSIZE.get(m.group(1), 2)
    dims = [int(x) for x in m.group(2).split(",")]
    if name.startswith(INDEX):
        if len(dims) != 3:
            return None
        rows, seq = dims[0], dims[1]
        hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
        return {"flops": 2.0 * hi * di * rows * causal_pairs(seq),
                "bytes": 2.0 * rows * seq * (hi * di + di) + 4.0 * rows * seq * hi}
    if len(dims) == 5:          # [rows, kv_heads, group, seq, head_dim]
        rows, kv, heads, seq, d = dims[0], dims[1], dims[1] * dims[2], dims[3], dims[4]
    elif len(dims) == 4:        # dK: [rows, kv_heads, seq, head_dim]
        rows, kv, seq, d = dims
        heads = config["num_attention_heads"]
    else:
        return None
    # two matmuls of ``head_dim`` a chosen pair and head, by every kernel
    return {"flops": 4.0 * heads * d * rows * chosen_pairs(seq, sa["topk"]),
            "bytes": float(itemsize) * rows * seq * d * (2 * heads + 2 * kv)}


def traced(run: dict, prefixes) -> Optional[dict]:
    """The traced custom calls whose instruction name starts with one of
    ``prefixes``: ``calls``, ``seconds`` and ``least`` seconds (``ssd_cost``'s
    walk over a trace's kernels); ``None`` when none matched."""
    config = run.get("config", {})

    def least_of(hlo, peaks):
        cost = call_cost(hlo.split(" ", 1)[0], hlo, config)
        return None if cost is None else ssd_cost.least_seconds(cost, peaks)
    return ssd_cost._traced(run, prefixes, least_of)


def roofline_pct(run: dict, prefix: str) -> Optional[float]:
    return ssd_cost.roofline_pct(traced(run, (prefix, )))


def ms_per_step(run: dict, prefixes) -> Optional[float]:
    """Device time of the calls named by ``prefixes`` a traced step and chip."""
    found = traced(run, prefixes)
    if found is None or not run.get("trace_steps"):
        return None
    return 1e3 * found["seconds"] / run["device"]["count"] / run["trace_steps"]
