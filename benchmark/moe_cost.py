"""Operations of the OLMoE configuration, from shapes, with the arithmetic
written out: parameters, FLOPs per token (what ``step.mfu_pct`` is computed
from in the OLMoE cell) and the least work of the grouped matmuls the device
trace shows. The benchmark's own, as ``flops.py`` is for the dense decoder.

A token passes ``num_experts_per_tok`` experts of the ``num_experts``, so the
*active* expert parameters count: per layer ``2 * (q, k, v, o)`` +
``4 * heads * d * mean keys per query`` (QK^T and PV) + ``2 * hidden *
num_experts`` (the router) + ``2 * top_k * 3 * hidden * intermediate``
(gate, up and down of each chosen expert), and ``2 * hidden * vocab`` for
the head. At the published widths and 4,096 tokens: 50.3 + 0.26 + 100.7
MFLOP a layer and 206.0 for the head. Norms, rotary, softmax, SwiGLU's
elementwise part, the sort, the gather and the scatter-add are not counted.

The grouped matmuls. A step sorts ``tokens * top_k`` (token, expert) rows by
expert and multiplies each group by its expert's matrix: three such matmuls
forward (gate, up, down), and in the backward one for the rows' gradient and
one for the weights' gradient of each, nine a layer and step. Each needs
``2 * rows * hidden * intermediate`` FLOPs whatever the split of the rows
over the experts: 5.50e11 at 131,072 rows x 2048 x 1024, 4.95e12 for all
nine. On a v5e XLA lowers ``jax.lax.ragged_dot`` to a grouped-matmul kernel
whose device events are named ``%ragged-dot-none*``; a Pallas kernel that
took its place would be named ``%moe_gmm*``. The bound is compute: an
expert's 2048 x 1024 bf16 matrix (4.2 MB) is read once for some 2,048 rows,
2,048 FLOP a weight byte against the chip's ridge of 240.
"""

import re
from typing import Optional

from benchmark import flops

GMM_PREFIXES = ("%ragged-dot", "%moe_gmm")
NOT_GMM = ("%ragged-dot-metadata", )   # the kernel's tile table, no matmul
_SHAPE = re.compile(r"\w+\[([\d,]+)\]")


def param_count(cfg: dict) -> int:
    h, f, e = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_experts"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or h // heads
    attn = 2 * h * heads * d + 2 * h * kv * d + heads * d + kv * d   # + q/k norms
    per_layer = attn + h * e + 3 * e * h * f + 2 * h
    embed = cfg["vocab_size"] * h
    head = 0 if cfg.get("tie_word_embeddings") else embed
    return cfg["num_hidden_layers"] * per_layer + embed + head + h


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or h // heads
    qkvo = 2 * h * heads * d + 2 * h * kv * d
    attn = 4 * heads * d * flops.mean_keys_per_query(seq, None)
    router = h * cfg["num_experts"]
    experts = cfg["num_experts_per_tok"] * 3 * h * f
    per_layer = 2 * (qkvo + router + experts) + attn
    return cfg["num_hidden_layers"] * per_layer + 2 * h * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (a gradient for the input and for the weight of
    every matmul: twice the forward). Recomputation does not count."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def gmm_flops(rows: int, hidden: int, intermediate: int) -> float:
    """One grouped matmul over ``rows`` (token, expert) rows."""
    return 2.0 * rows * hidden * intermediate


def call_flops(hlo: str, cfg: dict, rows: int) -> Optional[float]:
    """Least FLOPs of the grouped matmul whose device event reads ``hlo``,
    from the event's own result shape: ``[rows, hidden or intermediate]``
    (forward, or the rows' gradient) gives the rows itself; ``[experts, in,
    out]`` (the weights' gradient) shows no rows, so the step's ``rows``
    stand in. ``None`` for any other shape: the call is then not counted."""
    m = _SHAPE.search(hlo.split(" = ", 1)[-1])
    if m is None:
        return None
    dims = [int(x) for x in m.group(1).split(",")]
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    if len(dims) == 2 and dims[1] in (h, f):
        return gmm_flops(dims[0], h, f)
    if len(dims) == 3 and dims[0] == cfg["num_experts"] \
            and sorted(dims[1:]) == sorted((h, f)):
        return gmm_flops(rows, h, f)
    return None


def traced_gmm(run: dict) -> Optional[dict]:
    """The grouped-matmul calls matched in the run's device trace: their
    ``calls``, ``seconds`` and least ``flops``; ``None`` when none matched
    (a CPU rehearsal, a program with no such kernel)."""
    trace = run.get("trace")
    if not trace or "tokens_per_step" not in run:
        return None
    cfg = run["config"]
    rows = run["tokens_per_step"] * cfg["num_experts_per_tok"]
    out = {"calls": 0, "seconds": 0.0, "flops": 0.0}
    for name, k in trace.get("kernels", {}).items():
        if not name.startswith(GMM_PREFIXES) or name.startswith(NOT_GMM):
            continue
        need = call_flops(k["hlo"], cfg, rows)
        if need is None:
            continue
        out["calls"] += k["count"]
        out["seconds"] += k["seconds"]
        out["flops"] += need * k["count"]
    return out if out["seconds"] else None
