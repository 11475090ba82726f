"""Parameters and operations of an LFM2-MoE configuration as a chip holds it,
from the file's keys, with the arithmetic written out (what ``step.mfu_pct``
is computed from in the LFM2 cell). The benchmark's own, as ``flops.py`` is
for the dense decoder and ``moe_cost.py`` for OLMoE.

Layer ``i`` is an operator and an FFN. Operator, by ``layer_types[i]``:
``conv``: ``in_proj`` ``hidden x 3 hidden``, ``out_proj`` ``hidden x
hidden``, ``conv_L_cache`` taps a channel; ``full_attention``: q and o
``hidden x heads * d``, k and v ``hidden x kv_heads * d``, two norm weights
of ``d``. FFN: ``i < num_dense_layers``: three matrices ``hidden x
intermediate_size``; otherwise a router ``hidden x router width`` with its
selection bias and ``num_experts`` (the experts HELD here) times three
matrices ``hidden x moe_intermediate_size``. Two norm weights a layer, the
final norm, the embedding (the head is the same matrix).

The router's width is the published ``num_experts`` where the file's
``num_experts`` is a share (listed in ``reduced``). A token chooses
``num_experts_per_tok`` of the router's experts, each held here with
probability ``held / width``, so the experts held cost an expected
``top_k * held / width`` experts a token and layer: 4 * 8 / 64 = 0.5 in the
cell. That expectation, not a run's routing, is what the utilization counts.

Forward FLOPs a token, a matmul of ``[m, k]`` by ``[k, n]`` being ``2 m k
n``: twice each matrix a token passes, plus ``4 * heads * d * mean keys a
query`` for QK^T and PV in an attention layer. Norms, rotary, the gates, the
taps (``2 * L * hidden``), sigmoid, sort and gathers are not counted. At the
published widths, 8 of 64 held, 8,192-token sequences, a vocabulary of 8,192:
conv operator 33.6M, attention 21.0M + 33.6M, dense FFN 144.7M, router
0.26M, experts held 9.4M, head 33.6M; layers conv+dense, attention+MoE,
conv+MoE x 3: 405.8M forward, 1.217G a token for training.
"""

from benchmark import flops


def router_width(cfg: dict) -> int:
    if "num_experts" in cfg.get("reduced", ()):
        return cfg["published"]["num_experts"]
    return cfg["num_experts"]


def _matrices(cfg: dict) -> dict:
    """Elements of one layer's matrices by part, and the head's."""
    h, heads, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = h // heads
    return {"conv": 3 * h * h + h * h,
            "attention": 2 * h * heads * d + 2 * h * kv * d,
            "dense": 3 * h * cfg["intermediate_size"],
            "router": h * router_width(cfg),
            "expert": 3 * h * cfg["moe_intermediate_size"],
            "head": h * cfg["vocab_size"]}


def param_count(cfg: dict) -> int:
    h, m = cfg["hidden_size"], _matrices(cfg)
    d = h // cfg["num_attention_heads"]
    total = m["head"] + h                   # tied embedding, final norm
    for i, kind in enumerate(cfg["layer_types"]):
        total += 2 * h                      # operator_norm, ffn_norm
        total += (m["conv"] + cfg["conv_L_cache"] * h if kind == "conv"
                  else m["attention"] + 2 * d)
        if i < cfg["num_dense_layers"]:
            total += m["dense"]
        else:
            total += (m["router"] + cfg["num_experts"] * m["expert"]
                      + (router_width(cfg) if cfg.get("use_expert_bias") else 0))
    return total


def experts_held_per_token(cfg: dict) -> float:
    return cfg["num_experts_per_tok"] * cfg["num_experts"] / router_width(cfg)


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    m = _matrices(cfg)
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    scores = 4 * heads * (h // heads) * flops.mean_keys_per_query(seq, None)
    total = 2.0 * m["head"]
    for i, kind in enumerate(cfg["layer_types"]):
        total += 2 * m["conv"] if kind == "conv" else 2 * m["attention"] + scores
        if i < cfg["num_dense_layers"]:
            total += 2 * m["dense"]
        else:
            total += 2 * m["router"] + 2 * experts_held_per_token(cfg) * m["expert"]
    return total


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (a gradient for the input and for the weight of
    every matmul: twice the forward). Recomputation does not count."""
    return 3.0 * forward_flops_per_token(cfg, seq)
