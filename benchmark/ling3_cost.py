"""Parameters, bytes at rest and operations of Ling-3.0-flash (``bailing_hybrid``
blocks) as a chip holds it, from the file's keys, with the arithmetic written
out (what ``step.mfu_pct`` is computed from in the Ling-3.0 cell). The
benchmark's own, as ``kimi_cost.py`` and ``keye_cost.py`` are.

A KDA mixer (hidden 2,560, 32 heads of 128, inner 4,096): ``q/k/v_proj`` 3 x
10,485,760, three depthwise conv4 3 x 16,384, the decay gate ``f_proj``
10,485,760 (one full matrix: ``no_kda_lora``), ``A_log`` 32, ``dt_bias``
4,096, ``b_proj`` 81,920, the output gate ``g_proj`` 10,485,760, ``o_norm``
128, ``o_proj`` 10,485,760: 63,049,888. An MLA mixer: ``q_proj`` 2,560 x 6,144
= 15,728,640, ``kv_a_proj_with_mqa`` 2,560 x 576 = 1,474,560,
``kv_a_layernorm`` 512, ``kv_b_proj`` 512 x 8,192 = 4,194,304, the head-wise
gate 2,560 x 32 = 81,920, ``o_proj`` 10,485,760: 31,965,696. Two norm weights
of ``hidden`` a layer. The FFN: a dense layer three matrices 2,560 x 6,144 =
47,185,920; an expert layer a router 2,560 x 512 with its bias (1,311,232),
the shared expert 3 x 2,560 x 768 = 5,898,240 and ``num_experts`` (the experts
HELD here, 8) times 5,898,240. The final norm; an embedding and an untied head
of ``vocab_size`` rows each (2 x 19,648 x 2,560 = 100,597,760). Layers as
``layer_types`` says (five KDA, one MLA), the first dense: 767,009,056
parameters, 9.20 GB at 12 bytes a parameter (float32 masters and AdamW's two
moments; no gradient buffer outlives a fused step since PR 39).

The router's width is the published ``num_experts`` where the file's is a
share (listed in ``reduced``). A token chooses 8 of the router's 512 experts,
each held here with probability 8 / 512: an expected 0.125 experts a token
and expert layer. That expectation, not a run's routing, is what the
utilization counts.

Forward FLOPs a token, a matmul of ``[m, k]`` by ``[k, n]`` being ``2 m k n``:
twice each matrix a token passes (a KDA mixer's six 2 x 63.0M = 126.0M, an MLA
mixer's 63.9M, the dense FFN 94.4M, the shared expert 11.8M, the router 2.6M,
the experts held 0.125 x 11.8M = 1.5M, the head 100.6M); in the MLA layer ``2
* (192 + 128) * 32`` a live (query, key) pair, 16,384.5 mean keys a query at
32,768: 335.6M; in a KDA layer the chunk algebra of ``kda_cost.py`` (``2 Q (3
d_k + 2 d_v) + 6 d_k d_v`` a head and token at Q = 64: 5.8M a layer).
Training (a gradient for the input and the weight of every matmul) three
times that. Norms, convolutions, gates, rotary, softmax, SwiGLU's elementwise
part, sort and gathers are not counted, nor is recomputation.
"""

from benchmark import kda_cost

BYTES_AT_REST_PER_PARAM = 12    # float32 master, AdamW mu and nu


def router_width(cfg: dict) -> int:
    if "num_experts" in cfg.get("reduced", ()):
        return cfg["published"]["num_experts"]
    return cfg["num_experts"]


def _matrices(cfg: dict) -> dict:
    """Elements of one layer's matrices by part, and the head's."""
    h, heads, rank = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    inner = heads * cfg["head_dim"]
    return {"kda": 6 * h * inner + h * heads,
            "mla": (h * heads * (nope + rope) + h * (rank + rope)
                    + rank * heads * (nope + dv) + h * heads + heads * dv * h),
            "dense": 3 * h * cfg["intermediate_size"],
            "router": h * router_width(cfg),
            "shared": (3 * h * cfg["num_shared_experts"]
                       * cfg["moe_shared_expert_intermediate_size"]),
            "expert": 3 * h * cfg["moe_intermediate_size"],
            "head": h * cfg["vocab_size"]}


def layer_kinds(cfg: dict):
    """[(mixer, ffn)] of the layers kept: "kda" | "mla", "dense" | "moe"."""
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return [(kind, "dense" if i < dense else "moe")
            for i, kind in enumerate(cfg["layer_types"])]


def param_count(cfg: dict) -> int:
    h, heads, m = cfg["hidden_size"], cfg["num_attention_heads"], _matrices(cfg)
    inner = heads * cfg["head_dim"]
    small = {"kda": 3 * cfg["short_conv_kernel_size"] * inner + heads + inner
             + cfg["head_dim"], "mla": cfg["kv_lora_rank"]}
    ffn = {"dense": m["dense"],
           "moe": (m["router"] + router_width(cfg) + m["shared"]
                   + cfg["num_experts"] * m["expert"])}
    return (sum(m[mixer] + small[mixer] + ffn[kind] + 2 * h
                for mixer, kind in layer_kinds(cfg)) + 2 * m["head"] + h)


def bytes_at_rest(cfg: dict) -> int:
    return BYTES_AT_REST_PER_PARAM * param_count(cfg)


def experts_held_per_token(cfg: dict) -> float:
    return cfg["num_experts_per_tok"] * cfg["num_experts"] / router_width(cfg)


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    m = _matrices(cfg)
    mean_keys = (seq + 1) / 2.0     # causal: query i sees i + 1 keys
    mixer = {"mla": 2 * m["mla"] + (2 * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
                                         + cfg["v_head_dim"])
                                    * cfg["num_attention_heads"] * mean_keys),
             "kda": 2 * m["kda"] + cfg["num_attention_heads"] * kda_cost.token_flops(
                 cfg["kda_chunk_size"], cfg["head_dim"], cfg["head_dim"])}
    ffn = {"dense": 2 * m["dense"],
           "moe": 2 * (m["router"] + m["shared"]
                       + experts_held_per_token(cfg) * m["expert"])}
    return (sum(mixer[kind] + ffn[f] for kind, f in layer_kinds(cfg))
            + 2.0 * m["head"])


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (a gradient for the input and for the weight of
    every matmul: twice the forward). Recomputation does not count."""
    return 3.0 * forward_flops_per_token(cfg, seq)
