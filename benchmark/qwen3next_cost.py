"""Parameters, bytes at rest and operations of Qwen3-Next (``qwen3_next``
blocks) as a chip holds it, from the file's keys, with the arithmetic written
out (what ``step.mfu_pct`` is computed from in the Qwen3-Next cell). The
benchmark's own, as ``ling3_cost.py`` is.

A Gated DeltaNet mixer (hidden 2,048; 16 key heads and 32 value heads of 128):
``in_proj_qkvz`` 2,048 x 12,288 = 25,165,824, ``in_proj_ba`` 2,048 x 64 =
131,072, one depthwise conv4 over 8,192 channels 32,768, ``A_log`` and
``dt_bias`` 32 each, the output norm 128, ``out_proj`` 4,096 x 2,048 =
8,388,608: 33,718,464. A gated attention mixer (16 query and 2 key heads of
256): ``q_proj`` 2,048 x 8,192 (queries and gates) = 16,777,216, ``k_proj``
and ``v_proj`` 2 x 1,048,576, ``o_proj`` 8,388,608, ``q_norm`` and ``k_norm``
256 each: 27,263,488. Two norm weights of ``hidden`` a layer. An expert block:
a router 2,048 x 512 = 1,048,576, the shared expert 3 x 2,048 x 512 =
3,145,728 and its gate 2,048, and ``num_experts`` (the experts HELD here, 32)
times 3,145,728: 104,859,648. The final norm; an embedding and an untied head
of ``vocab_size`` rows each (2 x 18,992 x 2,048 = 77,791,232). Three GDN
layers and one of attention: 625,667,136 parameters, 7.51 GB at 12 bytes a
parameter (float32 masters and AdamW's two moments; no gradient buffer
outlives a fused step since PR 39).

The router's width is the published ``num_experts`` where the file's is a
share (listed in ``reduced``). A token chooses 10 of the router's 512 experts,
each held here with probability 32 / 512: an expected 0.625 experts a token
and layer. A run that gives ``moe_rows_per_step`` is counted by the rows its
experts held in fact (``flops.py`` takes the runner's
``train_flops_per_token``; the runner passes the measured share).

Forward FLOPs a token, a matmul of ``[m, k]`` by ``[k, n]`` being ``2 m k n``:
twice each matrix a token passes (a GDN mixer's three 67.4M, an attention
mixer's 54.5M, the router 2.1M, the shared expert and its gate 6.3M, the
experts held 0.625 x 6.3M = 3.9M, the head 77.8M); in the attention layer ``2
* 2 * 256 * 16`` a live (query, key) pair, 16,384.5 mean keys a query at
32,768: 268.4M; in a GDN layer the chunk algebra of ``gdn_cost.py`` (5.2M a
layer at Q = 64). Training (a gradient for the input and the weight of every
matmul) three times that. Norms, convolutions, gates, rotary, softmax,
SwiGLU's elementwise part, sort and gathers are not counted, nor is
recomputation.
"""

from benchmark import gdn_cost

BYTES_AT_REST_PER_PARAM = 12    # float32 master, AdamW mu and nu


def router_width(cfg: dict) -> int:
    if "num_experts" in cfg.get("reduced", ()):
        return cfg["published"]["num_experts"]
    return cfg["num_experts"]


def layer_kinds(cfg: dict):
    """"gdn" | "attention" of the layers kept, in order."""
    every = int(cfg["full_attention_interval"])
    return ["attention" if (i + 1) % every == 0 else "gdn"
            for i in range(cfg["num_hidden_layers"])]


def _matrices(cfg: dict) -> dict:
    """Elements of one layer's matrices by part, and the head's."""
    h, heads, kv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                        cfg["num_key_value_heads"], cfg["head_dim"])
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    values = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    return {"gdn": (h * (2 * keys + 2 * values) + h * 2 * cfg["linear_num_value_heads"]
                    + values * h),
            "attention": h * heads * hd * 2 + 2 * h * kv * hd + heads * hd * h,
            "router": h * router_width(cfg),
            "shared": 3 * h * cfg["shared_expert_intermediate_size"] + h,
            "expert": 3 * h * cfg["moe_intermediate_size"],
            "head": h * cfg["vocab_size"]}


def param_count(cfg: dict) -> int:
    h, m = cfg["hidden_size"], _matrices(cfg)
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    values = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    small = {"gdn": (cfg["linear_conv_kernel_dim"] * (2 * keys + values)
                     + 2 * cfg["linear_num_value_heads"] + cfg["linear_value_head_dim"]),
             "attention": 2 * cfg["head_dim"]}
    moe = m["router"] + m["shared"] + cfg["num_experts"] * m["expert"]
    return (sum(m[kind] + small[kind] + moe + 2 * h for kind in layer_kinds(cfg))
            + 2 * m["head"] + h)


def bytes_at_rest(cfg: dict) -> int:
    return BYTES_AT_REST_PER_PARAM * param_count(cfg)


def experts_held_per_token(cfg: dict) -> float:
    return cfg["num_experts_per_tok"] * cfg["num_experts"] / router_width(cfg)


def forward_flops_per_token(cfg: dict, seq: int, held_per_token=None) -> float:
    """``held_per_token``: the experts held a token and layer passed in fact
    (a run's ``moe_rows_per_step`` over its tokens); the expectation without."""
    m = _matrices(cfg)
    mean_keys = (seq + 1) / 2.0     # causal: query i sees i + 1 keys
    held = experts_held_per_token(cfg) if held_per_token is None else held_per_token
    mixer = {"attention": 2 * m["attention"] + (4 * cfg["head_dim"]
                                                * cfg["num_attention_heads"] * mean_keys),
             "gdn": 2 * m["gdn"] + gdn_cost.token_flops(
                 cfg["gdn_chunk_size"], cfg["linear_num_key_heads"],
                 cfg["linear_num_value_heads"], cfg["linear_key_head_dim"],
                 cfg["linear_value_head_dim"])}
    moe = 2 * (m["router"] + m["shared"] + held * m["expert"])
    return sum(mixer[kind] + moe for kind in layer_kinds(cfg)) + 2.0 * m["head"]


def train_flops_per_token(cfg: dict, seq: int, held_per_token=None) -> float:
    """Forward plus backward (a gradient for the input and for the weight of
    every matmul: twice the forward). Recomputation does not count."""
    return 3.0 * forward_flops_per_token(cfg, seq, held_per_token)
