"""Parameters, bytes at rest and operations of Kimi-VL-A3B's language model
(``deepseek_v3`` blocks) as a chip holds it, from the file's keys, with the
arithmetic written out (what ``step.mfu_pct`` is computed from in the Kimi-VL
cell). The benchmark's own, as ``lfm2_cost.py`` and ``sdar_cost.py`` are.

Every layer is latent attention: ``q_proj`` ``hidden x heads * (nope +
rope)`` = 2048 x 3072 = 6,291,456; ``kv_a_proj_with_mqa`` ``hidden x
(kv_lora_rank + rope)`` = 2048 x 576 = 1,179,648; ``kv_a_layernorm`` 512;
``kv_b_proj`` ``kv_lora_rank x heads * (nope + v)`` = 512 x 4096 =
2,097,152; ``o_proj`` ``heads * v x hidden`` = 4,194,304: 13,763,072, and two
norm weights of ``hidden`` a layer. Its FFN: layer ``i <
first_k_dense_replace`` three matrices ``hidden x intermediate_size`` =
69,206,016 (the layer: 82,973,184); otherwise a router ``hidden x router
width`` = 131,072 with its selection bias (64), the ungated shared expert of
``n_shared_experts * moe_intermediate_size`` = 3 x 2048 x 2816 = 17,301,504
and ``n_routed_experts`` (the experts HELD here) times 3 x 2048 x 1408 =
8,650,752 (the layer at 8 held: 100,405,824). The final norm; an embedding
and an untied head of ``vocab_size`` rows each (2 x 20,480 x 2048 =
83,886,080). One dense and five expert layers: 668,890,432 parameters.

At rest the engine holds float32 masters and AdamW's two float32 moments, 12
bytes a parameter (no gradient buffer outlives a fused step since PR 39):
8.03 GB.

The router's width is the published ``n_routed_experts`` where the file's is
a share (listed in ``reduced``). A token chooses ``num_experts_per_tok`` of
the router's experts, each held here with probability ``held / width``: an
expected ``6 * 8 / 64`` = 0.75 experts a token and expert layer. That
expectation, not a run's routing, is what the utilization counts.

Forward FLOPs a token, a matmul of ``[m, k]`` by ``[k, n]`` being ``2 m k
n``: twice each matrix a token passes (attention's four 2 x 13,762,560 =
27.5M; dense FFN 138.4M; shared expert 34.6M; router 0.26M; experts held 0.75
x 17.3M = 13.0M; head 83.9M) plus, in every layer, ``2 * (d_qk + d_v) *
heads`` a live (query, key) pair for QK^T at 192 and PV at 128: ``2 * 320 *
16 * 4,096.5`` mean keys a query at 8,192 = 41.9M. Dense layer 207.9M,
expert layer 117.3M, forward 878.4M, training (a gradient for the input and
the weight of every matmul) 2.635 GFLOP a token. Norms, rotary, softmax,
SwiGLU's elementwise part, sigmoid, sort and gathers are not counted, nor is
recomputation.
"""

BYTES_AT_REST_PER_PARAM = 12    # float32 master, AdamW mu and nu


def router_width(cfg: dict) -> int:
    if "n_routed_experts" in cfg.get("reduced", ()):
        return cfg["published"]["n_routed_experts"]
    return cfg["n_routed_experts"]


def _matrices(cfg: dict) -> dict:
    """Elements of one layer's matrices by part, and the head's."""
    h, heads, rank = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return {"attention": (h * heads * (nope + rope) + h * (rank + rope)
                          + rank * heads * (nope + dv) + heads * dv * h),
            "dense": 3 * h * cfg["intermediate_size"],
            "router": h * router_width(cfg),
            "shared": 3 * h * cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "expert": 3 * h * cfg["moe_intermediate_size"],
            "head": h * cfg["vocab_size"]}


def dense_layers(cfg: dict) -> int:
    return min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])


def param_count(cfg: dict) -> int:
    h, m = cfg["hidden_size"], _matrices(cfg)
    dense = dense_layers(cfg)
    every = m["attention"] + cfg["kv_lora_rank"] + 2 * h
    expert_layer = (m["router"] + router_width(cfg) + m["shared"]
                    + cfg["n_routed_experts"] * m["expert"])
    return (cfg["num_hidden_layers"] * every + dense * m["dense"]
            + (cfg["num_hidden_layers"] - dense) * expert_layer + 2 * m["head"] + h)


def bytes_at_rest(cfg: dict) -> int:
    return BYTES_AT_REST_PER_PARAM * param_count(cfg)


def experts_held_per_token(cfg: dict) -> float:
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / router_width(cfg)


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    m = _matrices(cfg)
    mean_keys = (seq + 1) / 2.0     # causal: query i sees i + 1 keys
    pairs = (2 * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])
             * cfg["num_attention_heads"] * mean_keys)
    dense = dense_layers(cfg)
    expert_layer = 2 * (m["router"] + m["shared"]
                        + experts_held_per_token(cfg) * m["expert"])
    return (cfg["num_hidden_layers"] * (2 * m["attention"] + pairs)
            + dense * 2 * m["dense"]
            + (cfg["num_hidden_layers"] - dense) * expert_layer + 2.0 * m["head"])


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (a gradient for the input and for the weight of
    every matmul: twice the forward). Recomputation does not count."""
    return 3.0 * forward_flops_per_token(cfg, seq)
