"""Parameters and operations of an SDAR-MoE configuration as a chip holds it
under block-diffusion training, from the file's keys, with the arithmetic
written out (what ``step.mfu_pct`` is computed from in the SDAR cell). The
benchmark's own, as ``lfm2_cost.py`` is for LFM2.

Every layer is GQA attention (q and o ``hidden x heads * head_dim``, k and v
``hidden x kv_heads * head_dim``, two norm weights of ``head_dim``) and an
MoE block: a router ``hidden x router width`` and ``num_experts`` (the
experts HELD here) times three matrices ``hidden x moe_intermediate_size``;
two norm weights a layer; the final norm; an embedding and an untied head of
``vocab_size`` rows each. At the published widths, 16 of 128 experts held,
18,992 rows, depth 6: 18,874,368 + 256 + 4,096 + 262,144 + 75,497,472 =
94,638,336 a layer, 2 x 38,895,616 + 2,048 outside them: 645,623,296.

The router's width is the published ``num_experts`` where the file's is a
share (listed in ``reduced``). A position chooses ``num_experts_per_tok`` of
the router's experts, each held here with probability ``held / width``: an
expected ``8 * 16 / 128`` = 1 expert a position and layer. That expectation,
not a run's routing, is what the utilization counts.

FLOPs are counted a DATA token, which is what ``train_tok_s`` counts: each
goes through the layers as two positions (its noised copy and its clean
copy) and through the head as one (only the noisy half carries loss).
Forward, a matmul of ``[m, k]`` by ``[k, n]`` being ``2 m k n``: a position
and layer 2 x (18,874,368 + 262,144) + 2 x 1 x 4,718,592 = 47.7M; attention
``4 * heads * head_dim`` a live (query, key) pair, of which a sequence of
``L`` data tokens in blocks of ``B`` has ``L^2 + L * B`` (own block ``L *
B``, noisy to clean ``L (L - B) / 2``, clean to clean ``L (L + B) / 2``):
``4 * 32 * 128 * (L + B)`` a data token and layer, 134.3M at L 8,192, B 4;
the head 2 x 2,048 x 18,992 = 77.8M. At depth 6: 1.456G forward, 4.368G a
data token for training. Norms, rotary, softmax, SwiGLU's elementwise part,
sort and gathers are not counted, nor is recomputation.
"""

from benchmark.lfm2_cost import router_width


def _matrices(cfg: dict) -> dict:
    h, heads, kv, d = (cfg[k] for k in ("hidden_size", "num_attention_heads",
                                        "num_key_value_heads", "head_dim"))
    return {"attention": 2 * h * heads * d + 2 * h * kv * d,
            "router": h * router_width(cfg),
            "expert": 3 * h * cfg["moe_intermediate_size"],
            "head": h * cfg["vocab_size"]}


def param_count(cfg: dict) -> int:
    h, m = cfg["hidden_size"], _matrices(cfg)
    per_layer = (m["attention"] + 2 * cfg["head_dim"] + 2 * h + m["router"]
                 + cfg["num_experts"] * m["expert"])
    return cfg["num_hidden_layers"] * per_layer + 2 * m["head"] + h


def experts_held_per_position(cfg: dict) -> float:
    return cfg["num_experts_per_tok"] * cfg["num_experts"] / router_width(cfg)


def live_pairs(seq: int, block_length: int) -> int:
    """(query, key) pairs the block-diffusion mask allows in one sequence of
    ``seq`` data tokens (``2 * seq`` positions)."""
    return seq * seq + seq * block_length


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """A data token: two positions through the layers, one through the head."""
    m = _matrices(cfg)
    position = 2 * (m["attention"] + m["router"]) \
        + 2 * experts_held_per_position(cfg) * m["expert"]
    scores = (4 * cfg["num_attention_heads"] * cfg["head_dim"]
              * live_pairs(seq, cfg["block_length"]) / seq)
    return cfg["num_hidden_layers"] * (2 * position + scores) + 2.0 * m["head"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (a gradient for the input and for the weight of
    every matmul: twice the forward). Recomputation does not count."""
    return 3.0 * forward_flops_per_token(cfg, seq)
