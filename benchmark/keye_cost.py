"""Parameters, bytes at rest and operations of Keye-VL-2.0-30B-A3B's language
model as a chip holds it, from the file's keys, with the arithmetic written
out (what ``step.mfu_pct`` is computed from in the Keye-VL cell). The
benchmark's own, as ``sdar_cost.py`` and ``kimi_cost.py`` are.

Every layer is alike. Attention: q and o ``hidden x heads * head_dim`` = 2 x
2048 x 4096, k and v ``hidden x kv_heads * head_dim`` = 2 x 2048 x 512:
18,874,368; two per-head norms of 128. Its indexer (``sa_config``): ``hidden
x 16 * 64`` = 2,097,152 for qI, ``hidden x 64`` = 131,072 for the one key,
its LayerNorm 2 x 64, ``hidden x 16`` = 32,768 for the head weights:
2,261,120. Two norm weights of ``hidden``. The FFN: a router ``hidden x
router width`` = 262,144 and ``num_experts`` (the experts HELD here) times 3
x 2048 x 768 = 4,718,592. At 16 held: 96,899,456 a layer. The final norm; an
embedding and an untied head of ``vocab_size`` rows each (2 x 18,992 x 2048 =
77,791,232). Six layers: 659,190,016 parameters, 7.91 GB at 12 bytes
(float32 masters and AdamW's two moments).

Forward FLOPs a token at ``seq`` positions, a matmul of ``[m, k]`` by ``[k,
n]`` being ``2 m k n``: twice each matrix a token passes (attention 37.7M,
the indexer's three 4.5M, router 0.5M, experts held ``8 * 16 / 128`` = 1 a
token: 9.4M, head 77.8M); the indexer's scores ``2 * 16 * 64`` a CAUSAL
pair, of which a token has ``(seq + 1) / 2`` (16,384.5 at 32,768: 33.6M);
attention ``4 * heads * head_dim`` a CHOSEN pair, of which a token has
``sum_t min(t + 1, topk) / seq`` (1,984.0: 32.5M), whatever computes them: a
kernel that sweeps every causal pair under a mask is credited with the
chosen ones. A layer 118.2M, forward 787M. Training: a gradient for the
input and the weight of every matmul but the indexer's, which the choice
gives none (forward only): ``3 x 558M + 229M`` = 1.90 GFLOP a token. Norms,
rotary, softmax, ReLU and the 16-way weighted sum of the indexer's heads,
the selection, SwiGLU's elementwise part, sort and gathers are not counted,
nor is recomputation.
"""

from benchmark import dsa_cost
from benchmark.lfm2_cost import router_width

BYTES_AT_REST_PER_PARAM = 12    # float32 master, AdamW mu and nu


def _matrices(cfg: dict) -> dict:
    h, heads, kv, d = (cfg[k] for k in ("hidden_size", "num_attention_heads",
                                        "num_key_value_heads", "head_dim"))
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return {"attention": 2 * h * heads * d + 2 * h * kv * d,
            "indexer": h * hi * di + h * di + h * hi,
            "router": h * router_width(cfg),
            "expert": 3 * h * cfg["moe_intermediate_size"],
            "head": h * cfg["vocab_size"]}


def param_count(cfg: dict) -> int:
    h, m = cfg["hidden_size"], _matrices(cfg)
    per_layer = (m["attention"] + 2 * cfg["head_dim"] + m["indexer"]
                 + 2 * cfg["sa_config"]["indexer_head_dim"] + 2 * h + m["router"]
                 + cfg["num_experts"] * m["expert"])
    return cfg["num_hidden_layers"] * per_layer + 2 * m["head"] + h


def bytes_at_rest(cfg: dict) -> int:
    return BYTES_AT_REST_PER_PARAM * param_count(cfg)


def experts_held_per_token(cfg: dict) -> float:
    return cfg["num_experts_per_tok"] * cfg["num_experts"] / router_width(cfg)


def forward_flops_per_token(cfg: dict, seq: int) -> dict:
    """-> the forward's FLOPs a token in two parts: ``indexer`` (its three
    matrices and its scores over the causal pairs: forward only in
    training) and ``rest``."""
    m, sa, layers = _matrices(cfg), cfg["sa_config"], cfg["num_hidden_layers"]
    scores = (2 * sa["indexer_num_heads"] * sa["indexer_head_dim"]
              * dsa_cost.causal_pairs(seq) / seq)
    attended = (4 * cfg["num_attention_heads"] * cfg["head_dim"]
                * dsa_cost.chosen_pairs(seq, sa["topk"]) / seq)
    layer = (2 * (m["attention"] + m["router"])
             + 2 * experts_held_per_token(cfg) * m["expert"] + attended)
    return {"indexer": layers * (2 * m["indexer"] + scores),
            "rest": layers * layer + 2.0 * m["head"]}


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (twice the forward) of all but the indexer,
    which runs forward only. Recomputation does not count."""
    f = forward_flops_per_token(cfg, seq)
    return 3.0 * f["rest"] + f["indexer"]
