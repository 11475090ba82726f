"""Parameters, bytes at rest and operations of Ouro (``model_type: ouro``, a
looped language model) as a chip holds it, from the file's keys, with the
arithmetic written out (what ``step.mfu_pct`` is computed from in the Ouro
cell). The benchmark's own, as ``qwen3next_cost.py`` is.

A layer (hidden 2,048; 16 heads of 128, as many key heads; SwiGLU 5,632 wide):
q, k, v and o ``4 x 2,048 x 2,048`` = 16,777,216, gate, up and down ``3 x 2,048
x 5,632`` = 34,603,008: 51,380,224 in matrices, and four norm weights of
``hidden``: 51,388,416. The final norm 2,048, the exit gate 2,048 and its bias,
an embedding and an untied head of ``vocab_size`` rows each (2 x 49,152 x
2,048 = 201,326,592). Eight layers: 612,438,017 parameters (the published 48:
2,667,974,657), 7.35 GB at 12 bytes a parameter (float32 masters and AdamW's
two moments; no gradient buffer outlives a fused step since PR 39). A
parameter is counted ONCE however many passes read it.

Forward FLOPs a token, a matmul of ``[m, k]`` by ``[k, n]`` being ``2 m k n``:
the stack runs ``T = total_ut_steps`` times, so a token passes each layer's
matrices T times, ``2 x T x N x 51,380,224``; in each of the ``T x N``
applications ``2 * 2 * 128 * 16`` a live (query, key) pair, ``(S + 1) / 2``
mean keys a query; the head reads every pass's stream, ``2 x T x 100,663,296``;
the gate ``2 x (T - 1) x 2,048``. At T 4, N 8 and S 16,384: 3.288e9 + 2.148e9
+ 0.805e9 = 6.241e9 (52.7%, 34.4%, 12.9%). Training (a gradient for the input
and the weight of every matmul) three times that: 18.7 GFLOP a token. Norms,
rotary, softmax, SwiGLU's elementwise part, the exit distribution and its
entropy are not counted, nor is recomputation.
"""

BYTES_AT_REST_PER_PARAM = 12    # float32 master, AdamW mu and nu


def _head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_matrices(cfg: dict) -> int:
    """Elements of one layer's matrices."""
    h, d = cfg["hidden_size"], _head_dim(cfg)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (h * heads * d + 2 * h * kv * d + heads * d * h
            + 3 * h * cfg["intermediate_size"])


def param_count(cfg: dict) -> int:
    h = cfg["hidden_size"]
    head = h * cfg["vocab_size"]
    return (cfg["num_hidden_layers"] * (layer_matrices(cfg) + 4 * h)
            + h                                     # the one final norm
            + h + 1                                 # the exit gate and its bias
            + head + (0 if cfg.get("tie_word_embeddings") else head))


def bytes_at_rest(cfg: dict) -> int:
    return BYTES_AT_REST_PER_PARAM * param_count(cfg)


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    passes, depth = cfg["total_ut_steps"], cfg["num_hidden_layers"]
    mean_keys = (seq + 1) / 2.0     # causal: query i sees i + 1 keys
    pairs = 4 * _head_dim(cfg) * cfg["num_attention_heads"] * mean_keys
    return (passes * depth * (2 * layer_matrices(cfg) + pairs)
            + passes * 2 * cfg["hidden_size"] * cfg["vocab_size"]
            + (passes - 1) * 2 * cfg["hidden_size"])


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (a gradient for the input and for the weight of
    every matmul: twice the forward). Recomputation does not count."""
    return 3.0 * forward_flops_per_token(cfg, seq)
