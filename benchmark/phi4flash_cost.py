"""Parameters, bytes at rest and operations of Phi-4-mini-flash's blocks
(SambaY with differential attention) as a chip holds them, from the file's
keys, with the arithmetic written out (what ``step.mfu_pct`` is computed from
in the Phi-4-mini-flash cell). The benchmark's own, as ``ling3_cost.py`` and
``granite_cost.py`` are.

Per layer, hidden 2,560, inner 5,120 (``mamba_expand`` 2), N = 16 states, rank
160 (the family's sizes where the file has no key), FFN 10,240: the SwiGLU
``3 * 2,560 * 10,240`` = 78,643,200; two LayerNorms with bias 10,240; a Mamba-1
mixer 41,241,600 (``in_proj`` 26,214,400; taps and bias 25,600; ``x_proj``
5,120 x 192 = 983,040; ``dt_proj`` 160 x 5,120 + 5,120 = 824,320; ``A_log``
81,920; ``D`` 5,120; ``out_proj`` 13,107,200); a differential self-attention
mixer 19,668,864 (``Wqkv`` 2,560 x 5,120 + 5,120; ``out_proj`` 6,553,600 +
2,560; four lambda vectors 256; ``subln`` 128); a cross-attention mixer
13,112,704 (``Wq`` and ``out_proj`` with their biases, the lambdas, ``subln``);
a GMU 2 x 2,560 x 5,120 = 26,214,400. The final norm 5,120; the tied embedding
``vocab_size`` rows of 2,560. Published layers 14-19 with an eighth of the
vocabulary: 697,094,272 parameters, 8.37 GB at 12 bytes a parameter (float32
masters and AdamW's two moments); the whole model by the same arithmetic
3,852,562,944.

Forward FLOPs a token, a matmul of ``[m, k]`` by ``[k, n]`` being ``2 m k n``:
twice each matrix a token passes (the FFN 157.3M; a Mamba mixer's four 82.3M;
self-attention 39.3M; cross 26.2M; the GMU 52.4M; the tied head 128.0M: 696.8M
matrix elements in all, 1.394 GFLOP); in an attention layer ``2 * 2 * (64 +
128)`` a live (query, key) pair and pair of heads (``diffattn_cost.py``): 7.7M
under the 512 window at 16,384 tokens, 125.8M causal. Training (a gradient for
the input and the weight of every matmul) three times that: 4.96 GFLOP a token
at 16,384. The selective scans' multiply-adds and exponentials (0.6M a token
and layer, on the vector unit), norms, convolutions, gates, softmax and
SwiGLU's elementwise part are not counted, nor is recomputation: a scan's time
shows in the utilization as time without work.
"""

from benchmark import diffattn_cost

BYTES_AT_REST_PER_PARAM = 12    # float32 master, AdamW mu and nu


def sizes(cfg: dict) -> dict:
    """The state-space sizes: the file's key, else the family's."""
    h = cfg["hidden_size"]
    return {"inner": int(cfg.get("mamba_expand", 2)) * h,
            "state": int(cfg.get("mamba_d_state", 16)),
            "conv": int(cfg.get("mamba_d_conv", 4)),
            "rank": int(cfg.get("mamba_dt_rank", -(-h // 16)))}


def _matrices(cfg: dict) -> dict:
    """Elements of one mixer's matrices by the layer's kind, the FFN's and
    the head's."""
    h, s = cfg["hidden_size"], sizes(cfg)
    d = h // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    self_attention = h * (q + 2 * kv) + q * h
    return {"mamba": (h * 2 * s["inner"] + s["inner"] * (s["rank"] + 2 * s["state"])
                      + s["rank"] * s["inner"] + s["inner"] * h),
            "sliding_attention": self_attention, "full_attention": self_attention,
            "cross_attention": h * q + q * h, "gmu": 2 * h * s["inner"],
            "ffn": 3 * h * cfg["intermediate_size"], "head": h * cfg["vocab_size"]}


def param_count(cfg: dict) -> int:
    h, s, m = cfg["hidden_size"], sizes(cfg), _matrices(cfg)
    d = h // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    lambdas = 4 * d + 2 * d
    small = {"mamba": ((s["conv"] + 1) * s["inner"] + s["inner"]
                       + s["inner"] * s["state"] + s["inner"]),
             "sliding_attention": q + 2 * kv + h + lambdas,
             "full_attention": q + 2 * kv + h + lambdas,
             "cross_attention": q + h + lambdas, "gmu": 0}
    return (sum(m[kind] + small[kind] + m["ffn"] + 4 * h for kind in cfg["layer_types"])
            + m["head"] + 2 * h)


def bytes_at_rest(cfg: dict) -> int:
    return BYTES_AT_REST_PER_PARAM * param_count(cfg)


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    m = _matrices(cfg)
    attention = sum(diffattn_cost.layer_flops(cfg, kind, 1, seq) / seq
                    for kind in cfg["layer_types"]
                    if kind in diffattn_cost.WINDOWED + diffattn_cost.CAUSAL)
    return (sum(2.0 * (m[kind] + m["ffn"]) for kind in cfg["layer_types"])
            + 2.0 * m["head"] + attention)


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (a gradient for the input and for the weight of
    every matmul: twice the forward). Recomputation does not count."""
    return 3.0 * forward_flops_per_token(cfg, seq)
