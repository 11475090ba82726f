"""Parameters and operations of a Granite 4.0-H dense-hybrid configuration
as a chip holds it, from the file's keys, with the arithmetic written out
(what ``step.mfu_pct`` is computed from in the Granite cell). The benchmark's
own, as ``lfm2_cost.py`` is for LFM2.

Layer ``i`` is a mixer and the shared SwiGLU. Mixer, by ``layer_types[i]``:
``mamba``: ``in_proj`` ``hidden x (d_inner + xBC + heads)`` with ``d_inner =
mamba_n_heads * mamba_d_head`` and ``xBC = d_inner + 2 * mamba_n_groups *
mamba_d_state``, ``mamba_d_conv`` taps and a bias a channel of ``xBC``,
``dt_bias``, ``A_log`` and ``D`` a head, the gated norm's ``d_inner``,
``out_proj`` ``d_inner x hidden``; ``attention``: q and o ``hidden x heads *
d``, k and v ``hidden x kv_heads * d``. FFN: ``input_linear`` ``hidden x 2
shared_intermediate_size``, ``output_linear`` back. Two norm weights a
layer, the final norm, the embedding (the head is the same matrix).

At the published widths: a Mamba-2 mixer 25.85M, the SwiGLU 50.33M,
attention 10.49M; a Mamba layer 76.19M, an attention layer 60.82M; layers
0-9 (nine Mamba, one attention) 746.5M; 12,544 rows of the vocabulary 25.7M;
772.2M in all.

Forward FLOPs a token, a matmul of ``[m, k]`` by ``[k, n]`` being ``2 m k
n``: twice each matrix a token passes; ``4 * heads * d * mean keys a query``
for QK^T and PV in an attention layer; and in a Mamba layer the scan in
chunks of ``Q = mamba_chunk_size``: ``2 Q N`` for ``C B^T`` (shared by the
heads), and a head ``2 Q P`` for the intra-chunk product and ``4 P N`` for
the chunk's state and what the carried state adds (4.26M at the published
sizes; the recurrence token by token would be 5 P N a head, 2.6M). Norms,
the taps (``2 L`` a channel), the gates, softplus and exponentials are not
counted. At 16,384-token sequences: Mamba layer 152.2M + 4.3M, attention
layer 121.6M + 67.1M, head 51.4M: 1.649G forward, 4.95G a token for
training.
"""

from benchmark import flops


def _sizes(cfg: dict) -> dict:
    h, heads, kv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = h // heads
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    xbc = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return {"inner": inner, "xbc": xbc,
            "mamba": h * (inner + xbc + cfg["mamba_n_heads"]) + inner * h,
            "attention": 2 * h * heads * d + 2 * h * kv * d,
            "ffn": 3 * h * cfg["shared_intermediate_size"],
            "head": h * cfg["vocab_size"]}


def param_count(cfg: dict) -> int:
    h, m = cfg["hidden_size"], _sizes(cfg)
    total = m["head"] + h                   # tied embedding, final norm
    for kind in cfg["layer_types"]:
        total += 2 * h + m["ffn"]           # operator_norm, ffn_norm, SwiGLU
        if kind == "mamba":
            total += (m["mamba"] + m["inner"] + 3 * cfg["mamba_n_heads"]
                      + (cfg["mamba_d_conv"] + bool(cfg["mamba_conv_bias"])) * m["xbc"])
        else:
            total += m["attention"]
    return total


def scan_flops_per_token(cfg: dict) -> float:
    """The chunked scan's matmuls, forward, a token and Mamba layer."""
    q, n = cfg["mamba_chunk_size"], cfg["mamba_d_state"]
    return 2.0 * q * n + cfg["mamba_n_heads"] * cfg["mamba_d_head"] * (2.0 * q + 4.0 * n)


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    m = _sizes(cfg)
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    scores = 4 * heads * (h // heads) * flops.mean_keys_per_query(seq, None)
    total = 2.0 * m["head"]
    for kind in cfg["layer_types"]:
        total += 2 * m["ffn"]
        total += (2 * m["mamba"] + scan_flops_per_token(cfg) if kind == "mamba"
                  else 2 * m["attention"] + scores)
    return total


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward (a gradient for the input and for the weight of
    every matmul: twice the forward). Recomputation does not count."""
    return 3.0 * forward_flops_per_token(cfg, seq)
