"""Operations and bytes from shapes, with the arithmetic written out. The
benchmark's own: utilization and roofline shares are computed from these,
never from a number the program reports."""

from typing import Iterable, Optional, Tuple


def _head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def mean_keys_per_query(seq: int, window: Optional[int]) -> float:
    """Causal attention: query ``i`` (0-based) sees ``min(i + 1, window)``
    keys. The mean over a sequence of ``seq`` tokens."""
    w = seq if not window else min(window, seq)
    # positions 0..w-1 see 1..w keys; the other seq-w positions see w each
    return (w * (w + 1) / 2 + (seq - w) * w) / seq


def forward_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward-pass FLOPs per token of a Llama/Mistral decoder at sequence
    length ``seq``. A matmul of an ``[m, k]`` by a ``[k, n]`` matrix is
    ``2 m k n`` FLOPs, so per token each weight matrix costs twice its
    elements; norms, rotary, SwiGLU's elementwise part, softmax and the
    embedding gather are not counted."""
    h, ffn = cfg["hidden_size"], cfg["intermediate_size"]
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], _head_dim(cfg)
    qkvo = h * heads * d + 2 * h * kv * d + heads * d * h
    mlp = 3 * h * ffn                       # gate, up, down
    # QK^T and PV: 2 * d FLOPs each per (head, query, key) pair
    attn = 4 * heads * d * mean_keys_per_query(seq, cfg.get("sliding_window"))
    per_layer = 2 * (qkvo + mlp) + attn
    return cfg["num_hidden_layers"] * per_layer + 2 * h * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward plus backward: the backward pass computes a gradient for the
    input and for the weight of every matmul, twice the forward's FLOPs.
    Recomputed operations (remat, the flash kernel's second pass over the
    scores) do not count."""
    return 3.0 * forward_flops_per_token(cfg, seq)


def train_state_bytes(cfg: dict, n_params: int) -> int:
    """fp32 master, two fp32 Adam moments, fp32 gradient, bf16 compute copy."""
    return (4 + 8 + 4 + 2) * n_params


def param_count(cfg: dict) -> int:
    h, ffn = cfg["hidden_size"], cfg["intermediate_size"]
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], _head_dim(cfg)
    per_layer = (h * heads * d + 2 * h * kv * d + heads * d * h
                 + 3 * h * ffn + 2 * h)     # two norm weights
    embed = cfg["vocab_size"] * h
    head = 0 if cfg.get("tie_word_embeddings") else embed
    return cfg["num_hidden_layers"] * per_layer + embed + head + h


def paged_attention_cost(cfg: dict, calls: Iterable[Tuple[int, int]],
                         page_size: int, itemsize: int = 2) -> dict:
    """FLOPs and bytes the paged attention kernel needs for one layer's call
    over sequences ``calls`` = (new tokens ``n``, tokens already cached
    ``seen``). A query at position ``p`` attends ``min(p + 1, window)`` keys:
    ``4 * heads * d`` FLOPs per (query, key) pair (QK^T and PV). Bytes: the K
    and V pages that hold attended positions are read once per sequence
    (``2 * kv_heads * d * itemsize`` per cached token, whole pages), the
    queries are read and the output written once. Which bound applies is the
    larger of FLOPs over peak FLOP/s and bytes over peak bytes/s."""
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], _head_dim(cfg)
    window = cfg.get("sliding_window")
    flops = byts = 0.0
    for n, seen in calls:
        total = seen + n
        pairs = 0
        for p in range(seen, total):        # exact, n is at most ~1k
            pairs += min(p + 1, window) if window else p + 1
        flops += 4.0 * heads * d * pairs
        first = max(0, seen + 1 - window) if window else 0
        pages = -(-total // page_size) - first // page_size
        byts += 2.0 * kv * d * itemsize * pages * page_size
        byts += 2.0 * n * heads * d * itemsize
    return {"flops": flops, "bytes": byts}


def roofline_seconds(cost: dict, peaks: dict) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_c = cost["flops"] / peaks["bf16_flops_per_s"]
    t_m = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
