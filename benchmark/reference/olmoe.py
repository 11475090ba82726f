"""Plain reference of the OLMoE decoder's forward pass, loss and routing.

Straightforward ``jax.numpy`` in float32 with the matmul precision at
"highest" (on a TPU a float32 matmul otherwise runs in bf16 passes). No
kernels, no sort, no grouped matmul, and nothing imported from the program
under test. It follows arXiv:2409.02060 and ``transformers``'
``OlmoeForCausalLM``: pre-norm layers; RMSNorm; q and k RMS-normalised over
the flat ``[heads * head_dim]`` projection before the split into heads;
rotary embedding in the half-split layout; causal multi-head attention; a
router ``softmax(x W_g)`` in float32 over all experts, the ``top_k`` largest
kept with their softmax mass as it is (``norm_topk_prob`` false: not
renormalised); each expert ``down(silu(gate(x)) * up(x))`` over the rows
routed to it; an untied head; token-mean cross-entropy with the shift by one,
plus ``router_aux_loss_coef * E * sum_e frac_e * meanprob_e`` summed over
layers (``frac_e``: expert ``e``'s share of the ``tokens * top_k``
assignments; ``meanprob_e``: its mean router probability over the tokens).

Departures, each stated:
- attention runs in blocks of queries, and the head one sequence at a time,
  so neither a 4,096 x 4,096 score matrix per head nor the ``[tokens,
  50304]`` logits need exist whole;
- an expert's rows are a fixed-length list (``capacity``, by default every
  token: an expert is chosen at most once a token) padded with an
  out-of-range row, which reads as zeros and is dropped when the results are
  added back, so that shapes do not depend on the routing;
- the balance term is the Switch form above, per layer and summed, as the
  program computes it; ``transformers`` concatenates the layers and counts
  each of the ``top_k`` slots apart. No router z-loss (the published recipe
  has one; the program does not implement it either).

Weights come as the tree the program holds (``{"model": {"embed_tokens":
{"embedding"}, "layers_<i>": {"input_layernorm", "self_attn": {q_proj,
k_proj, v_proj, o_proj, q_norm, k_norm}, "post_attention_layernorm",
"block_sparse_moe": {"gate": {"kernel"}, "w1", "w3", "w2"}}, "norm",
"lm_head": {"kernel"}}}``; kernels ``[in, out]``, experts stacked ``[E, in,
out]``, ``w1`` the gate, ``w3`` the up and ``w2`` the down projection).
"""

import functools

import jax
import jax.numpy as jnp

# the dense decoder's plain pieces, shared as they are: the float32 matmul at
# "highest", RMSNorm, half-split rotary, causal attention in query blocks
# (no window and as many KV heads as query heads here)
from benchmark.reference.mistral import _attend, _mm, _rope, rms_norm


@functools.partial(jax.jit, static_argnames=("top_k", "renormalize", "capacity"))
def moe_block(h, moe, top_k: int, renormalize: bool = False, capacity=None):
    """h: [tokens, hidden] float32 -> (output [tokens, hidden], balance
    ``E * sum_e frac_e * meanprob_e``, per-expert assignment counts [E],
    margin [tokens]: how far, in router logits, each token's last chosen
    expert lies above its first rejected one)."""
    T, E = h.shape[0], moe["w1"].shape[0]
    probs = jax.nn.softmax(_mm(h, moe["gate"]["kernel"]), axis=-1)   # [T, E]
    top_w, top_i = jax.lax.top_k(probs, min(top_k + 1, E))
    margin = (jnp.log(top_w[:, top_k - 1]) - jnp.log(top_w[:, top_k])
              if top_k < E else jnp.full((T, ), jnp.inf))
    top_w, top_i = top_w[:, :top_k], top_i[:, :top_k]
    if renormalize:
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    chosen = top_i[:, :, None] == jnp.arange(E)                       # [T, k, E]
    weight = jnp.sum(top_w[:, :, None] * chosen, axis=1)              # [T, E]
    routed = jnp.any(chosen, axis=1)                                  # [T, E]
    counts = jnp.sum(routed, axis=0, dtype=jnp.int32)
    balance = E * jnp.sum(counts / (T * top_k) * jnp.mean(probs, axis=0))

    def one_expert(out, e):
        # the expert's own rows; the filler row T reads as zeros, adds nowhere
        rows, = jnp.nonzero(routed[:, e], size=capacity or T, fill_value=T)
        x = jnp.take(h, rows, axis=0, mode="fill", fill_value=0.0)
        y = _mm(jax.nn.silu(_mm(x, moe["w1"][e])) * _mm(x, moe["w3"][e]),
                moe["w2"][e])
        y = y * jnp.take(weight[:, e], rows, mode="fill", fill_value=0.0)[:, None]
        return out.at[rows].add(y, mode="drop"), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(E))
    return out, balance, counts, margin


def hidden_states(params, ids, cfg: dict, q_block: int = 512, capacity=None):
    """-> (final-norm hidden states ``[batch, seq, hidden]`` float32, the
    layers' balance terms ``[layers]``, their assignment counts ``[layers,
    E]``, each token's smallest routing margin over the layers ``[batch,
    seq]``)."""
    m = params["model"]
    H = cfg["num_attention_heads"]
    if cfg["num_key_value_heads"] != H:
        raise ValueError("the OLMoE reference is multi-head attention only")
    d = cfg.get("head_dim") or cfg["hidden_size"] // H
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    b, s = ids.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = jnp.take(m["embed_tokens"]["embedding"], ids, axis=0).astype(jnp.float32)
    balances, counts, margins = [], [], []
    for i in range(cfg["num_hidden_layers"]):
        lp = m[f"layers_{i}"]
        a = lp["self_attn"]
        h = rms_norm(x, lp["input_layernorm"]["weight"], eps)
        q = rms_norm(_mm(h, a["q_proj"]["kernel"]), a["q_norm"]["weight"], eps)
        k = rms_norm(_mm(h, a["k_proj"]["kernel"]), a["k_norm"]["weight"], eps)
        q = _rope(q.reshape(b, s, H, d), positions, theta)
        k = _rope(k.reshape(b, s, H, d), positions, theta)
        v = _mm(h, a["v_proj"]["kernel"]).reshape(b, s, H, d)
        x = x + _mm(_attend(q, k, v, None, q_block), a["o_proj"]["kernel"])
        h = rms_norm(x, lp["post_attention_layernorm"]["weight"], eps)
        out, balance, count, margin = moe_block(
            h.reshape(b * s, -1), lp["block_sparse_moe"],
            int(cfg["num_experts_per_tok"]), bool(cfg.get("norm_topk_prob")),
            capacity)
        x = x + out.reshape(b, s, -1)
        balances.append(balance)
        counts.append(count)
        margins.append(margin.reshape(b, s))
    return (rms_norm(x, m["norm"]["weight"], eps), jnp.stack(balances),
            jnp.stack(counts), jnp.min(jnp.stack(margins), axis=0))


def logits_and_margin(params, ids, cfg: dict, last: int = 0):
    """Logits ``[batch, positions, vocab]`` of the last ``last`` positions
    (all of them when 0) and those positions' routing margins."""
    x, _, _, margin = hidden_states(params, ids, cfg)
    if last:
        x, margin = x[:, -last:], margin[:, -last:]
    return _mm(x, params["model"]["lm_head"]["kernel"]), margin


def logits(params, ids, cfg: dict, last: int = 0):
    return logits_and_margin(params, ids, cfg, last)[0]


def loss_parts(params, ids, cfg: dict) -> dict:
    """One forward pass: ``ce`` (token-mean next-token loss: position ``t``
    predicts ``ids[t + 1]``), ``aux`` (the balance terms summed over layers
    times ``router_aux_loss_coef``) and ``counts`` ``[E]`` (assignments an
    expert received, summed over layers)."""
    x, balances, counts, _ = hidden_states(params, ids, cfg)
    head = params["model"]["lm_head"]["kernel"]
    nll = []
    for row in range(ids.shape[0]):     # one sequence's logits at a time
        lg = _mm(x[row, :-1], head)
        gold = jnp.take_along_axis(lg, ids[row, 1:, None], axis=-1)[:, 0]
        nll.append(jax.nn.logsumexp(lg, axis=-1) - gold)
    return {"ce": jnp.mean(jnp.stack(nll)),
            "aux": float(cfg.get("router_aux_loss_coef", 0.0)) * jnp.sum(balances),
            "counts": jnp.sum(counts, axis=0)}


def cross_entropy(params, ids, cfg: dict) -> jax.Array:
    """The training loss: cross-entropy plus the router's balance term."""
    parts = loss_parts(params, ids, cfg)
    return parts["ce"] + parts["aux"]


def expert_counts(params, ids, cfg: dict) -> jax.Array:
    return loss_parts(params, ids, cfg)["counts"]
