"""Plain reference of the LFM2-MoE decoder: forward pass, loss and routing.

Straightforward ``jax.numpy`` in float32 with the matmul precision at
"highest". No kernels, no sort, no grouped matmul, and nothing imported from
the program under test; ``jax.grad`` of :func:`cross_entropy` gives the
step's gradients, and :func:`step_parts` gives them with the loss, the
counts and the last positions' logits in one pass at the timed sizes. It follows ``transformers``' ``modeling_lfm2_moe.py`` and
the published ``config.json``. Layer ``i``:

    r = x + op_i(rms(x, operator_norm));   out = r + ffn_i(rms(r, ffn_norm))

- ``op_i``, ``layer_types[i] == "conv"``: ``B, C, u = split3(h W_in)``;
  ``v = B * u``; ``c[t] = sum_j w[j] * v[t - (L - 1) + j]`` (depthwise,
  causal, ``L = conv_L_cache`` taps, zeros left of the sequence, no bias);
  ``y = (C * c) W_out``. No activation.
- ``op_i``, ``"full_attention"``: grouped-query attention; q and k
  RMS-normalised over each head's ``head_dim`` with one weight ``[head_dim]``
  shared by the heads; rotary embedding in the half-split layout over the
  whole head; causal softmax at ``1 / sqrt(head_dim)``; ``out_proj``.
- ``ffn_i``, ``i < num_dense_layers``: ``W2(silu(W1 h) * W3 h)``.
- ``ffn_i`` otherwise: ``s = sigmoid(h W_g)`` over the router's width in
  float32; chosen = the ``top_k`` largest of ``s + expert_bias``;
  ``p = s[chosen]``; ``p <- p / (sum p + 1e-6)`` (``norm_topk_prob``);
  ``p <- p * routed_scaling_factor``; ``out = sum_{e chosen} p_e *
  expert_e(h)``, each expert a SwiGLU as above.
- a final RMSNorm; the head is the embedding transposed; token-mean
  cross-entropy with the shift by one.

The chip's share. The router's width is the gate's, the experts held are
those whose matrices the tree has (``w1.shape[0]``), ``first_expert`` says
which of the router's they are. A chosen expert that is not held adds
nothing: that partial sum is what goes on to the next layer, here as in the
program. With all experts held this is the uncut model. A sliced vocabulary
is a smaller vocabulary: the embedding has that many rows.

Departures, each stated:
- attention runs in blocks of queries and the whole model one sequence at a
  time, so that at 8,192 tokens neither a score matrix of a whole sequence
  nor the dense FFN's ``[tokens, 11776]`` activations of a whole batch exist;
  each block of queries, each expert and each layer is a ``jax.checkpoint``,
  which changes no value: a backward pass recomputes them and never holds every block's
  scores (8.6 GB a sequence at 8,192 tokens);
- an expert's rows are a fixed-length list (``capacity``, by default every
  token of the sequence: an expert is chosen at most once a token) padded
  with an out-of-range row, which reads as zeros and is dropped when the
  results are added back, so that shapes do not depend on the routing;
- ``expert_bias`` is a constant here as in the program: the rule that moves
  it during training is the recipe's, not the config's, and is not built.

Weights come as the tree the program holds (``{"model": {"embed_tokens":
{"embedding"}, "layers_<i>": {"operator_norm", "ffn_norm", "conv": {in_proj,
out_proj, conv_weight [L, C]} or "self_attn": {q_proj, k_proj, v_proj,
o_proj, q_norm, k_norm}, "mlp": {gate_proj, up_proj, down_proj} or
"block_sparse_moe": {"gate": {"kernel"}, "expert_bias", "w1", "w3", "w2"}},
"norm"}}``; kernels ``[in, out]``, experts stacked ``[held, in, out]``,
``w1`` the gate, ``w3`` the up and ``w2`` the down projection).
"""

import functools
import json

import jax
import jax.numpy as jnp

import numpy as np

# the dense decoder's plain pieces, shared as they are: the float32 matmul at
# "highest", RMSNorm, half-split rotary
from benchmark.reference.mistral import _HI, _mm, _rope, rms_norm

RENORM_EPS = 1e-6    # in modeling_lfm2_moe.py, not a config key


def attend(q, k, v, q_block: int):
    """q: [b, s, H, d], k/v: [b, s, KV, d] -> [b, s, H*d]: causal softmax
    attention, grouped queries, one block of ``q_block`` queries after another
    (``lax.map``: a backward pass holds one block's scores; one block where
    ``q_block`` does not divide the sequence)."""
    b, s, H, d = q.shape
    if s % q_block:
        q_block = s
    k = jnp.repeat(k, H // k.shape[2], axis=2)
    v = jnp.repeat(v, H // v.shape[2], axis=2)

    @jax.checkpoint
    def block(args):
        qb, first = args
        seen = jnp.arange(s)[None, :] <= first + jnp.arange(q_block)[:, None]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=_HI) / jnp.sqrt(
            jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=_HI)

    blocks = q.reshape(b, s // q_block, q_block, H, d).swapaxes(0, 1)
    outs = jax.lax.map(block, (blocks, jnp.arange(0, s, q_block)))
    return outs.swapaxes(0, 1).reshape(b, s, H * d)


def short_conv(h, conv):
    """h: [batch, seq, hidden] float32 -> the gated short convolution."""
    gate_b, gate_c, u = jnp.split(_mm(h, conv["in_proj"]["kernel"]), 3, axis=-1)
    w = conv["conv_weight"].astype(jnp.float32)              # [L, C]
    taps, seq = w.shape[0], h.shape[1]
    v = jnp.pad(gate_b * u, ((0, 0), (taps - 1, 0), (0, 0)))
    c = sum(w[j] * v[:, j:j + seq] for j in range(taps))
    return _mm(gate_c * c, conv["out_proj"]["kernel"])


def swiglu(h, w1, w3, w2):
    return _mm(jax.nn.silu(_mm(h, w1)) * _mm(h, w3), w2)


def route(h, moe, top_k: int, renormalize: bool, scaling: float,
          weigh_biased: bool = False):
    """-> (chosen experts ``[T, k]``, their weights ``[T, k]``, margin
    ``[T]``: the ``top_k``-th less the next of ``s + bias``). ``weigh_biased``
    is the WRONG router the cell's comparison has to tell from this one: it
    weights the chosen experts by ``s + bias`` and not by ``s`` (a bool or a
    traced scalar: one compiled pass serves both routers)."""
    s = jax.nn.sigmoid(_mm(h, moe["gate"]["kernel"]))               # [T, E]
    biased = s + moe["expert_bias"].astype(jnp.float32) \
        if "expert_bias" in moe else s
    top, chosen = jax.lax.top_k(biased, min(top_k + 1, s.shape[1]))
    margin = (top[:, top_k - 1] - top[:, top_k] if top_k < s.shape[1]
              else jnp.full(h.shape[:1], jnp.inf))
    chosen = chosen[:, :top_k]
    p = jnp.take_along_axis(jnp.where(weigh_biased, biased, s), chosen, axis=1)
    if renormalize:
        p = p / (jnp.sum(p, axis=1, keepdims=True) + RENORM_EPS)
    return chosen, p * scaling, margin


@functools.partial(jax.jit, static_argnames=("top_k", "renormalize", "scaling",
                                             "first_expert", "capacity"))
def moe_block(h, moe, top_k: int, renormalize: bool = True, scaling: float = 1.0,
              first_expert: int = 0, capacity=None, weigh_biased: bool = False):
    """h: [tokens, hidden] float32 -> (what the experts held give ``[tokens,
    hidden]``, per-expert assignment counts over the router's width ``[E]``,
    margin ``[tokens]``)."""
    T, E = h.shape[0], moe["gate"]["kernel"].shape[1]
    chosen, p, margin = route(h, moe, top_k, renormalize, scaling, weigh_biased)
    picked = chosen[:, :, None] == jnp.arange(E)                      # [T, k, E]
    weight = jnp.sum(p[:, :, None] * picked, axis=1)                  # [T, E]
    routed = jnp.any(picked, axis=1)                                  # [T, E]

    @jax.checkpoint
    def one_expert(out, j):
        # the expert's own rows; the filler row T reads as zeros, adds nowhere
        e = first_expert + j
        rows, = jnp.nonzero(routed[:, e], size=capacity or T, fill_value=T)
        x = jnp.take(h, rows, axis=0, mode="fill", fill_value=0.0)
        y = swiglu(x, moe["w1"][j], moe["w3"][j], moe["w2"][j])
        y = y * jnp.take(weight[:, e], rows, mode="fill", fill_value=0.0)[:, None]
        return out.at[rows].add(y, mode="drop"), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          jnp.arange(moe["w1"].shape[0]))
    return out, jnp.sum(routed, axis=0, dtype=jnp.int32), margin


def _layer(x, lp, weigh_biased, i: int, cfg: dict, first_expert: int, q_block: int,
           capacity):
    """Layer ``i`` on one sequence ``x [1, seq, hidden]`` -> (the stream after
    it, the router's counts ``[E]`` and margins ``[seq]``, or None for a
    dense layer)."""
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // H
    eps = float(cfg["norm_eps"])
    b, s, _ = x.shape
    h = rms_norm(x, lp["operator_norm"]["weight"], eps)
    if cfg["layer_types"][i] == "conv":
        x = x + short_conv(h, lp["conv"])
    else:
        a = lp["self_attn"]
        theta = float(cfg["rope_parameters"]["rope_theta"])
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        q = rms_norm(_mm(h, a["q_proj"]["kernel"]).reshape(b, s, H, d),
                     a["q_norm"]["weight"], eps)
        k = rms_norm(_mm(h, a["k_proj"]["kernel"]).reshape(b, s, KV, d),
                     a["k_norm"]["weight"], eps)
        v = _mm(h, a["v_proj"]["kernel"]).reshape(b, s, KV, d)
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
        x = x + _mm(attend(q, k, v, q_block), a["o_proj"]["kernel"])
    h = rms_norm(x, lp["ffn_norm"]["weight"], eps)
    if i < cfg["num_dense_layers"]:
        f = lp["mlp"]
        return x + swiglu(h, f["gate_proj"]["kernel"], f["up_proj"]["kernel"],
                          f["down_proj"]["kernel"]), None, None
    out, count, margin = moe_block(
        h.reshape(b * s, -1), lp["block_sparse_moe"],
        int(cfg["num_experts_per_tok"]), bool(cfg["norm_topk_prob"]),
        float(cfg["routed_scaling_factor"]), first_expert, capacity, weigh_biased)
    return x + out.reshape(b, s, -1), count, margin


def _sequence(params, ids, cfg: dict, first_expert: int, q_block: int, capacity,
              weigh_biased: bool = False):
    """One sequence ``ids [1, seq]`` -> (final-norm hidden states ``[1, seq,
    hidden]``, counts ``[expert layers, E]``, least margin ``[seq]``)."""
    m = params["model"]
    x = jnp.take(m["embed_tokens"]["embedding"], ids, axis=0).astype(jnp.float32)
    counts, margins = [], []
    for i in range(len(cfg["layer_types"])):
        layer = jax.checkpoint(functools.partial(
            _layer, i=i, cfg=cfg, first_expert=first_expert, q_block=q_block,
            capacity=capacity))
        x, count, margin = layer(x, m[f"layers_{i}"], jnp.asarray(weigh_biased))
        if count is not None:
            counts.append(count)
            margins.append(margin)
    return (rms_norm(x, m["norm"]["weight"], float(cfg["norm_eps"])),
            jnp.stack(counts), jnp.min(jnp.stack(margins), axis=0))


def hidden_states(params, ids, cfg: dict, first_expert: int = 0,
                  q_block: int = 512, capacity=None):
    """-> (final-norm hidden states ``[batch, seq, hidden]`` float32, the
    expert layers' assignment counts over the router's width ``[layers,
    E]``, each position's least routing margin over those layers ``[batch,
    seq]``). One sequence at a time."""
    parts = [_sequence(params, ids[r:r + 1], cfg, first_expert, q_block, capacity)
             for r in range(ids.shape[0])]
    return (jnp.concatenate([p[0] for p in parts]),
            sum(p[1] for p in parts), jnp.stack([p[2] for p in parts]))


def logits_and_margin(params, ids, cfg: dict, last: int = 0,
                      first_expert: int = 0):
    """Logits ``[batch, positions, vocab]`` of the last ``last`` positions
    (all of them when 0) and those positions' routing margins."""
    x, _, margin = hidden_states(params, ids, cfg, first_expert)
    if last:
        x, margin = x[:, -last:], margin[:, -last:]
    return _mm(x, params["model"]["embed_tokens"]["embedding"].T), margin


def _sequence_nll(params, ids, weigh_biased, cfg: dict, first_expert: int, last: int):
    """One sequence ``ids [1, seq]`` -> (the sum of its next-token losses,
    (counts ``[layers, E]``, the last positions' logits ``[last, vocab]`` and
    routing margins ``[last]``))."""
    x, counts, margin = _sequence(params, ids, cfg, first_expert, 512, None,
                                  weigh_biased)
    lg = _mm(x[0], params["model"]["embed_tokens"]["embedding"].T)
    gold = jnp.take_along_axis(lg[:-1], ids[0, 1:, None], axis=-1)[:, 0]
    nll = jnp.sum(jax.nn.logsumexp(lg[:-1], axis=-1) - gold)
    return nll, (counts, lg[-last:], margin[-last:])


def loss_parts(params, ids, cfg: dict, first_expert: int = 0) -> dict:
    """One forward pass: ``ce`` (token-mean next-token loss: position ``t``
    predicts ``ids[t + 1]``), ``counts`` ``[E]`` (assignments over the
    router's width, summed over the expert layers) and ``rows_held`` (those
    of them sent to the experts held)."""
    nll, counts = 0.0, 0
    for row in range(ids.shape[0]):     # one sequence's logits at a time
        part, (count, _, _) = _sequence_nll(params, ids[row:row + 1], False, cfg,
                                            first_expert, 1)
        nll, counts = nll + part, counts + jnp.sum(count, axis=0)
    held = _experts_held(params)
    return {"ce": nll / (ids.shape[0] * (ids.shape[1] - 1)), "counts": counts,
            "rows_held": jnp.sum(counts[first_expert:first_expert + held])}


@functools.lru_cache(maxsize=None)
def _compiled_pass(cfg_json: str, first_expert: int, last: int, gradients: bool):
    fn = functools.partial(_sequence_nll, cfg=json.loads(cfg_json),
                           first_expert=first_expert, last=last)
    return jax.jit(jax.value_and_grad(fn, has_aux=True) if gradients else fn)


def step_parts(params, ids, cfg: dict, last: int, first_expert: int = 0,
               weigh_biased: bool = False, gradients: bool = True) -> dict:
    """What one training step on ``ids [batch, seq]`` has to reproduce, one
    sequence at a time and each a single compiled pass: ``loss_parts``' keys,
    ``grads`` (``jax.grad`` of ``ce``, the tree as numpy float32 summed on the
    host so that one sequence's gradients are on the device at a time; None
    without ``gradients``), ``logits`` ``[batch, last, vocab]`` and ``margin``
    ``[batch, last]`` of each sequence's last ``last`` positions."""
    keys = sorted(k for k in cfg if k not in ("published", "assumed", "rehearse"))
    fn = _compiled_pass(json.dumps({k: cfg[k] for k in keys}), first_expert, last,
                        gradients)
    tokens = ids.shape[0] * (ids.shape[1] - 1)
    nll, counts, grads, logits, margins = 0.0, 0, None, [], []
    for row in range(ids.shape[0]):
        out = fn(params, ids[row:row + 1], weigh_biased)
        (part, (count, lg, margin)), grad = out if gradients else (out, None)
        nll, counts = nll + float(part), counts + np.asarray(jnp.sum(count, axis=0))
        logits.append(np.asarray(lg))
        margins.append(np.asarray(margin))
        if gradients:
            grad = jax.tree_util.tree_map(lambda g: np.asarray(g) / tokens, grad)
            grads = grad if grads is None else jax.tree_util.tree_map(
                np.add, grads, grad)
    held = _experts_held(params)
    return {"ce": nll / tokens, "counts": counts, "grads": grads,
            "rows_held": int(counts[first_expert:first_expert + held].sum()),
            "logits": np.stack(logits), "margin": np.stack(margins)}


def _experts_held(params) -> int:
    return next(lp["block_sparse_moe"]["w1"].shape[0]
                for lp in params["model"].values() if "block_sparse_moe" in lp)


def cross_entropy(params, ids, cfg: dict, first_expert: int = 0) -> jax.Array:
    """The training loss (the config has no balance term)."""
    return loss_parts(params, ids, cfg, first_expert)["ce"]
