"""Plain reference of SDAR-MoE's decoder under block-diffusion training:
forward pass, the weighted unshifted loss, its gradients and the routing.

Straightforward ``jax.numpy`` in float32 with the matmul precision at
"highest". No kernels, no sort, no grouped matmul, and nothing imported from
the program under test; :func:`step_parts` gives loss, gradients, counts and
chosen positions' logits of one training batch, one sequence at a time. It
follows the published ``config.json`` (``model_type: sdar_moe``: Qwen3-MoE's
blocks) and BD3-LM's efficient training form (arXiv:2503.09573, section
3.2 and appendix B.6), which SDAR's training follows. Layer ``i``:

    r = x + Attn(rms(x, input_layernorm));
    out = r + MoE(rms(r, post_attention_layernorm))

- ``Attn``: grouped-query attention without biases; q and k RMS-normalised
  over each head's ``head_dim`` with one weight ``[head_dim]`` shared by the
  heads; rotary embedding in the half-split layout at the position ids
  given; scores times ``head_dim ** -0.5``; softmax over the keys THE MASK
  ALLOWS; ``o_proj``.
- ``MoE``: ``p = softmax(h W_g)`` over the router's width in float32; the
  ``top_k`` largest; ``p <- p / sum p`` over the chosen (``norm_topk_prob``);
  ``out = sum_{e chosen} p_e * W2_e(silu(W1_e h) * W3_e h)``. No shared
  expert, no router bias, no balance term.
- a final RMSNorm, an untied head.

Block-diffusion training. A document ``x0`` of ``L`` tokens in blocks of
``B``; ``xt`` is ``x0`` with each token of block ``b`` replaced by the mask
id with probability ``t_b``. The model runs ONCE on ``xt ⊕ x0`` (``2L``
positions, position ids ``0..L-1`` twice). With ``clean(i) = i >= L`` and
``blk(i) = (i mod L) // B``, query ``i`` sees key ``j`` iff (:func:`sees`)

    noisy -> noisy:  blk(i) == blk(j)
    noisy -> clean:  blk(j) <  blk(i)
    clean -> clean:  blk(j) <= blk(i)
    clean -> noisy:  never

The logits at noisy position ``i`` predict ``x0[i]`` itself (no shift), and
``loss = (1 / (rows * L)) sum_rows sum_i w_i CE(logits_i, x0_i)`` with ``w_i
= 1 / t_blk(i)`` where ``xt_i`` is the mask id, else 0. The clean half
carries no loss and does not reach the head. The batch (``xt ⊕ x0``,
targets, position ids, weights) is given: the noising is the program's
(``runtime/data_pipeline/block_diffusion.py``) and the comparison is on the
same noise.

The chip's share. The router's width is the gate's, the experts held are
those whose matrices the tree has (``w1.shape[0]``), ``first_expert`` says
which of the router's they are. A chosen expert that is not held adds
nothing: that partial sum is what goes on to the next layer, here as in the
program. With all experts held this is the uncut model. A sliced vocabulary
is a smaller vocabulary: embedding and head have that many rows.

Departures, each stated:
- attention runs in blocks of queries and the whole model one sequence at a
  time, so that at ``2L`` = 16,384 positions no score matrix of a whole
  sequence exists (34 GB); each block of queries, each expert and each layer
  is a ``jax.checkpoint``, which changes no value;
- the experts are dense over the tokens: every expert held multiplies every
  position and the result is weighted by ``p_e`` or by 0, so that no shape
  depends on the routing (an expert not held is never computed);
- the noise schedule (``t`` a block, uniform on ``(t_min, 1]``, weight
  ``1 / t``) and the block length are not in the published config: they are
  the configuration file's ``assumed``, and reach this file only through the
  batch's weights and the ``block_length`` key.

``wrong`` (a set of names) makes it the WRONG model in one stated way, for
the calibration of the cell's limits and nothing else: ``causal`` (a causal
mask over the ``2L`` positions), ``leak`` (noisy queries see their OWN clean
block: ``<=`` for ``<``), ``clean_sees_noisy`` (clean queries see the noisy
keys of their own block), ``unit_weights`` (1 for ``1 / t``),
``shifted_labels`` (position ``i`` predicts ``x0[i + 1]``), ``no_renorm``
(the top-k weights as the softmax gave them), ``fp8`` (every matmul's
operands rounded to fp8 e4m3's three mantissa bits, the exponent left wide as
per-tensor scaling leaves it: the nearest precision below the bf16 the
configuration states). ``bf16`` (the operands rounded to bf16) is the
configuration's OWN precision: the calibration reads it to show that it is
not told from the program, and requires nothing of it.

Weights come as the tree the program holds (``{"model": {"embed_tokens":
{"embedding"}, "layers_<i>": {"input_layernorm", "post_attention_layernorm",
"self_attn": {q_proj, k_proj, v_proj, o_proj, q_norm, k_norm},
"block_sparse_moe": {"gate": {"kernel"}, "w1", "w3", "w2"}}, "norm",
"lm_head": {"kernel"}}}``; kernels ``[in, out]``, experts stacked ``[held,
in, out]``, ``w1`` the gate, ``w3`` the up and ``w2`` the down projection).
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

# the dense decoder's plain pieces, shared as they are: RMSNorm, half-split
# rotary, the "highest" precision
from benchmark.reference.mistral import _HI, _rope, rms_norm

WRONG = ("causal", "leak", "clean_sees_noisy", "unit_weights", "shifted_labels",
         "no_renorm", "fp8")
OWN_PRECISION = "bf16"


def _operand(x, wrong):
    """float32, its mantissa rounded to bf16's 7 bits under ``bf16`` and to
    fp8 e4m3's 3 under ``fp8`` (``reduce_precision`` and not a pair of
    casts, which XLA may drop on a TPU)."""
    x = x.astype(jnp.float32)
    for name, bits in (("bf16", 7), ("fp8", 3)):
        if name in wrong:
            x = jax.lax.reduce_precision(x, 8, bits)
    return x


def _mm(x, w, wrong=frozenset()):
    return jnp.dot(_operand(x, wrong), _operand(w, wrong), precision=_HI)


def sees(i, j, seq: int, block: int, wrong=frozenset()):
    """Whether query ``i`` sees key ``j`` (index arrays that broadcast), of
    ``2 * seq`` positions in blocks of ``block``: the four rules."""
    if "causal" in wrong:
        return j <= i
    q_clean, k_clean = i >= seq, j >= seq
    q_blk, k_blk = (i % seq) // block, (j % seq) // block
    noisy_noisy = ~q_clean & ~k_clean & (q_blk == k_blk)
    noisy_clean = ~q_clean & k_clean & ((k_blk <= q_blk) if "leak" in wrong
                                        else (k_blk < q_blk))
    clean_clean = q_clean & k_clean & (k_blk <= q_blk)
    clean_noisy = (q_clean & ~k_clean & (k_blk == q_blk)
                   if "clean_sees_noisy" in wrong else False)
    return noisy_noisy | noisy_clean | clean_clean | clean_noisy


def attend(q, k, v, block: int, q_block: int, wrong=frozenset()):
    """q: [b, 2L, H, d], k/v: [b, 2L, KV, d] -> [b, 2L, H*d]: softmax
    attention under :func:`sees`, grouped queries, one block of ``q_block``
    queries after another (``lax.map``: a backward pass holds one block's
    scores; one block where ``q_block`` does not divide the positions)."""
    b, s, H, d = q.shape
    if s % q_block:
        q_block = s
    k = jnp.repeat(k, H // k.shape[2], axis=2)
    v = jnp.repeat(v, H // v.shape[2], axis=2)

    @jax.checkpoint
    def block_of_queries(args):
        qb, first = args
        seen = sees(first + jnp.arange(q_block)[:, None], jnp.arange(s)[None, :],
                    s // 2, block, wrong)
        scores = jnp.einsum("bqhd,bkhd->bhqk", _operand(qb, wrong), _operand(k, wrong),
                            precision=_HI) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _operand(probs, wrong), _operand(v, wrong),
                          precision=_HI)

    blocks = q.reshape(b, s // q_block, q_block, H, d).swapaxes(0, 1)
    outs = jax.lax.map(block_of_queries, (blocks, jnp.arange(0, s, q_block)))
    return outs.swapaxes(0, 1).reshape(b, s, H * d)


def route(h, gate, top_k: int, renormalize: bool):
    """-> (chosen experts ``[T, k]``, their weights ``[T, k]``): the softmax
    over the router's width in float32, the ``top_k`` largest, renormalised
    over the chosen."""
    p = jax.nn.softmax(jnp.dot(h.astype(jnp.float32), gate.astype(jnp.float32),
                               precision=_HI), axis=-1)
    p, chosen = jax.lax.top_k(p, top_k)
    if renormalize:
        p = p / jnp.sum(p, axis=1, keepdims=True)
    return chosen, p


def moe_block(h, moe, top_k: int, renormalize: bool, first_expert: int = 0,
              wrong=frozenset()):
    """h: [tokens, hidden] float32 -> (what the experts held give ``[tokens,
    hidden]``, per-expert assignment counts over the router's width
    ``[E]``). Dense over the tokens: each expert held multiplies them all."""
    E = moe["gate"]["kernel"].shape[1]
    chosen, p = route(h, moe["gate"]["kernel"], top_k,
                      renormalize and "no_renorm" not in wrong)
    picked = chosen[:, :, None] == jnp.arange(E)                      # [T, k, E]
    weight = jnp.sum(p[:, :, None] * picked, axis=1)                  # [T, E]

    @jax.checkpoint
    def one_expert(out, j):
        w1, w3, w2 = moe["w1"][j], moe["w3"][j], moe["w2"][j]
        y = _mm(jax.nn.silu(_mm(h, w1, wrong)) * _mm(h, w3, wrong), w2, wrong)
        return out + y * weight[:, first_expert + j, None], None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                          jnp.arange(moe["w1"].shape[0]))
    return out, jnp.sum(picked, axis=(0, 1), dtype=jnp.int32)


def attention_residual(x, lp, positions, cfg: dict, q_block: int = 512,
                       wrong=frozenset()):
    """``x + Attn(rms(x))`` of one layer on ``x [b, 2L, hidden]``."""
    H, KV, d = (int(cfg[k]) for k in ("num_attention_heads", "num_key_value_heads",
                                      "head_dim"))
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    b, s, _ = x.shape
    a = lp["self_attn"]
    h = rms_norm(x, lp["input_layernorm"]["weight"], eps)
    q = rms_norm(_mm(h, a["q_proj"]["kernel"], wrong).reshape(b, s, H, d),
                 a["q_norm"]["weight"], eps)
    k = rms_norm(_mm(h, a["k_proj"]["kernel"], wrong).reshape(b, s, KV, d),
                 a["k_norm"]["weight"], eps)
    v = _mm(h, a["v_proj"]["kernel"], wrong).reshape(b, s, KV, d)
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    att = attend(q, k, v, int(cfg["block_length"]), q_block, wrong)
    return x + _mm(att, a["o_proj"]["kernel"], wrong)


def layer(x, lp, positions, cfg: dict, first_expert: int = 0, q_block: int = 512,
          wrong=frozenset()):
    """One layer on ``x [b, 2L, hidden]`` -> (the stream after it, the
    router's counts ``[E]``)."""
    b, s, _ = x.shape
    r = attention_residual(x, lp, positions, cfg, q_block, wrong)
    h = rms_norm(r, lp["post_attention_layernorm"]["weight"], float(cfg["rms_norm_eps"]))
    out, counts = moe_block(h.reshape(b * s, -1), lp["block_sparse_moe"],
                            int(cfg["num_experts_per_tok"]), bool(cfg["norm_topk_prob"]),
                            first_expert, wrong)
    return r + out.reshape(b, s, -1), counts


def hidden_states(params, input_ids, positions, cfg: dict, first_expert: int = 0,
                  wrong=frozenset()):
    """-> (final-norm hidden states of the NOISY half ``[b, L, hidden]``
    float32, the layers' assignment counts ``[layers, E]``)."""
    m = params["model"]
    x = jnp.take(m["embed_tokens"]["embedding"], input_ids, axis=0).astype(jnp.float32)
    counts = []
    for i in range(int(cfg["num_hidden_layers"])):
        x, count = jax.checkpoint(functools.partial(
            layer, cfg=cfg, first_expert=first_expert, wrong=wrong))(
                x, m[f"layers_{i}"], positions)
        counts.append(count)
    noisy = x[:, :x.shape[1] // 2]
    return (rms_norm(noisy, m["norm"]["weight"], float(cfg["rms_norm_eps"])),
            jnp.stack(counts))


def _sequence_loss(params, input_ids, positions, targets, weights, at, cfg: dict,
                   first_expert: int, wrong):
    """One sequence (``input_ids``, ``positions`` [1, 2L]; ``targets``,
    ``weights`` [1, L]) -> (the sum of its weighted losses, (counts
    ``[layers, E]``, the logits ``[len(at), vocab]`` at noisy positions
    ``at``))."""
    x, counts = hidden_states(params, input_ids, positions, cfg, first_expert, wrong)
    lg = _mm(x[0], params["model"]["lm_head"]["kernel"], wrong)
    tg, w = targets[0], weights[0]
    if "unit_weights" in wrong:
        w = (w > 0).astype(jnp.float32)
    if "shifted_labels" in wrong:       # position i predicts x0[i + 1]
        tg, w = jnp.roll(tg, -1), w.at[-1].set(0.0)
    gold = jnp.take_along_axis(lg, tg[:, None], axis=-1)[:, 0]
    loss = jnp.sum((jax.nn.logsumexp(lg, axis=-1) - gold) * w)
    return loss, (counts, lg[at])


@functools.lru_cache(maxsize=None)
def _compiled_pass(cfg_json: str, first_expert: int, wrong: frozenset,
                   gradients: bool):
    fn = functools.partial(_sequence_loss, cfg=json.loads(cfg_json),
                           first_expert=first_expert, wrong=wrong)
    return jax.jit(jax.value_and_grad(fn, has_aux=True) if gradients else fn)


def step_parts(params, batch, cfg: dict, at, first_expert: int = 0,
               wrong=frozenset(), gradients: bool = True) -> dict:
    """What one training step on ``batch`` = (``xt ⊕ x0`` [rows, 2L],
    targets [rows, L], position ids [rows, 2L], weights [rows, L]) has to
    reproduce, one sequence at a time and each a single compiled pass:
    ``ce`` (the loss), ``counts`` ``[E]`` (assignments over the router's
    width, summed over the layers), ``rows_held`` (those of them sent to the
    experts held), ``masked_tokens``, ``grads`` (``jax.grad`` of ``ce``, the
    tree as numpy float32 summed on the host; None without ``gradients``)
    and ``logits`` ``[rows, n, vocab]`` at each sequence's noisy positions
    ``at[row]``."""
    keys = ("num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "rope_theta", "num_experts_per_tok",
            "norm_topk_prob", "block_length")
    fn = _compiled_pass(json.dumps({k: cfg[k] for k in keys}), first_expert,
                        frozenset(wrong), gradients)
    input_ids, targets, positions, weights = (np.asarray(a) for a in batch)
    rows, seq = targets.shape
    with jax.default_matmul_precision("highest"):
        loss, counts, grads, logits = 0.0, 0, None, []
        for row in range(rows):
            one = slice(row, row + 1)
            out = fn(params, input_ids[one], positions[one], targets[one],
                     weights[one], jnp.asarray(at[row]))
            (part, (count, lg)), grad = out if gradients else (out, None)
            loss += float(part)
            counts = counts + np.asarray(jnp.sum(count, axis=0))
            logits.append(np.asarray(lg))
            if gradients:
                grad = jax.tree_util.tree_map(lambda g: np.asarray(g) / (rows * seq), grad)
                grads = grad if grads is None else jax.tree_util.tree_map(
                    np.add, grads, grad)
    held = next(lp["block_sparse_moe"]["w1"].shape[0]
                for lp in params["model"].values() if "block_sparse_moe" in lp)
    return {"ce": loss / (rows * seq), "counts": counts, "grads": grads,
            "rows_held": int(counts[first_expert:first_expert + held].sum()),
            "masked_tokens": int((weights > 0).sum()), "logits": np.stack(logits)}
