"""Plain reference of Keye-VL-2.0-30B-A3B's language model as it trains:
forward pass, next-token loss, its gradients, the routing and the sparse
attention's choice.

Straightforward ``jax.numpy`` in float32 with the matmul precision at
"highest". No kernels, no cache, no batching, and nothing imported from the
program under test; :func:`step_parts` gives loss, gradients, counts, the
chosen positions' logits and the choice of one training batch, one sequence
at a time. It follows the published ``config.json`` (``model_type: KeyeVL2``,
the language model's keys: Qwen3-MoE's blocks with ``sa_config``) and, for
the sparse attention the config names without equations, DeepSeek Sparse
Attention as published with DeepSeek-V3.2-Exp, at ``sa_config``'s sizes.
Layer ``i``:

    r = x + Attn(rms(x, input_layernorm));
    out = r + MoE(rms(r, post_attention_layernorm))

- ``Attn``, main path: grouped-query attention without biases (32 query
  heads, 4 key/value heads of 128); q and k RMS-normalised over each head's
  128 values with one weight a projection; rotary embedding in the
  half-split layout over all 128; scores times ``128 ** -0.5``; softmax
  over the keys THE INDEXER CHOSE; ``o_proj``.
- ``Attn``, indexer, from the same normed input ``h`` (no gradient reaches
  it): ``qI = rope(h W_qI)`` as 16 heads of 64; ONE key a token ``kI =
  rope(LayerNorm_64(h W_kI))`` (scale and bias); head weights ``w = h W_w *
  16 ** -0.5 * 64 ** -0.5``; ``I[t, s] = sum_j w[t, j] relu(qI[t, j] .
  kI[s])`` for ``s <= t``.
- the choice: ``S_t`` = the 2,048 keys of largest ``I[t, .]`` among ``s <=
  t`` (``lax.top_k`` for the 2,048th value; of keys equal to it the lowest
  positions), all of them when ``t < 2,048``; one choice a token and layer,
  shared by the heads. It is a constant of the backward pass, kept from the
  forward as two numbers a query (``threshold``).
- ``MoE``: ``reference/sdar_moe.py``'s block as it is (softmax over the
  router's width, top-8 renormalised, SwiGLU experts, the chip's share: a
  chosen expert that is not held adds nothing).
- a final RMSNorm, an untied head, next-token cross-entropy (position ``i``
  predicts token ``i + 1``), the mean over the positions that have a next.

Departures, each stated (the configuration file's ``assumed`` says why):
- qI comes from the layer's input by its own matrix (V3.2 takes it from a
  query latent this GQA model does not have);
- the LayerNorm with bias on kI and the ``16 ** -0.5 * 64 ** -0.5`` on w are
  DeepSeek's modeling code's; rotary over all 64 of the indexer's width with
  the main theta; float32 here where the released kernels score in fp8;
- ``q_chunk_size`` / ``kv_chunk_size`` (512) are read as the published
  kernel's tiles and change no result;
- no alignment loss (DSA's recipe trains the indexer by a KL term toward the
  head-summed attention; the config has no key or coefficient of it), so the
  indexer's leaves have exactly zero gradient; no balance loss;
- ``mrope_section`` [16, 24, 24]: on token ids the three position components
  are equal, so M-RoPE is the one-dimensional rotary over 128; no vision
  tower;
- what belongs to a query (its q, its indexer's qI and w, its scores, its
  output through ``o_proj``) is made one block of 128 queries after another
  and the whole model one sequence at a time, so that at 32,768 positions no
  [T, T] array exists (4.3 GB in float32); each block of queries, each
  expert, each layer and each chunk of the head is a ``jax.checkpoint``,
  which changes no value; the experts are dense over the tokens
  (``sdar_moe.py`` says why).

``wrong`` (a set of names) makes it the WRONG model in one stated way, for
the calibration of the cell's limits and nothing else: ``dense`` (causal
attention over every earlier key: the choice ignored), ``top1024`` (half the
keys), ``no_relu`` (the indexer's heads summed without the ReLU), ``no_w``
(the head weights left out: 1 for each), ``acausal_choice`` (the 2,048 taken
over ALL keys, later ones too, and the causal ones of them attended, with the
query's own position so that none is left with no key),
``fp8`` (every matmul's operands, the indexer's too, rounded to fp8 e4m3's
three mantissa bits: the nearest precision below the configuration's bf16).
``bf16`` is the configuration's OWN precision: read to show that it is not
told from the program, and required of nothing.

Weights come as the tree the program holds: ``sdar_moe.py``'s, and in each
``self_attn`` beside it ``indexer_q_proj`` / ``indexer_k_proj`` /
``indexer_weights_proj`` (``{"kernel"}``, ``[in, out]``) and
``indexer_k_norm`` (``{"scale", "bias"}``).
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from benchmark.reference.mistral import _HI, _rope, rms_norm
from benchmark.reference.sdar_moe import _mm, _operand, moe_block

WRONG = ("dense", "top1024", "no_relu", "no_w", "acausal_choice", "fp8")
OWN_PRECISION = "bf16"


def layer_norm(x, scale, bias, eps: float):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def index_scores(qi, w, ki, wrong=frozenset()):
    """I[t, s], every pair: qi [n, 16, 64], w [n, 16], ki [T, 64] -> [n, T]."""
    dots = jnp.einsum("qhd,sd->qhs", _operand(qi, wrong), _operand(ki, wrong),
                      precision=_HI)
    if "no_relu" not in wrong:
        dots = jnp.maximum(dots, 0.0)
    if "no_w" in wrong:
        w = jnp.ones_like(w)
    return jnp.einsum("qhs,qh->qs", dots, w, precision=_HI)


def threshold(scores, q_pos, topk: int, wrong=frozenset()):
    """The choice of each query as two numbers: ``kth`` [n, 1], the
    ``topk``-th largest of its scores over the keys ``s <= t`` (``lax.top_k``),
    and ``cut`` [n, 1], the last position at which a key EQUAL to ``kth`` is
    still taken: of the keys equal to it the lowest positions, so that
    ``min(t + 1, topk)`` are chosen in all (-1: none of them). ``scores``
    [n, T], ``q_pos`` [n] the queries' positions."""
    T = scores.shape[1]
    if "top1024" in wrong:
        topk = topk // 2
    k = min(topk, T)
    if "acausal_choice" not in wrong:
        scores = jnp.where(jnp.arange(T)[None, :] <= q_pos[:, None], scores, -jnp.inf)
    kth = jax.lax.top_k(scores, k)[0][:, -1:]
    equal = scores == kth
    need = k - jnp.sum(scores > kth, axis=1, keepdims=True)
    taken = equal & (jnp.cumsum(equal, axis=1) <= need)
    return kth, jnp.max(jnp.where(taken, jnp.arange(T)[None, :], -1), axis=1, keepdims=True)


def chosen(scores, kth, cut, q_pos, wrong=frozenset()):
    """-> ``[n, T]`` bool: the keys each query attends, from ``threshold``'s
    two numbers: ``s <= t`` and ``I[t, s] > kth``, or equal to it at a
    position up to ``cut``."""
    pos = jnp.arange(scores.shape[1])[None, :]
    causal = pos <= q_pos[:, None]
    if "dense" in wrong:
        return causal
    picked = ((scores > kth) | ((scores == kth) & (pos <= cut))) & causal
    if "acausal_choice" in wrong:   # a query left with no earlier key sees itself
        picked = picked | (pos == q_pos[:, None])
    return picked


def attention_residual(x, lp, positions, cfg: dict, sample, q_block: int = 128,
                       wrong=frozenset()):
    """``x + Attn(rms(x))`` of one layer on ONE sequence ``x [1, T, hidden]``
    -> (the stream, pairs chosen a row [T], each row's smallest chosen score
    [T], the choice of the queries ``sample`` [n, T] bool). Keys, values and
    the indexer's keys are made for the whole sequence; what belongs to a
    query (its q, its indexer's qI and w, its scores, its output through
    ``o_proj``) one block of ``q_block`` queries after another (``lax.map``:
    a backward pass holds one block's)."""
    H, KV, d = (int(cfg[k]) for k in ("num_attention_heads", "num_key_value_heads",
                                      "head_dim"))
    sa = cfg["sa_config"]
    HI, DI, topk = (int(sa[k]) for k in ("indexer_num_heads", "indexer_head_dim", "topk"))
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    T = x.shape[1]
    if T % q_block:
        q_block = T
    a = lp["self_attn"]
    h = rms_norm(x, lp["input_layernorm"]["weight"], eps)
    k = rms_norm(_mm(h, a["k_proj"]["kernel"], wrong).reshape(1, T, KV, d),
                 a["k_norm"]["weight"], eps)
    k = _rope(k, positions, theta)[0]
    v = _mm(h, a["v_proj"]["kernel"], wrong).reshape(T, KV, d)
    # the indexer: a constant of the backward pass
    hi = jax.lax.stop_gradient(h)
    ki = layer_norm(_mm(hi, a["indexer_k_proj"]["kernel"], wrong),
                    a["indexer_k_norm"]["scale"], a["indexer_k_norm"]["bias"], eps)
    ki = jax.lax.stop_gradient(_rope(ki[:, :, None, :], positions, theta)[0, :, 0])

    def indexer_of(h_rows, pos):
        """The indexer's qI [n, 16, 64] and w [n, 16] of the queries at ``pos``."""
        h_rows = jax.lax.stop_gradient(h_rows)
        qi = _rope(_mm(h_rows, a["indexer_q_proj"]["kernel"], wrong)
                   .reshape(1, -1, HI, DI), pos[None], theta)[0]
        w = _mm(h_rows, a["indexer_weights_proj"]["kernel"], wrong) * (HI**-0.5 * DI**-0.5)
        return jax.lax.stop_gradient(qi), jax.lax.stop_gradient(w)

    # a block's scores are made again in the backward pass, its ``lax.top_k``
    # is not: the choice's two numbers a query are kept
    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.save_only_these_names("choice"))
    def block_of_queries(args):
        hb, first = args
        pos = first + jnp.arange(q_block)
        q = rms_norm(_mm(hb, a["q_proj"]["kernel"], wrong).reshape(1, q_block, H, d),
                     a["q_norm"]["weight"], eps)
        q = _rope(q, pos[None], theta)[0].reshape(q_block, KV, H // KV, d)
        sc = index_scores(*indexer_of(hb, pos), ki, wrong)
        kth, cut = (checkpoint_name(t, "choice") for t in threshold(sc, pos, topk, wrong))
        picked = jax.lax.stop_gradient(chosen(sc, kth, cut, pos, wrong))
        scores = jnp.einsum("qkgd,skd->kgqs", _operand(q, wrong), _operand(k, wrong),
                            precision=_HI) / jnp.sqrt(jnp.float32(d))
        probs = jax.nn.softmax(jnp.where(picked[None, None], scores, -jnp.inf), axis=-1)
        out = jnp.einsum("kgqs,skd->qkgd", _operand(probs, wrong), _operand(v, wrong),
                         precision=_HI)
        return (_mm(out.reshape(q_block, H * d), a["o_proj"]["kernel"], wrong),
                jnp.sum(picked, axis=1, dtype=jnp.int32),
                jnp.min(jnp.where(picked, sc, jnp.inf), axis=1))

    out, pairs, kth = jax.lax.map(
        block_of_queries, (h[0].reshape(T // q_block, q_block, -1),
                           jnp.arange(0, T, q_block)))
    sc = index_scores(*indexer_of(h[0][sample], sample), ki, wrong)
    picked = chosen(sc, *threshold(sc, sample, topk, wrong), sample, wrong)
    return x + out.reshape(1, T, -1), pairs.reshape(T), kth.reshape(T), picked


def layer(x, lp, positions, sample, cfg: dict, first_expert: int = 0,
          wrong=frozenset()):
    b, s, _ = x.shape
    r, pairs, kth, picked = attention_residual(x, lp, positions, cfg, sample,
                                               wrong=wrong)
    h = rms_norm(r, lp["post_attention_layernorm"]["weight"], float(cfg["rms_norm_eps"]))
    out, counts = moe_block(h.reshape(b * s, -1), lp["block_sparse_moe"],
                            int(cfg["num_experts_per_tok"]), bool(cfg["norm_topk_prob"]),
                            first_expert, wrong)
    return r + out.reshape(b, s, -1), (counts, pairs, kth, picked)


def _sequence_loss(params, ids, at, sample, cfg: dict, first_expert: int, wrong,
                   head_chunks: int = 8):
    """One sequence ``ids`` [1, T] -> (the sum of its next-token losses,
    (counts [layers, E], logits [len(at), vocab], pairs chosen [layers],
    the rows' smallest chosen scores' mean [layers], the choice of the
    queries ``sample`` [layers, n, T]))."""
    m = params["model"]
    T = ids.shape[1]
    positions = jnp.arange(T)[None, :]
    x = jnp.take(m["embed_tokens"]["embedding"], ids, axis=0).astype(jnp.float32)
    seen = []
    for i in range(int(cfg["num_hidden_layers"])):
        x, aux = jax.checkpoint(functools.partial(
            layer, cfg=cfg, first_expert=first_expert, wrong=wrong))(
                x, m[f"layers_{i}"], positions, sample)
        seen.append(aux)
    counts, pairs, kth, picked = (jnp.stack(a) for a in zip(*seen))
    x = rms_norm(x, m["norm"]["weight"], float(cfg["rms_norm_eps"]))[0]
    head = m["lm_head"]["kernel"]
    chunks = head_chunks if T % head_chunks == 0 else 1
    # position i predicts token i + 1; the last position has none: weight 0
    targets = jnp.concatenate([ids[0, 1:], ids[0, :1]])
    weight = (jnp.arange(T) < T - 1).astype(jnp.float32)

    @jax.checkpoint
    def chunk_loss(args):
        xc, tc, wc = args
        lg = _mm(xc, head, wrong)
        gold = jnp.take_along_axis(lg, tc[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(lg, axis=-1) - gold) * wc)

    parts = jax.lax.map(chunk_loss, tuple(
        a.reshape(chunks, T // chunks, *a.shape[1:]) for a in (x, targets, weight)))
    return jnp.sum(parts), (counts, _mm(x[at], head, wrong),
                            jnp.sum(pairs, axis=1), jnp.mean(kth, axis=1), picked)


@functools.lru_cache(maxsize=None)
def _compiled_pass(cfg_json: str, first_expert: int, wrong: frozenset, gradients: bool):
    fn = functools.partial(_sequence_loss, cfg=json.loads(cfg_json),
                           first_expert=first_expert, wrong=wrong)
    return jax.jit(jax.value_and_grad(fn, has_aux=True) if gradients else fn)


def step_parts(params, ids, cfg: dict, at, sample, first_expert: int = 0,
               wrong=frozenset(), gradients: bool = True) -> dict:
    """What one training step on ``ids`` [rows, T] has to reproduce, one
    sequence at a time and each a single compiled pass: ``ce`` (the loss),
    ``counts`` [E] (assignments over the router's width, summed over the
    layers), ``rows_held``, ``grads`` (``jax.grad`` of ``ce``, numpy float32
    summed on the host; None without ``gradients``), ``logits`` [rows, n,
    vocab] at each sequence's positions ``at[row]``, and of the sparse
    attention ``chosen_pairs`` [layers] (summed over the rows),
    ``kth_score_mean`` (the mean over rows, positions and layers of a
    query's smallest chosen score) and ``choice`` [rows, layers, n, T] bool
    (the keys the queries ``sample[row]`` attend)."""
    keys = ("num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "rope_theta", "num_experts_per_tok",
            "norm_topk_prob", "sa_config")
    fn = _compiled_pass(json.dumps({k: cfg[k] for k in keys}), first_expert,
                        frozenset(wrong), gradients)
    ids = np.asarray(ids)
    rows, seq = ids.shape
    n_loss = rows * (seq - 1)
    with jax.default_matmul_precision("highest"):
        loss, counts, pairs, kth, grads, logits, choice = 0.0, 0, 0, [], None, [], []
        for row in range(rows):
            out = fn(params, ids[row:row + 1], jnp.asarray(at[row]),
                     jnp.asarray(sample[row]))
            (part, (count, lg, chosen, k_mean, picked)), grad = (
                out if gradients else (out, None))
            loss += float(part)
            counts = counts + np.asarray(jnp.sum(count, axis=0))
            pairs = pairs + np.asarray(chosen, np.int64)
            kth.append(np.asarray(k_mean))
            logits.append(np.asarray(lg))
            choice.append(np.asarray(picked))
            if gradients:
                grad = jax.tree_util.tree_map(lambda g: np.asarray(g) / n_loss, grad)
                grads = grad if grads is None else jax.tree_util.tree_map(
                    np.add, grads, grad)
    held = next(lp["block_sparse_moe"]["w1"].shape[0]
                for lp in params["model"].values() if "block_sparse_moe" in lp)
    return {"ce": loss / n_loss, "counts": counts, "grads": grads,
            "rows_held": int(counts[first_expert:first_expert + held].sum()),
            "logits": np.stack(logits), "chosen_pairs": pairs,
            "kth_score_mean": float(np.mean(kth)), "choice": np.stack(choice)}
