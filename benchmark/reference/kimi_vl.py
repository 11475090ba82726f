"""Plain reference of Kimi-VL-A3B's language model (``model_type:
deepseek_v3`` blocks) under next-token training: forward pass, loss, its
gradients, the routing and the latent's statistics.

Straightforward ``jax.numpy`` in float32 with the matmul precision at
"highest". No kernels, no cache, no sort, no grouped matmul, and nothing
imported from the program under test; :func:`step_parts` gives loss,
gradients, counts and chosen positions' logits of one training batch, one
sequence at a time. It follows the published ``config.json`` and the
DeepSeek-V3 modeling code the checkpoint names. Layer ``i`` on a token's
stream ``x`` (hidden 2048):

    r = x + Attn(rms(x, operator_norm));  out = r + FFN(rms(r, ffn_norm))

- ``Attn`` (latent attention, training form; nothing absorbed):
  ``q = W_q h`` as ``heads x [nope 128 | rope 64]``; ``[c | k_r] = W_kva h``
  (512 + 64); ``c <- rms(c, kv_a_layernorm)``; ``[k_nope | v] = W_kvb c`` as
  ``heads x [128 | 128]``; the rotary embedding turns ADJACENT pairs ``(x_2i,
  x_2i+1)`` of ``q``'s rope slice and of ``k_r`` by ``pos * theta^(-2i/64)``;
  ``k = [k_nope | k_r]`` with the one ``k_r`` a token shared by the heads;
  ``softmax_causal(q k^T / sqrt(192)) v``, 128 wide a head; ``W_o``.
- ``FFN``: layer ``i < first_k_dense_replace``: SwiGLU ``intermediate_size``
  wide. Else ``routed + shared``: ``s = sigmoid(h W_g)`` in float32 over the
  router's width; the ``top_k`` largest of ``s + b`` (``b`` the selection
  bias, a buffer); weights ``s_i / (sum_chosen s + 1e-20) *
  routed_scaling_factor`` from the UNBIASED scores; ``sum_i w_i
  SwiGLU_i(h)`` over the chosen experts; plus one SwiGLU ``n_shared_experts
  * moe_intermediate_size`` wide that every token passes, no gate, weight 1.
- a final RMSNorm, an untied head, token-mean next-token cross-entropy:
  position ``t`` predicts ``ids[t + 1]``.

The chip's share. The router's width is the gate's, the experts held are
those whose matrices the tree has (``w1.shape[0]``), ``first_expert`` says
which of the router's they are. A chosen expert that is not held adds
nothing (the normaliser is still over all the chosen): that partial sum,
plus the whole shared expert, is what goes on to the next layer, here as in
the program. With all experts held this is the uncut layer. A sliced
vocabulary is a smaller vocabulary: embedding and head have that many rows.

Departures from the published description, each stated:
- no vision tower: the catalog gives no width of MoonViT or its projector;
  the language model runs on token ids alone;
- no balance loss (``seq_aux`` comes with no coefficient), and the selection
  bias is a constant: its update rule is the training recipe's;
- the share, as above, without the exchange between the chips;
- attention runs in blocks of queries and the model one sequence at a time,
  so that at 8,192 positions no score matrix of a whole sequence exists; each
  block of queries, each expert and each layer is a ``jax.checkpoint``, which
  changes no value; the experts are dense over the tokens (every expert held
  multiplies every position and the result is weighted by ``w`` or by 0).

``wrong`` (a set of names) makes it the WRONG model in one stated way, for
the calibration of the cell's limits and nothing else: ``no_latent_norm``
(``kv_a_layernorm`` dropped), ``rope_on_nope`` (the rotation applied to the
first 64 of q's and k's 128 unrotated values, the rope slices left as they
are), ``scale_128`` (scores over ``sqrt(128)``), ``no_shared`` (no shared
expert), ``gated_shared`` (the shared expert under Qwen2-MoE's sigmoid gate
as it is born: half), ``no_scaling`` (``routed_scaling_factor`` left out),
``unbiased_topk`` (the top-k of ``s`` alone), ``fp8`` (every matmul's
operands rounded to fp8 e4m3's three mantissa bits, the exponent left wide:
the nearest precision below the bf16 the configuration states). ``bf16`` (the
operands rounded to bf16) is the configuration's OWN precision: read to show
that it is not told from the program, and required of nothing.

Weights come as the tree the program holds (``{"model": {"embed_tokens":
{"embedding"}, "layers_<i>": {"operator_norm", "ffn_norm", "self_attn":
{q_proj, kv_a_proj_with_mqa, kv_a_layernorm, kv_b_proj, o_proj}, "mlp":
{gate_proj, up_proj, down_proj} | "block_sparse_moe": {"gate": {"kernel"},
"expert_bias", "w1", "w3", "w2", "shared_expert": {gate_proj, up_proj,
down_proj}}}, "norm", "lm_head": {"kernel"}}}``; kernels ``[in, out]``,
experts stacked ``[held, in, out]``, ``w1`` the gate, ``w3`` the up and
``w2`` the down projection).
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

# the plain pieces other references have, shared as they are: RMSNorm and the
# "highest" precision (the dense decoder's), the operands' rounding under
# ``bf16`` / ``fp8`` and the matmul over it (SDAR's)
from benchmark.reference.mistral import _HI, rms_norm
from benchmark.reference.sdar_moe import _mm, _operand

WRONG = ("no_latent_norm", "rope_on_nope", "scale_128", "no_shared",
         "gated_shared", "no_scaling", "unbiased_topk", "fp8")
OWN_PRECISION = "bf16"
RENORM_EPS = 1e-20          # in the modeling code, not a config key


def rope_pairs(x, positions, theta: float):
    """x: [b, s, heads, d]: adjacent pairs ``(x_2i, x_2i+1)`` turned by
    ``positions * theta^(-2i/d)``."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq      # [b, s, d/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).reshape(x.shape)


def attend(q, k, v, scale: float, q_block: int, wrong=frozenset()):
    """q, k: [b, s, H, d_qk], v: [b, s, H, d_v] -> [b, s, H * d_v]: causal
    softmax attention, one block of ``q_block`` queries after another
    (``lax.map``: a backward pass holds one block's scores; one block where
    ``q_block`` does not divide the sequence)."""
    b, s, H, d = q.shape
    if s % q_block:
        q_block = s

    @jax.checkpoint
    def block_of_queries(args):
        qb, first = args
        seen = (jnp.arange(s)[None, :] <= first + jnp.arange(q_block)[:, None])
        scores = jnp.einsum("bqhd,bkhd->bhqk", _operand(qb, wrong), _operand(k, wrong),
                            precision=_HI) * scale
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _operand(probs, wrong), _operand(v, wrong),
                          precision=_HI)

    blocks = q.reshape(b, s // q_block, q_block, H, d).swapaxes(0, 1)
    outs = jax.lax.map(block_of_queries, (blocks, jnp.arange(0, s, q_block)))
    return outs.swapaxes(0, 1).reshape(b, s, -1)


def latent_attention(h, a, positions, cfg: dict, q_block: int = 512,
                     wrong=frozenset()):
    """``Attn(h)`` on ``h [b, s, hidden]`` -> (its output, the mean squares
    of the latent before its norm and of the rope key before the rotation
    ``[2]``)."""
    H, rank = int(cfg["num_attention_heads"]), int(cfg["kv_lora_rank"])
    nope, rope, dv = (int(cfg[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim",
                                            "v_head_dim"))
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    b, s, _ = h.shape
    q = _mm(h, a["q_proj"]["kernel"], wrong).reshape(b, s, H, nope + rope)
    kva = _mm(h, a["kv_a_proj_with_mqa"]["kernel"], wrong)
    c, k_r = kva[..., :rank], kva[..., rank:].reshape(b, s, 1, rope)
    stats = jnp.stack([jnp.mean(c * c), jnp.mean(k_r * k_r)])
    if "no_latent_norm" not in wrong:
        c = rms_norm(c, a["kv_a_layernorm"]["weight"], eps)
    kvb = _mm(c, a["kv_b_proj"]["kernel"], wrong).reshape(b, s, H, nope + dv)
    q_nope, q_rope, k_nope, v = q[..., :nope], q[..., nope:], kvb[..., :nope], kvb[..., nope:]
    if "rope_on_nope" in wrong:
        turn = lambda x: jnp.concatenate(       # noqa: E731
            [rope_pairs(x[..., :rope], positions, theta), x[..., rope:]], -1)
        q_nope, k_nope = turn(q_nope), turn(k_nope)
    else:
        q_rope, k_r = rope_pairs(q_rope, positions, theta), rope_pairs(k_r, positions, theta)
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_r, (b, s, H, rope))], -1)
    scale = 1.0 / float(np.sqrt(nope if "scale_128" in wrong else nope + rope))
    return _mm(attend(q, k, v, scale, q_block, wrong), a["o_proj"]["kernel"], wrong), stats


def swiglu(h, f, wrong=frozenset()):
    return _mm(jax.nn.silu(_mm(h, f["gate_proj"]["kernel"], wrong))
               * _mm(h, f["up_proj"]["kernel"], wrong), f["down_proj"]["kernel"], wrong)


def route(h, moe, top_k: int, renormalize: bool, scaling: float, wrong=frozenset(),
          choice=None):
    """-> (chosen experts ``[T, k]``, their weights ``[T, k]``): sigmoid
    scores in float32, the ``top_k`` largest of ``s + b``, weighted by ``s``.
    ``choice`` ``[T, k]`` takes the place of the chosen experts (the
    calibration's: the reference routed as the program routed, weighted by its
    own scores)."""
    s = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32),
                               moe["gate"]["kernel"].astype(jnp.float32), precision=_HI))
    biased = s if "unbiased_topk" in wrong else s + moe["expert_bias"].astype(jnp.float32)
    chosen = jax.lax.top_k(biased, top_k)[1] if choice is None else choice
    p = jnp.take_along_axis(s, chosen, axis=1)
    if renormalize:
        p = p / (jnp.sum(p, axis=1, keepdims=True) + RENORM_EPS)
    return chosen, p if "no_scaling" in wrong else p * scaling


def moe_block(h, moe, cfg: dict, first_expert: int = 0, wrong=frozenset(), choice=None):
    """h: [tokens, hidden] float32 -> (what the experts held and the shared
    expert give ``[tokens, hidden]``, per-expert assignment counts over the
    router's width ``[E]``, the chosen experts ``[tokens, k]``). Dense over
    the tokens."""
    E = moe["gate"]["kernel"].shape[1]
    chosen, p = route(h, moe, int(cfg["num_experts_per_tok"]), bool(cfg["norm_topk_prob"]),
                      float(cfg["routed_scaling_factor"]), wrong, choice)
    picked = chosen[:, :, None] == jnp.arange(E)                      # [T, k, E]
    weight = jnp.sum(p[:, :, None] * picked, axis=1)                  # [T, E]

    @jax.checkpoint
    def one_expert(out, j):
        f = {"gate_proj": {"kernel": moe["w1"][j]}, "up_proj": {"kernel": moe["w3"][j]},
             "down_proj": {"kernel": moe["w2"][j]}}
        return out + swiglu(h, f, wrong) * weight[:, first_expert + j, None], None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(moe["w1"].shape[0]))
    if "shared_expert" in moe and "no_shared" not in wrong:
        shared = jax.checkpoint(functools.partial(swiglu, wrong=wrong))(
            h, moe["shared_expert"])
        out = out + (0.5 * shared if "gated_shared" in wrong else shared)
    return out, jnp.sum(picked, axis=(0, 1), dtype=jnp.int32), chosen


def layer(x, lp, positions, cfg: dict, first_expert: int = 0, q_block: int = 512,
          wrong=frozenset(), choice=None):
    """One layer on ``x [b, s, hidden]`` -> (the stream after it, the
    latent's mean squares ``[2]``, and of an expert layer the router's counts
    ``[E]`` and the chosen experts ``[b * s, k]``)."""
    b, s, _ = x.shape
    eps = float(cfg["rms_norm_eps"])
    att, stats = latent_attention(rms_norm(x, lp["operator_norm"]["weight"], eps),
                                  lp["self_attn"], positions, cfg, q_block, wrong)
    r = x + att
    h = rms_norm(r, lp["ffn_norm"]["weight"], eps)
    if "mlp" in lp:
        return r + swiglu(h, lp["mlp"], wrong), stats
    out, counts, chosen = moe_block(h.reshape(b * s, -1), lp["block_sparse_moe"], cfg,
                                    first_expert, wrong, choice)
    return r + out.reshape(b, s, -1), stats, counts, chosen


def hidden_states(params, ids, cfg: dict, first_expert: int = 0, wrong=frozenset(),
                  choice=None):
    """-> (final-norm hidden states ``[b, s, hidden]`` float32, the expert
    layers' counts ``[expert layers, E]``, the layers' latent mean squares
    ``[layers, 2]``, the expert layers' chosen experts ``[expert layers,
    b * s, k]``). ``choice``, shaped as the last, routes each expert layer as
    it says."""
    m = params["model"]
    b, s = ids.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = jnp.take(m["embed_tokens"]["embedding"], ids, axis=0).astype(jnp.float32)
    counts, stats, chosen = [], [], []
    for i in range(int(cfg["num_hidden_layers"])):
        one = jax.checkpoint(functools.partial(
            layer, cfg=cfg, first_expert=first_expert, wrong=wrong,
            choice=None if choice is None else choice[len(chosen)]))
        x, stat, *routed = one(x, m[f"layers_{i}"], positions)
        stats.append(stat)
        if routed:
            counts.append(routed[0])
            chosen.append(routed[1])
    return (rms_norm(x, m["norm"]["weight"], float(cfg["rms_norm_eps"])),
            jnp.stack(counts), jnp.stack(stats), jnp.stack(chosen))


def _sequence_nll(params, ids, at, choice, cfg: dict, first_expert: int, wrong):
    """One sequence ``ids [1, seq]`` -> (the sum of its next-token losses,
    (counts ``[expert layers, E]``, the logits ``[len(at), vocab]`` at
    positions ``at``, the latent's mean squares ``[layers, 2]``, the chosen
    experts ``[expert layers, seq, k]``))."""
    x, counts, stats, chosen = hidden_states(params, ids, cfg, first_expert, wrong, choice)
    lg = _mm(x[0], params["model"]["lm_head"]["kernel"], wrong)
    gold = jnp.take_along_axis(lg[:-1], ids[0, 1:, None], axis=-1)[:, 0]
    nll = jnp.sum(jax.nn.logsumexp(lg[:-1], axis=-1) - gold)
    return nll, (counts, lg[at], stats, chosen.astype(jnp.int16))


@functools.lru_cache(maxsize=None)
def _compiled_pass(cfg_json: str, first_expert: int, wrong: frozenset, gradients: bool):
    fn = functools.partial(_sequence_nll, cfg=json.loads(cfg_json),
                           first_expert=first_expert, wrong=wrong)
    return jax.jit(jax.value_and_grad(fn, has_aux=True) if gradients else fn)


KEYS = ("num_hidden_layers", "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "rms_norm_eps", "rope_theta",
        "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor")


def step_parts(params, ids, cfg: dict, at, first_expert: int = 0, wrong=frozenset(),
               gradients: bool = True, choice=None) -> dict:
    """What one training step on ``ids [rows, seq]`` has to reproduce, one
    sequence at a time and each a single compiled pass: ``ce`` (the
    token-mean next-token loss), ``counts`` ``[E]`` (assignments over the
    router's width, summed over the expert layers), ``rows_held`` (those of
    them sent to the experts held), ``grads`` (``jax.grad`` of ``ce``, the
    tree as numpy float32 summed on the host; None without ``gradients``),
    ``logits`` ``[rows, n, vocab]`` at each sequence's positions ``at[row]``,
    ``latent_rms`` / ``k_rope_rms`` (root mean squares over the batch of the
    latent before its norm and of the rope key, the layers' means), and
    ``chosen`` ``[rows, expert layers, seq, k]`` (each token's experts; with
    ``choice``, shaped alike, they are the ones it gives)."""
    fn = _compiled_pass(json.dumps({k: cfg[k] for k in KEYS}), first_expert,
                        frozenset(wrong), gradients)
    ids = np.asarray(ids)
    rows, seq = ids.shape
    tokens = rows * (seq - 1)
    with jax.default_matmul_precision("highest"):
        nll, counts, squares, grads, logits, chosen = 0.0, 0, 0.0, None, [], []
        for row in range(rows):
            out = fn(params, ids[row:row + 1], jnp.asarray(at[row]),
                     None if choice is None else jnp.asarray(choice[row], jnp.int32))
            (part, (count, lg, stats, picks)), grad = out if gradients else (out, None)
            chosen.append(np.asarray(picks))
            nll += float(part)
            counts = counts + np.asarray(jnp.sum(count, axis=0))
            squares = squares + np.asarray(stats, np.float64) / rows
            logits.append(np.asarray(lg))
            if gradients:
                grad = jax.tree_util.tree_map(lambda g: np.asarray(g) / tokens, grad)
                grads = grad if grads is None else jax.tree_util.tree_map(
                    np.add, grads, grad)
    held = next(lp["block_sparse_moe"]["w1"].shape[0]
                for lp in params["model"].values() if "block_sparse_moe" in lp)
    rms = np.sqrt(squares).mean(axis=0)
    return {"ce": nll / tokens, "counts": counts, "grads": grads,
            "rows_held": int(counts[first_expert:first_expert + held].sum()),
            "logits": np.stack(logits), "latent_rms": float(rms[0]),
            "k_rope_rms": float(rms[1]), "chosen": np.stack(chosen)}
