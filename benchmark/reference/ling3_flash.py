"""Plain reference of Ling-3.0-flash (``model_type: bailing_hybrid``) under
next-token training: forward pass, loss, its gradients, the routing and the
linear-attention layers' statistics.

Straightforward ``jax.numpy`` in float32 with the matmul precision at
"highest". No kernels, no chunked scan, no sort, no grouped matmul, and
nothing imported from the program under test; :func:`step_parts` gives loss,
gradients, counts and chosen positions' logits of one training batch, one
sequence at a time. Layer ``i`` on a token's stream ``x`` (hidden 2,560):

    r = x + Mixer(rms(x, operator_norm));  out = r + FFN(rms(r, ffn_norm))

- ``Mixer``, a ``"kda"`` layer (Kimi Delta Attention, 32 heads of 128):
  ``q, k, v = W_q h, W_k h, W_v h``, each through its own 4 causal depthwise
  taps (no bias) and SiLU; per head ``q = l2norm(q) / sqrt(128)``, ``k =
  l2norm(k)`` (``l2norm(x) = x / sqrt(sum x^2 + 1e-6)``); the decay of every
  key channel ``g = -5 * sigmoid(exp(A_log_h) * (W_f h + dt_bias))``, ``alpha
  = exp(g)``; ``beta = sigmoid(W_b h)`` one a head; the state ``S`` (128 x
  128 a head, zero before the first token) TOKEN BY TOKEN, never in chunks:
  ``S <- Diag(alpha_t) S; S <- S + beta_t k_t (v_t - S^T k_t)^T; o_t = S^T
  q_t``; ``y = W_o [rms_128(o_h) * o_norm * sigmoid(W_g h)_h]_h``.
- ``Mixer``, an ``"mla"`` layer: Kimi-VL's latent attention
  (``reference/kimi_vl.py``: q 32 x [nope 128 | rope 64] straight from the
  stream, a latent of 512 and ONE rope key a token, ``kv_a_layernorm``, scores
  over sqrt(192), adjacent-pair rotary at theta 6e6) with one addition: each
  head's output times ``sigmoid(W_gate h)_h`` before ``W_o``.
- ``FFN``: layer ``i < first_k_dense_replace``: SwiGLU 6,144 wide. Else
  ``routed + shared``: ``s = sigmoid(h W_r)`` in float32 over the router's 512
  experts; for the choice only ``s' = s + b`` (``b`` the selection bias, a
  buffer); ``n_group`` groups of consecutive experts, a group's score the sum
  of its two largest ``s'``, the ``topk_group`` best groups kept, the top 8
  of ``s'`` inside them; weights ``s_e / (sum_chosen s + 1e-20) * 2.5``;
  ``sum_e w_e SwiGLU_e(h)`` over the chosen experts; plus one SwiGLU 768 wide
  that every token passes, no gate.
- a final RMSNorm, an untied head, token-mean next-token cross-entropy.

The chip's share, as ``reference/kimi_vl.py``: the experts held are those
whose matrices the tree has, a chosen expert that is not held adds nothing
(the normaliser is still over all eight chosen), a sliced vocabulary is a
smaller vocabulary.

Departures from the published description, each stated (the configuration's
``assumed`` has the reasons): the gate's bounded form and where ``dt_bias``
enters; the ``128^-0.5`` on q; conv without bias and with SiLU on all three;
the output gate elementwise and sigmoid; the head-wise gate read as the MLA
layers' alone, sigmoid, from the normed input; no multi-token-prediction
module (its published loss weight is 0); no SwiGLU clamp (0 in every kept
layer); no balance loss, and the selection bias a constant; the share
without its exchange. The recurrence runs in blocks of ``SCAN_BLOCK`` tokens,
attention in blocks of queries, the head in blocks of positions, each expert
and each layer a ``jax.checkpoint``, which changes no value; the experts are
dense over the tokens.

``wrong`` (a set of names) makes it the WRONG model in one stated way, for
the calibration of the cell's limits and nothing else: ``scalar_decay`` (one
decay a head, the mean of ``g`` over its channels: Mamba-2's form),
``no_delta`` (the ``beta k k^T`` term dropped: a decayed outer-product
state), ``softplus_gate`` (the unbounded ``-exp(A_log) * softplus(.)``),
``no_k_norm`` (k left as its convolution gives it), ``bf16_state`` (the state
rounded to bf16 after every token), ``no_v_conv`` (v through SiLU alone, no
taps), ``no_mla_gate``, ``no_groups`` (the plain top 8 of ``s'``), ``fp8``
(every matmul's operands rounded to fp8 e4m3's three mantissa bits: the
nearest precision below the bf16 the configuration states). ``bf16`` (the
operands rounded to bf16) is the configuration's OWN precision: required of
nothing.

Weights come as the tree the program holds (``layers_<i>`` with
``operator_norm``, ``ffn_norm``, ``self_attn`` (a KDA layer: ``q_proj``,
``k_proj``, ``v_proj``, ``f_proj``, ``b_proj``, ``g_proj``, ``o_proj``
kernels, ``q/k/v_conv_weight`` ``[4, 4096]``, ``A_log`` ``[32]``, ``dt_bias``
``[4096]``, ``o_norm`` ``[128]``; an MLA layer: Kimi-VL's five and
``gate_proj``), ``mlp`` or ``block_sparse_moe``).
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.kimi_vl import RENORM_EPS, attend, rope_pairs, swiglu
from benchmark.reference.mistral import _HI, rms_norm
from benchmark.reference.sdar_moe import _mm

WRONG = ("scalar_decay", "no_delta", "softplus_gate", "no_k_norm", "bf16_state",
         "no_v_conv", "no_mla_gate", "no_groups", "fp8")
OWN_PRECISION = "bf16"
SCAN_BLOCK = 128     # tokens of the recurrence a checkpoint
SEQ_BLOCKS = 8       # blocks of positions the head's loss runs in
L2_EPS = 1e-6


def delta_recurrence(q, k, v, g, beta, chunk: int, wrong=frozenset()):
    """``q``, ``k``, ``g`` ``[s, H, dk]``, ``v`` ``[s, H, dv]``, ``beta`` ``[s,
    H]`` -> (``o [s, H, dv]``, the largest ``|S|`` over the tokens that end a
    run of ``chunk`` or the sequence): the delta rule token by token."""
    s, H, dk = q.shape
    ends = ((jnp.arange(s) + 1) % chunk == 0).at[s - 1].set(True)

    def token(carry, inp):
        S, top = carry
        qt, kt, vt, gt, bt, end = inp
        S = jnp.exp(gt)[:, :, None] * S
        write = vt if "no_delta" in wrong else vt - jnp.einsum(
            "hkv,hk->hv", S, kt, precision=_HI)
        S = S + (bt[:, None] * kt)[:, :, None] * write[:, None, :]
        if "bf16_state" in wrong:
            S = jax.lax.reduce_precision(S, 8, 7)
        o = jnp.einsum("hkv,hk->hv", S, qt, precision=_HI)
        size = jax.lax.stop_gradient(jnp.max(jnp.abs(S)))
        return (S, jnp.where(end, jnp.maximum(top, size), top)), o

    block = next(b for b in (SCAN_BLOCK, 64, 32, 16, 8, 4, 2, 1) if s % b == 0)

    @jax.checkpoint
    def tokens(carry, inps):
        return jax.lax.scan(token, carry, inps)

    inputs = [a.reshape(s // block, block, *a.shape[1:])
              for a in (q, k, v, g, beta, ends)]
    init = (jnp.zeros((H, dk, v.shape[-1]), jnp.float32), jnp.float32(0.0))
    (_, top), o = jax.lax.scan(tokens, init, inputs)
    return o.reshape(s, H, -1), top


def kda_mixer(h, a, cfg: dict, wrong=frozenset()):
    """``h [s, hidden]`` float32 -> (the mixer's output, ``[state_absmax at the
    chunk ends, mean exp(g), mean beta]``)."""
    H, d = int(cfg["num_attention_heads"]), int(cfg["head_dim"])
    s = h.shape[0]

    def conv_silu(x, w):
        w = w.astype(jnp.float32)                                    # [L, C]
        taps = w.shape[0]
        x = jnp.pad(x, ((taps - 1, 0), (0, 0)))
        return jax.nn.silu(sum(w[j] * x[j:j + s] for j in range(taps)))

    def l2norm(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)

    q = conv_silu(_mm(h, a["q_proj"]["kernel"], wrong), a["q_conv_weight"])
    k = conv_silu(_mm(h, a["k_proj"]["kernel"], wrong), a["k_conv_weight"])
    v = _mm(h, a["v_proj"]["kernel"], wrong)
    v = jax.nn.silu(v) if "no_v_conv" in wrong else conv_silu(v, a["v_conv_weight"])
    q, k, v = (x.reshape(s, H, d) for x in (q, k, v))
    q = l2norm(q) * float(d) ** -0.5
    if "no_k_norm" not in wrong:
        k = l2norm(k)
    rate = jnp.exp(a["A_log"].astype(jnp.float32))[:, None]
    pre = (_mm(h, a["f_proj"]["kernel"], wrong)
           + a["dt_bias"].astype(jnp.float32)).reshape(s, H, d)
    if "softplus_gate" in wrong:
        g = -rate * jax.nn.softplus(pre)
    else:
        g = float(cfg["kda_lower_bound"]) * jax.nn.sigmoid(rate * pre)
    if "scalar_decay" in wrong:
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(_mm(h, a["b_proj"]["kernel"], wrong))
    o, top = delta_recurrence(q, k, v, g, beta, int(cfg["kda_chunk_size"]), wrong)
    gate = jax.nn.sigmoid(_mm(h, a["g_proj"]["kernel"], wrong)).reshape(s, H, d)
    y = rms_norm(o, a["o_norm"], float(cfg["rms_norm_eps"])) * gate
    stats = jnp.stack([top, jnp.mean(jnp.exp(g)), jnp.mean(beta)])
    return _mm(y.reshape(s, H * d), a["o_proj"]["kernel"], wrong), stats


def latent_attention(h, a, cfg: dict, q_block: int, wrong=frozenset()):
    """``h [s, hidden]`` -> the gated latent attention's output."""
    H, rank = int(cfg["num_attention_heads"]), int(cfg["kv_lora_rank"])
    nope, rope, dv = (int(cfg[k]) for k in ("qk_nope_head_dim", "qk_rope_head_dim",
                                            "v_head_dim"))
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    s = h.shape[0]
    positions = jnp.arange(s)[None]
    q = _mm(h, a["q_proj"]["kernel"], wrong).reshape(1, s, H, nope + rope)
    kva = _mm(h, a["kv_a_proj_with_mqa"]["kernel"], wrong)
    c, k_r = kva[..., :rank], kva[..., rank:].reshape(1, s, 1, rope)
    c = rms_norm(c, a["kv_a_layernorm"]["weight"], eps)
    kvb = _mm(c, a["kv_b_proj"]["kernel"], wrong).reshape(1, s, H, nope + dv)
    q = jnp.concatenate([q[..., :nope], rope_pairs(q[..., nope:], positions, theta)], -1)
    k = jnp.concatenate([kvb[..., :nope], jnp.broadcast_to(
        rope_pairs(k_r, positions, theta), (1, s, H, rope))], -1)
    out = attend(q, k, kvb[..., nope:], 1.0 / float(np.sqrt(nope + rope)), q_block,
                 wrong)[0].reshape(s, H, dv)
    if "no_mla_gate" not in wrong:
        out = out * jax.nn.sigmoid(_mm(h, a["gate_proj"]["kernel"], wrong))[:, :, None]
    return _mm(out.reshape(s, H * dv), a["o_proj"]["kernel"], wrong)


def route(h, moe, cfg: dict, wrong=frozenset()):
    """-> (chosen experts ``[T, k]``, their weights ``[T, k]``, whether a
    token kept each group ``[T, groups]``)."""
    top_k, groups, best = (int(cfg[key]) for key in ("num_experts_per_tok", "n_group",
                                                     "topk_group"))
    s = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32),
                               moe["gate"]["kernel"].astype(jnp.float32), precision=_HI))
    biased = s + moe["expert_bias"].astype(jnp.float32)
    T, E = s.shape
    in_groups = biased.reshape(T, groups, E // groups)
    two_largest = jnp.sort(in_groups, axis=-1)[..., -2:]
    group_score = jnp.sum(two_largest, axis=-1)                       # [T, groups]
    # the ``best`` largest group scores: a group is kept when fewer than
    # ``best`` groups beat it (ties to the lower index, as a top-k breaks them)
    beats = (group_score[:, None, :] > group_score[:, :, None]) | (
        (group_score[:, None, :] == group_score[:, :, None])
        & (jnp.arange(groups)[None, None, :] < jnp.arange(groups)[None, :, None]))
    kept = jnp.sum(beats, axis=-1) < best                             # [T, groups]
    if "no_groups" not in wrong:
        biased = jnp.where(kept[:, :, None], in_groups, -jnp.inf).reshape(T, E)
    chosen = jax.lax.top_k(biased, top_k)[1]
    p = jnp.take_along_axis(s, chosen, axis=1)
    if cfg["norm_topk_prob"]:
        p = p / (jnp.sum(p, axis=1, keepdims=True) + RENORM_EPS)
    return chosen, p * float(cfg["routed_scaling_factor"]), kept


def moe_block(h, moe, cfg: dict, first_expert: int = 0, wrong=frozenset()):
    """h: [tokens, hidden] float32 -> (what the experts held and the shared
    expert give, per-expert assignment counts over the router's width ``[E]``,
    the tokens that kept each group ``[groups]``). Dense over the tokens."""
    E = moe["gate"]["kernel"].shape[1]
    chosen, p, kept = route(h, moe, cfg, wrong)
    picked = chosen[:, :, None] == jnp.arange(E)                      # [T, k, E]
    held = moe["w1"].shape[0]
    weight = jnp.sum(p[:, :, None] * picked[:, :, first_expert:first_expert + held],
                     axis=1)                                          # [T, held]

    @jax.checkpoint
    def one_expert(out, j):
        f = {"gate_proj": {"kernel": moe["w1"][j]}, "up_proj": {"kernel": moe["w3"][j]},
             "down_proj": {"kernel": moe["w2"][j]}}
        return out + swiglu(h, f, wrong) * weight[:, j, None], None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(h), jnp.arange(held))
    if "shared_expert" in moe:
        out = out + jax.checkpoint(functools.partial(swiglu, wrong=wrong))(
            h, moe["shared_expert"])
    return (out, jnp.sum(picked, axis=(0, 1), dtype=jnp.int32),
            jnp.sum(kept, axis=0, dtype=jnp.int32))


def layer(x, lp, cfg: dict, first_expert: int = 0, q_block: int = 256,
          wrong=frozenset()):
    """One layer on ``x [s, hidden]`` -> (the stream after it, a KDA layer's
    statistics ``[3]`` (zeros for an MLA layer), an expert layer's counts
    ``[E]`` and group counts ``[groups]`` (zeros for a dense layer))."""
    eps = float(cfg["rms_norm_eps"])
    h = rms_norm(x, lp["operator_norm"]["weight"], eps)
    if "f_proj" in lp["self_attn"]:
        mixed, stats = kda_mixer(h, lp["self_attn"], cfg, wrong)
    else:
        mixed, stats = latent_attention(h, lp["self_attn"], cfg, q_block, wrong), None
    r = x + mixed
    h = rms_norm(r, lp["ffn_norm"]["weight"], eps)
    if "mlp" in lp:
        return r + jax.checkpoint(functools.partial(swiglu, wrong=wrong))(
            h, lp["mlp"]), stats, None
    out, counts, groups = moe_block(h, lp["block_sparse_moe"], cfg, first_expert, wrong)
    return r + out, stats, (counts, groups)


def hidden_states(params, ids, cfg: dict, first_expert: int = 0, wrong=frozenset()):
    """``ids [s]`` -> (final-norm hidden states ``[s, hidden]`` float32, the
    expert layers' counts ``[expert layers, E]`` and group counts ``[expert
    layers, groups]``, the KDA layers' statistics ``[kda layers, 3]``)."""
    m = params["model"]
    x = jnp.take(m["embed_tokens"]["embedding"], ids, axis=0).astype(jnp.float32)
    counts, groups, stats = [], [], []
    for i in range(int(cfg["num_hidden_layers"])):
        one = jax.checkpoint(functools.partial(layer, cfg=cfg, first_expert=first_expert,
                                               wrong=wrong))
        x, stat, routed = one(x, m[f"layers_{i}"])
        if stat is not None:
            stats.append(stat)
        if routed is not None:
            counts.append(routed[0])
            groups.append(routed[1])
    return (rms_norm(x, m["norm"]["weight"], float(cfg["rms_norm_eps"])),
            jnp.stack(counts), jnp.stack(groups), jnp.stack(stats))


def _sequence_nll(params, ids, at, cfg: dict, first_expert: int, wrong,
                  loss_positions: int):
    """One sequence ``ids [seq]`` -> (the sum of the next-token losses of its
    first ``loss_positions`` positions (all where 0), (counts, group counts,
    the logits ``[len(at), vocab]`` at positions ``at``, KDA statistics))."""
    x, counts, groups, stats = hidden_states(params, ids, cfg, first_expert, wrong)
    head = params["model"]["lm_head"]["kernel"]
    seq = ids.shape[0]
    targets = jnp.concatenate([ids[1:], ids[:1]])
    counted = jnp.arange(seq) < (loss_positions or seq - 1)
    counted = counted & (jnp.arange(seq) < seq - 1)
    blocks = SEQ_BLOCKS if seq % SEQ_BLOCKS == 0 else 1

    @jax.checkpoint
    def block(args):
        xb, tb, wb = args
        lg = _mm(xb, head, wrong)
        gold = jnp.take_along_axis(lg, tb[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(lg, axis=-1) - gold) * wb)

    split = lambda a: a.reshape(blocks, seq // blocks, *a.shape[1:])     # noqa: E731
    nll = jnp.sum(jax.lax.map(block, (split(x), split(targets),
                                      split(counted.astype(jnp.float32)))))
    return nll, (counts, groups, _mm(x[at], head, wrong), stats)


@functools.lru_cache(maxsize=None)
def _compiled_pass(cfg_json: str, first_expert: int, wrong: frozenset, gradients: bool,
                   loss_positions: int):
    fn = functools.partial(_sequence_nll, cfg=json.loads(cfg_json),
                           first_expert=first_expert, wrong=wrong,
                           loss_positions=loss_positions)
    return jax.jit(jax.value_and_grad(fn, has_aux=True) if gradients else fn)


KEYS = ("num_hidden_layers", "num_attention_heads", "head_dim", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rms_norm_eps",
        "rope_theta", "num_experts_per_tok", "n_group", "topk_group", "norm_topk_prob",
        "routed_scaling_factor", "kda_lower_bound", "kda_chunk_size")


def step_parts(params, ids, cfg: dict, at, first_expert: int = 0, wrong=frozenset(),
               gradients: bool = True, loss_positions: int = 0,
               one_program: bool = False) -> dict:
    """What one training step on ``ids [rows, seq]`` has to reproduce, one
    sequence at a time and each a single compiled pass: ``ce`` (the token-mean
    next-token loss; over each sequence's first ``loss_positions`` positions
    where that is not 0), ``counts`` ``[E]`` (assignments over the router's
    width, summed over the expert layers), ``group_counts`` ``[groups]`` (the
    tokens that kept each group, summed alike), ``rows_held``, ``grads``
    (``jax.grad`` of ``ce``, numpy float32; None without ``gradients``),
    ``logits`` ``[rows, n, vocab]`` at each sequence's positions ``at[row]``,
    and the KDA layers' ``state_absmax`` (the largest), ``decay_mean`` and
    ``beta_mean`` (the layers' and sequences' means). ``one_program``: a pass
    without ``gradients`` runs the gradients' program and drops them (on the
    chip a forward-only program of the cell's size compiles for 50 s more than
    its backward runs)."""
    differentiated = gradients or one_program
    fn = _compiled_pass(json.dumps({k: cfg[k] for k in KEYS}), first_expert,
                        frozenset(wrong), differentiated, int(loss_positions))
    ids = np.asarray(ids)
    rows, seq = ids.shape
    tokens = rows * (min(loss_positions, seq - 1) if loss_positions else seq - 1)
    with jax.default_matmul_precision("highest"):
        nll, counts, groups, grads, logits, stats = 0.0, 0, 0, None, [], []
        for row in range(rows):
            out = fn(params, jnp.asarray(ids[row]), jnp.asarray(at[row]))
            (part, (count, group, lg, stat)), grad = out if differentiated else (out, None)
            nll += float(part)
            counts = counts + np.asarray(jnp.sum(count, axis=0))
            groups = groups + np.asarray(jnp.sum(group, axis=0))
            stats.append(np.asarray(stat, np.float64))
            logits.append(np.asarray(lg))
            if gradients:
                grad = jax.tree_util.tree_map(lambda g: np.asarray(g) / tokens, grad)
                grads = grad if grads is None else jax.tree_util.tree_map(
                    np.add, grads, grad)
    held = next(lp["block_sparse_moe"]["w1"].shape[0]
                for lp in params["model"].values() if "block_sparse_moe" in lp)
    stats = np.stack(stats)                                     # [rows, kda layers, 3]
    return {"ce": nll / tokens, "counts": counts, "group_counts": groups, "grads": grads,
            "rows_held": int(counts[first_expert:first_expert + held].sum()),
            "logits": np.stack(logits), "state_absmax": float(stats[..., 0].max()),
            "decay_mean": float(stats[..., 1].mean()),
            "beta_mean": float(stats[..., 2].mean())}
