"""Plain reference of Qwen3-Next (``model_type: qwen3_next``) under next-token
training: forward pass, loss, its gradients, the routing and the
linear-attention layers' statistics.

Straightforward ``jax.numpy`` in float32 with the matmul precision at
"highest". No kernels, no chunked scan, no sort, no grouped matmul, and
nothing imported from the program under test; :func:`step_parts` gives loss,
gradients, counts and chosen positions' logits of one training batch, one
sequence at a time. Every RMSNorm but the linear layers' output norm is
zero-centred, ``zrms(x, w) = x / rms(x) * (1 + w)``. Layer ``i`` on a token's
stream ``x`` (hidden 2,048):

    r = x + Mixer(zrms(x, operator_norm));  out = r + MoE(zrms(r, ffn_norm))

- ``Mixer``, a ``"gdn"`` layer (Gated DeltaNet: 16 key heads and 32 value
  heads of 128): ``q | k | v | z = W_qkvz h`` and ``b | a = W_ba h`` (the
  columns kind by kind, as the program's tree holds them); ``q | k | v``
  through ONE convolution of 4 causal depthwise taps (no bias) and SiLU; per
  head ``q = l2norm(q) / sqrt(128)``, ``k = l2norm(k)`` (``l2norm(x) = x /
  sqrt(sum x^2 + 1e-6)``); value head ``h`` reads key head ``h // 2``; ``beta
  = sigmoid(b)``, ``g = -exp(A_log_h) * softplus(a + dt_bias_h)``, one a value
  head and token; the state ``S`` (128 x 128 a value head, zero before the
  first token) TOKEN BY TOKEN, never in chunks: ``S <- exp(g_t) S; S <- S +
  beta_t k_t (v_t - S^T k_t)^T; o_t = S^T q_t``; ``y = W_o [rms_128(o_h) * w *
  silu(z_h)]_h`` (``w`` as it is).
- ``Mixer``, an ``"attention"`` layer: ``W_q h`` -> 16 heads' queries and
  their gates (256 each), ``W_k h``, ``W_v h`` -> 2 heads of 256; ``q =
  zrms_256(q, q_norm)``, ``k = zrms_256(k, k_norm)`` per head; half-split
  rotary at theta 1e7 on lanes 0-63 of 256, the rest untouched; causal softmax
  of ``q k^T / 16``, eight query heads a key head; ``W_o (attn *
  sigmoid(gate))``.
- ``MoE``: ``p = softmax(h W_r)`` in float32 over the router's 512 experts;
  the top 10; weights ``p_e / sum_chosen p``; ``sum_e w_e SwiGLU_e(h)`` over
  the chosen experts, 512 wide; plus ``sigmoid(h w_s) * SwiGLU_shared(h)``.
- a final zero-centred norm, an untied head, token-mean next-token
  cross-entropy.

The chip's share, as ``reference/kimi_vl.py``: the experts held are those
whose matrices the tree has, a chosen expert that is not held adds nothing
(the normaliser is still over all ten chosen), a sliced vocabulary is a
smaller vocabulary.

Departures from the published description, each stated (the configuration's
``assumed`` has the reasons): no multi-token-prediction module, no balance
loss, the share without its exchange. The recurrence runs in blocks of
``SCAN_BLOCK`` tokens, attention in blocks of queries, the head in blocks of
positions, each expert and each layer a ``jax.checkpoint``, which changes no
value; the experts are dense over the tokens.

``wrong`` (a set of names) makes it the WRONG model in one stated way, for
the calibration of the cell's limits and nothing else: ``no_softplus`` (``g =
-exp(A_log) * (a + dt_bias)``), ``beta_doubled`` (``2 sigmoid(b)``),
``no_key_repeat`` (value head ``h`` reading key head ``h % 16``: the sixteen
tiled, not each repeated), ``sigmoid_out`` (a sigmoid for the SiLU in the
output norm), ``norm_plus_one`` (``1 + w`` in the GDN output norm),
``rope_all`` (the rotary embedding over all 256 lanes), ``no_attn_gate``,
``ungated_shared``, ``no_renorm`` (the top 10 weighted by ``p`` as it is),
``bf16_state`` (the state rounded to bf16 after every token), ``fp8`` (every
matmul's operands rounded to fp8 e4m3's three mantissa bits: the nearest
precision below the bf16 the configuration states). ``bf16`` (the operands
rounded to bf16) is the configuration's OWN precision. The cell's limits tell
every one of them from the sound model but ``NOT_TOLD``.

Weights come as the tree the program holds (``layers_<i>`` with
``operator_norm``, ``ffn_norm``, ``self_attn`` (a GDN layer: ``in_proj_qkvz``
``[2048, 12288]``, ``in_proj_ba`` ``[2048, 64]``, ``out_proj`` kernels,
``conv_weight`` ``[4, 8192]``, ``A_log``, ``dt_bias`` ``[32]``,
``norm_weight`` ``[128]``; an attention layer: ``q_proj`` ``[2048, 8192]``
(queries, then gates), ``k_proj``, ``v_proj``, ``o_proj``, ``q_norm``,
``k_norm``), ``block_sparse_moe`` (``gate``, ``w1`` / ``w3`` / ``w2`` of the
experts held, ``shared_expert``, ``shared_expert_gate``)).
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.kimi_vl import swiglu
from benchmark.reference.mistral import _HI, _rope, rms_norm
from benchmark.reference.sdar_moe import _mm, _operand

WRONG = ("no_softplus", "beta_doubled", "no_key_repeat", "sigmoid_out", "norm_plus_one",
         "rope_all", "no_attn_gate", "ungated_shared", "no_renorm", "bf16_state", "fp8")
OWN_PRECISION = "bf16"
# wrong and not told apart by the cell at its seeded gate (a state that forgets
# in some six tokens piles no rounding up: the runner's comment has the readings)
NOT_TOLD = ("bf16_state", )
SCAN_BLOCK = 128     # tokens of the recurrence a checkpoint
SEQ_BLOCKS = 8       # blocks of positions the head's loss runs in
L2_EPS = 1e-6


def zrms(x, weight, eps: float):
    """The zero-centred RMSNorm: ``x / rms(x) * (1 + w)``."""
    return rms_norm(x, 1.0 + weight.astype(jnp.float32), eps)


def delta_recurrence(q, k, v, g, beta, chunk: int, wrong=frozenset()):
    """``q``, ``k`` ``[s, H, dk]`` (a key head a value head: already
    repeated), ``v`` ``[s, H, dv]``, ``g``, ``beta`` ``[s, H]`` -> (``o [s, H,
    dv]``, the largest ``|S|`` over the tokens that end a run of ``chunk`` or
    the sequence): the gated delta rule token by token."""
    s, H, dk = q.shape
    ends = ((jnp.arange(s) + 1) % chunk == 0).at[s - 1].set(True)

    def token(carry, inp):
        S, top = carry
        qt, kt, vt, gt, bt, end = inp
        S = jnp.exp(gt)[:, None, None] * S
        seen = jnp.einsum("hkv,hk->hv", S, kt, precision=_HI)
        S = S + (bt[:, None] * kt)[:, :, None] * (vt - seen)[:, None, :]
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


def gdn_mixer(h, a, cfg: dict, wrong=frozenset()):
    """``h [s, hidden]`` float32 -> (the mixer's output, ``[state_absmax at the
    chunk ends, mean exp(g), mean beta]``)."""
    Hk, Hv = int(cfg["linear_num_key_heads"]), int(cfg["linear_num_value_heads"])
    dk, dv = int(cfg["linear_key_head_dim"]), int(cfg["linear_value_head_dim"])
    keys, values, s = Hk * dk, Hv * dv, h.shape[0]
    f32 = jnp.float32

    def l2norm(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)

    qkvz = _mm(h, a["in_proj_qkvz"]["kernel"], wrong)
    ba = _mm(h, a["in_proj_ba"]["kernel"], wrong)
    mixed, z = qkvz[:, :2 * keys + values], qkvz[:, 2 * keys + values:]
    w = a["conv_weight"].astype(f32)                                    # [L, C]
    taps = w.shape[0]
    padded = jnp.pad(mixed, ((taps - 1, 0), (0, 0)))
    mixed = jax.nn.silu(sum(w[j] * padded[j:j + s] for j in range(taps)))
    q = l2norm(mixed[:, :keys].reshape(s, Hk, dk)) * float(dk) ** -0.5
    k = l2norm(mixed[:, keys:2 * keys].reshape(s, Hk, dk))
    v = mixed[:, 2 * keys:].reshape(s, Hv, dv)
    if "no_key_repeat" in wrong:
        q, k = jnp.tile(q, (1, Hv // Hk, 1)), jnp.tile(k, (1, Hv // Hk, 1))
    else:
        q, k = jnp.repeat(q, Hv // Hk, axis=1), jnp.repeat(k, Hv // Hk, axis=1)
    beta = jax.nn.sigmoid(ba[:, :Hv]) * (2.0 if "beta_doubled" in wrong else 1.0)
    pre = ba[:, Hv:] + a["dt_bias"].astype(f32)
    g = -jnp.exp(a["A_log"].astype(f32)) * (pre if "no_softplus" in wrong
                                             else jax.nn.softplus(pre))
    o, top = delta_recurrence(q, k, v, g, beta, int(cfg["gdn_chunk_size"]), wrong)
    z = z.reshape(s, Hv, dv)
    weight = a["norm_weight"].astype(f32) + (1.0 if "norm_plus_one" in wrong else 0.0)
    y = rms_norm(o, weight, float(cfg["rms_norm_eps"])) * (
        jax.nn.sigmoid(z) if "sigmoid_out" in wrong else jax.nn.silu(z))
    stats = jnp.stack([top, jnp.mean(jnp.exp(g)), jnp.mean(beta)])
    return _mm(y.reshape(s, values), a["out_proj"]["kernel"], wrong), stats


def attend(q, k, v, scale: float, q_block: int, wrong=frozenset()):
    """``q [s, H, d]``, ``k``, ``v`` ``[s, KV, d]`` -> ``[s, H * d]``: causal
    softmax attention, ``H / KV`` query heads a key head, one block of
    ``q_block`` queries after another (``lax.map``: a backward pass holds one
    block's scores; one block where ``q_block`` does not divide the sequence)."""
    s, H, d = q.shape
    KV = k.shape[1]
    if s % q_block:
        q_block = s

    @jax.checkpoint
    def block_of_queries(args):
        qb, first = args                                        # [q_block, KV, G, d]
        seen = jnp.arange(s)[None, :] <= first + jnp.arange(q_block)[:, None]
        scores = jnp.einsum("qkgd,lkd->kgql", _operand(qb, wrong), _operand(k, wrong),
                            precision=_HI) * scale
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("kgql,lkd->qkgd", _operand(probs, wrong), _operand(v, wrong),
                          precision=_HI)

    blocks = q.reshape(s // q_block, q_block, KV, H // KV, d)
    outs = jax.lax.map(block_of_queries, (blocks, jnp.arange(0, s, q_block)))
    return outs.reshape(s, H * d)


def gated_attention(h, a, cfg: dict, q_block: int, wrong=frozenset()):
    """``h [s, hidden]`` -> (the gated attention's output, the mean of
    ``sigmoid(gate)``)."""
    H, KV, d = (int(cfg[key]) for key in ("num_attention_heads", "num_key_value_heads",
                                          "head_dim"))
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    s = h.shape[0]
    qg = _mm(h, a["q_proj"]["kernel"], wrong)
    q, gate = qg[:, :H * d].reshape(s, H, d), jax.nn.sigmoid(qg[:, H * d:])
    k = _mm(h, a["k_proj"]["kernel"], wrong).reshape(s, KV, d)
    v = _mm(h, a["v_proj"]["kernel"], wrong).reshape(s, KV, d)
    q, k = zrms(q, a["q_norm"]["weight"], eps), zrms(k, a["k_norm"]["weight"], eps)
    rot = d if "rope_all" in wrong else int(d * float(cfg["partial_rotary_factor"]))
    positions = jnp.arange(s)[None]

    def turned(x):
        return jnp.concatenate([_rope(x[None, ..., :rot], positions, theta)[0],
                                x[..., rot:]], axis=-1)

    out = attend(turned(q), turned(k), v, 1.0 / float(np.sqrt(d)), q_block, wrong)
    if "no_attn_gate" not in wrong:
        out = out * gate
    return _mm(out, a["o_proj"]["kernel"], wrong), jnp.mean(gate)


def route(h, moe, cfg: dict, wrong=frozenset()):
    """-> (chosen experts ``[T, k]``, their weights ``[T, k]``)."""
    p = jax.nn.softmax(jnp.dot(h.astype(jnp.float32),
                               moe["gate"]["kernel"].astype(jnp.float32), precision=_HI),
                       axis=-1)
    w, chosen = jax.lax.top_k(p, int(cfg["num_experts_per_tok"]))
    if cfg["norm_topk_prob"] and "no_renorm" not in wrong:
        w = w / jnp.sum(w, axis=1, keepdims=True)
    return chosen, w


def moe_block(h, moe, cfg: dict, first_expert: int = 0, wrong=frozenset()):
    """h: [tokens, hidden] float32 -> (what the experts held and the gated
    shared expert give, per-expert assignment counts over the router's width
    ``[E]``). Dense over the tokens."""
    E = moe["gate"]["kernel"].shape[1]
    chosen, p = route(h, moe, cfg, wrong)
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

    @jax.checkpoint
    def shared(h):
        y = swiglu(h, moe["shared_expert"], wrong)
        if "ungated_shared" in wrong:
            return y
        return y * jax.nn.sigmoid(jnp.dot(
            h, moe["shared_expert_gate"]["kernel"].astype(jnp.float32), precision=_HI))

    return out + shared(h), jnp.sum(picked, axis=(0, 1), dtype=jnp.int32)


def layer(x, lp, cfg: dict, first_expert: int = 0, q_block: int = 256,
          wrong=frozenset()):
    """One layer on ``x [s, hidden]`` -> (the stream after it, ``[4]``: a GDN
    layer's statistics and 0, or three zeros and an attention layer's mean
    gate; the expert counts ``[E]``)."""
    eps = float(cfg["rms_norm_eps"])
    h = zrms(x, lp["operator_norm"]["weight"], eps)
    if "in_proj_qkvz" in lp["self_attn"]:
        mixed, stats = gdn_mixer(h, lp["self_attn"], cfg, wrong)
        stats = jnp.concatenate([stats, jnp.zeros((1, ), jnp.float32)])
    else:
        mixed, gate = gated_attention(h, lp["self_attn"], cfg, q_block, wrong)
        stats = jnp.zeros((4, ), jnp.float32).at[3].set(gate)
    r = x + mixed
    out, counts = moe_block(zrms(r, lp["ffn_norm"]["weight"], eps),
                            lp["block_sparse_moe"], cfg, first_expert, wrong)
    return r + out, stats, counts


def hidden_states(params, ids, cfg: dict, first_expert: int = 0, wrong=frozenset()):
    """``ids [s]`` -> (final-norm hidden states ``[s, hidden]`` float32, the
    layers' expert counts ``[layers, E]``, the layers' statistics ``[layers,
    4]`` with whether each is a GDN layer ``[layers]``)."""
    m = params["model"]
    x = jnp.take(m["embed_tokens"]["embedding"], ids, axis=0).astype(jnp.float32)
    counts, stats = [], []
    for i in range(int(cfg["num_hidden_layers"])):
        one = jax.checkpoint(functools.partial(layer, cfg=cfg, first_expert=first_expert,
                                               wrong=wrong))
        x, stat, count = one(x, m[f"layers_{i}"])
        stats.append(stat)
        counts.append(count)
    return (zrms(x, m["norm"]["weight"], float(cfg["rms_norm_eps"])),
            jnp.stack(counts), jnp.stack(stats))


def _sequence_nll(params, ids, at, cfg: dict, first_expert: int, wrong):
    """One sequence ``ids [seq]`` -> (the sum of its next-token losses,
    (counts, the logits ``[len(at), vocab]`` at positions ``at``, the layers'
    statistics))."""
    x, counts, stats = hidden_states(params, ids, cfg, first_expert, wrong)
    head = params["model"]["lm_head"]["kernel"]
    seq = ids.shape[0]
    targets = jnp.concatenate([ids[1:], ids[:1]])
    counted = jnp.arange(seq) < seq - 1
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
    return nll, (counts, _mm(x[at], head, wrong), stats)


@functools.lru_cache(maxsize=None)
def _compiled_pass(cfg_json: str, first_expert: int, wrong: frozenset, gradients: bool):
    fn = functools.partial(_sequence_nll, cfg=json.loads(cfg_json),
                           first_expert=first_expert, wrong=wrong)
    return jax.jit(jax.value_and_grad(fn, has_aux=True) if gradients else fn)


KEYS = ("num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim",
        "partial_rotary_factor", "rms_norm_eps", "rope_theta", "num_experts_per_tok",
        "norm_topk_prob", "linear_num_key_heads", "linear_num_value_heads",
        "linear_key_head_dim", "linear_value_head_dim", "gdn_chunk_size")


def step_parts(params, ids, cfg: dict, at, first_expert: int = 0, wrong=frozenset(),
               gradients: bool = True, one_program: bool = False) -> dict:
    """What one training step on ``ids [rows, seq]`` has to reproduce, one
    sequence at a time and each a single compiled pass: ``ce`` (the token-mean
    next-token loss), ``counts`` ``[E]`` (assignments over the router's width,
    summed over the layers), ``rows_held``, ``grads`` (``jax.grad`` of ``ce``,
    numpy float32; None without ``gradients``), ``logits`` ``[rows, n,
    vocab]`` at each sequence's positions ``at[row]``, the GDN layers'
    ``state_absmax`` (the largest), ``decay_mean`` and ``beta_mean`` and the
    attention layers' ``gate_mean`` (the layers' and sequences' means).
    ``one_program``: a pass without ``gradients`` runs the gradients' program
    and drops them (one compilation fewer on the chip)."""
    differentiated = gradients or one_program
    fn = _compiled_pass(json.dumps({k: cfg[k] for k in KEYS}), first_expert,
                        frozenset(wrong), differentiated)
    ids = np.asarray(ids)
    rows, seq = ids.shape
    tokens = rows * (seq - 1)
    with jax.default_matmul_precision("highest"):
        nll, counts, grads, logits, stats = 0.0, 0, None, [], []
        for row in range(rows):
            out = fn(params, jnp.asarray(ids[row]), jnp.asarray(at[row]))
            (part, (count, lg, stat)), grad = out if differentiated else (out, None)
            nll += float(part)
            counts = counts + np.asarray(jnp.sum(count, axis=0))
            stats.append(np.asarray(stat, np.float64))
            logits.append(np.asarray(lg))
            if gradients:
                grad = jax.tree_util.tree_map(lambda g: np.asarray(g) / tokens, grad)
                grads = grad if grads is None else jax.tree_util.tree_map(
                    np.add, grads, grad)
    layers = params["model"]
    held = next(lp["block_sparse_moe"]["w1"].shape[0]
                for lp in layers.values() if "block_sparse_moe" in lp)
    is_gdn = np.array(["in_proj_qkvz" in layers[f"layers_{i}"]["self_attn"]
                       for i in range(int(cfg["num_hidden_layers"]))])
    stats = np.stack(stats)                                     # [rows, layers, 4]
    gdn, attn = stats[:, is_gdn], stats[:, ~is_gdn]
    return {"ce": nll / tokens, "counts": counts, "grads": grads,
            "rows_held": int(counts[first_expert:first_expert + held].sum()),
            "logits": np.stack(logits), "state_absmax": float(gdn[..., 0].max()),
            "decay_mean": float(gdn[..., 1].mean()), "beta_mean": float(gdn[..., 2].mean()),
            "gate_mean": float(attn[..., 3].mean()) if attn.size else float("nan")}
