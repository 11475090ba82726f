"""Plain reference of the Granite 4.0-H dense hybrid: forward pass, loss and
gradients.

Straightforward ``jax.numpy`` in float32 with the matmul precision at
"highest". No kernels, no chunked scan, nothing imported from the program
under test; ``jax.grad`` of the loss gives the step's gradients. It follows
``transformers``' ``modeling_granitemoehybrid.py`` and the published
``config.json``:

    x = embed(ids) * embedding_multiplier
    h = x + residual_multiplier * mixer_i(rms(x, operator_norm))
    out = h + residual_multiplier * shared_mlp(rms(h, ffn_norm))
    logits = (rms(x, norm) embed^T) / logits_scaling           (tied)

- ``shared_mlp``: ``W_down(silu(W_gate h) * W_up h)`` (``input_linear``'s
  first half is the gate).
- ``mixer_i``, ``layer_types[i] == "attention"``: grouped-query attention,
  no position embedding, no bias, scores times ``attention_multiplier``,
  causal softmax.
- ``mixer_i``, ``"mamba"``: ``z | xBC | dt = split(W_in h, [d_inner, d_inner
  + 2 N, heads])``; ``xBC = silu(conv(xBC) + bias)`` (depthwise, causal,
  ``mamba_d_conv`` taps, ``w[j]`` multiplies ``xBC[t - (L - 1) + j]``, zeros
  left of the sequence); ``x | B | C = split(xBC)``, ``x`` as heads of
  ``mamba_d_head``; ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; per
  head, one token after another in a ``lax.scan``,

      S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,   y_t = S_t C_t + D x_t

  with ``S`` ``[d_head, N]`` float32, zero before the first token; then
  ``rms(y * silu(z)) * w`` over all ``d_inner`` values and ``W_out``.
- token-mean cross-entropy with the shift by one. A sliced vocabulary is a
  smaller vocabulary: the embedding has that many rows.

Departures, each stated; none changes a value:
- the recurrence runs in blocks of ``SCAN_BLOCK`` tokens, each a
  ``jax.checkpoint``, so that a backward pass holds a block's states (128 x
  2 MB) and not all 16,384; there are no chunks in the arithmetic: no decay
  matrix, no state passed other than from a token to the next;
- attention runs in blocks of queries, the FFN and the head in blocks of
  the sequence, every block and every layer a ``jax.checkpoint``;
- one sequence at a time.

It also reports the largest ``|S|`` any token left, and the largest over
the tokens that end a run of ``mamba_chunk_size`` (a statistic sampled there,
because those are the states the program's kernels keep and can report).

``wrong`` (a set of names) makes it the WRONG model in one stated way, for
the calibration of the cell's limits and nothing else: ``bf16_state`` (the
state rounded to bf16 after every token), ``bf16_decay`` (``exp(dt A)``
rounded to bf16), ``no_softplus``, ``no_residual_multiplier``, ``rope``
(rotary embedding at ``rope_theta`` on q and k), ``no_carry`` (the state
dropped where a chunk ends).

``initialisation_readings`` checks, in numpy, that seeded weights are drawn
as the configuration's ``assumed`` says (the seeded weights come from the
program's own initialisers, so this side states the rule and measures it).

Weights come as the tree the program holds (``{"model": {"embed_tokens":
{"embedding"}, "layers_<i>": {"operator_norm", "ffn_norm", "mamba":
{in_proj, conv_weight [L, C], conv_bias, dt_bias, A_log, D, norm_weight,
out_proj} or "self_attn": {q_proj, k_proj, v_proj, o_proj}, "mlp":
{gate_proj, up_proj, down_proj}}, "norm"}}``; kernels ``[in, out]``).
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

# the dense decoder's plain pieces, shared as they are
from benchmark.reference.mistral import _HI, _mm, _rope, rms_norm

SCAN_BLOCK = 128     # tokens of the recurrence a checkpoint
SEQ_BLOCKS = 4       # blocks of the sequence the FFN and the head run in


def _in_blocks(fn, x, blocks: int):
    """``fn`` over ``blocks`` equal row blocks of ``x [rows, ...]``, each a
    checkpoint (one block where ``blocks`` does not divide the rows)."""
    rows = x.shape[0]
    if rows % blocks:
        blocks = 1
    out = jax.lax.map(jax.checkpoint(fn), x.reshape(blocks, rows // blocks, *x.shape[1:]))
    return out.reshape(rows, *out.shape[2:])


def attend(q, k, v, scale: float, q_block: int = 512):
    """q ``[s, H, d]``, k/v ``[s, KV, d]`` -> ``[s, H * d]``: causal softmax
    of ``scale * q k^T``, grouped queries, a block of queries at a time."""
    s, H, d = q.shape
    if s % q_block:
        q_block = s
    k = jnp.repeat(k, H // k.shape[1], axis=1)
    v = jnp.repeat(v, H // v.shape[1], axis=1)

    @jax.checkpoint
    def block(args):
        qb, first = args
        seen = jnp.arange(s)[None, :] <= first + jnp.arange(q_block)[:, None]
        scores = jnp.einsum("qhd,khd->hqk", qb, k, precision=_HI) * scale
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v, precision=_HI)

    outs = jax.lax.map(block, (q.reshape(s // q_block, q_block, H, d),
                               jnp.arange(0, s, q_block)))
    return outs.reshape(s, H * d)


def _as_bf16(x):
    """Rounded to bf16's 8 bits and kept in float32. ``reduce_precision``
    and not a pair of casts: on a TPU XLA drops a cast to bf16 and back
    (``xla_allow_excess_precision``), and the wrong model would be sound."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def recurrence(x, dt, A, B, C, D, chunk: int, wrong=frozenset()):
    """``x [s, H, P]``, ``dt [s, H]``, ``A``, ``D`` ``[H]``, ``B``, ``C``
    ``[s, N]`` -> (``y [s, H, P]``, the largest ``|S|`` over all tokens, the
    largest over the tokens that end a run of ``chunk``)."""
    s, H, P = x.shape
    ends = (jnp.arange(s) + 1) % chunk == 0
    ends = ends.at[s - 1].set(True)

    def token(carry, inp):
        S, top, top_ends = carry
        xt, dtt, Bt, Ct, end = inp
        decay = jnp.exp(dtt * A)
        if "bf16_decay" in wrong:
            decay = _as_bf16(decay)
        S = decay[:, None, None] * S + (dtt[:, None] * xt)[:, :, None] * Bt
        if "bf16_state" in wrong:
            S = _as_bf16(S)
        y = jnp.einsum("hpn,n->hp", S, Ct, precision=_HI) + D[:, None] * xt
        size = jax.lax.stop_gradient(jnp.max(jnp.abs(S)))
        carry = (jnp.where(end, 0.0, S) if "no_carry" in wrong else S,
                 jnp.maximum(top, size),
                 jnp.where(end, jnp.maximum(top_ends, size), top_ends))
        return carry, y

    block = next(b for b in (SCAN_BLOCK, 64, 32, 16, 8, 4, 2, 1) if s % b == 0)

    @jax.checkpoint
    def tokens(carry, inps):
        return jax.lax.scan(token, carry, inps)

    inputs = [a.reshape(s // block, block, *a.shape[1:]) for a in (x, dt, B, C, ends)]
    init = (jnp.zeros((H, P, B.shape[-1]), jnp.float32), jnp.float32(0.0),
            jnp.float32(0.0))
    (_, top, top_ends), y = jax.lax.scan(tokens, init, inputs)
    return y.reshape(s, H, P), top, top_ends


def mamba_mixer(h, mp, cfg: dict, wrong=frozenset()):
    """``h [s, hidden]`` float32 -> (the mixer's output, largest ``|S|``,
    largest ``|S|`` at the chunk ends, mean ``dt``)."""
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    inner = H * P
    s = h.shape[0]
    z, xbc, dt = jnp.split(_mm(h, mp["in_proj"]["kernel"]),
                           [inner, 2 * inner + 2 * N], axis=-1)
    w = mp["conv_weight"].astype(jnp.float32)                        # [L, C]
    taps = w.shape[0]
    v = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    xbc = sum(w[j] * v[j:j + s] for j in range(taps))
    if "conv_bias" in mp:
        xbc = xbc + mp["conv_bias"].astype(jnp.float32)
    xbc = jax.nn.silu(xbc)
    x, B, C = jnp.split(xbc, [inner, inner + N], axis=-1)
    dt = dt + mp["dt_bias"].astype(jnp.float32)
    if "no_softplus" not in wrong:
        dt = jax.nn.softplus(dt)
    y, top, top_ends = recurrence(
        x.reshape(s, H, P), dt, -jnp.exp(mp["A_log"].astype(jnp.float32)), B, C,
        mp["D"].astype(jnp.float32), int(cfg["mamba_chunk_size"]), wrong)
    y = rms_norm(y.reshape(s, inner) * jax.nn.silu(z), mp["norm_weight"],
                 float(cfg["rms_norm_eps"]))
    return _mm(y, mp["out_proj"]["kernel"]), top, top_ends, jnp.mean(dt)


def swiglu(h, f):
    def block(rows):
        return _mm(jax.nn.silu(_mm(rows, f["gate_proj"]["kernel"]))
                   * _mm(rows, f["up_proj"]["kernel"]), f["down_proj"]["kernel"])
    return _in_blocks(block, h, SEQ_BLOCKS)


def _layer(x, lp, i: int, cfg: dict, wrong):
    """Layer ``i`` on one sequence ``x [s, hidden]`` -> (the stream after it,
    (largest |S|, largest at chunk ends, mean dt) or zeros for attention)."""
    eps = float(cfg["rms_norm_eps"])
    scale = 1.0 if "no_residual_multiplier" in wrong else float(cfg["residual_multiplier"])
    h = rms_norm(x, lp["operator_norm"]["weight"], eps)
    stats = (jnp.float32(0.0), ) * 3
    if cfg["layer_types"][i] == "mamba":
        out, *stats = mamba_mixer(h, lp["mamba"], cfg, wrong)
    else:
        a = lp["self_attn"]
        heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        s, d = x.shape[0], cfg["hidden_size"] // cfg["num_attention_heads"]
        q = _mm(h, a["q_proj"]["kernel"]).reshape(s, heads, d)
        k = _mm(h, a["k_proj"]["kernel"]).reshape(s, kv, d)
        v = _mm(h, a["v_proj"]["kernel"]).reshape(s, kv, d)
        if "rope" in wrong:
            positions = jnp.arange(s)[None]
            q = _rope(q[None], positions, float(cfg["rope_theta"]))[0]
            k = _rope(k[None], positions, float(cfg["rope_theta"]))[0]
        out = _mm(attend(q, k, v, float(cfg["attention_multiplier"])),
                  a["o_proj"]["kernel"])
    x = x + scale * out
    h = rms_norm(x, lp["ffn_norm"]["weight"], eps)
    return x + scale * swiglu(h, lp["mlp"]), tuple(stats)


def hidden_states(params, ids, cfg: dict, wrong=frozenset()):
    """One sequence ``ids [s]`` -> (final-norm hidden states ``[s, hidden]``,
    largest ``|S|``, largest at the chunk ends, mean ``dt`` over the Mamba
    layers)."""
    m = params["model"]
    x = jnp.take(m["embed_tokens"]["embedding"], ids, axis=0).astype(jnp.float32)
    x = x * float(cfg["embedding_multiplier"])
    tops, tops_ends, dts = [], [], []
    for i, kind in enumerate(cfg["layer_types"]):
        layer = jax.checkpoint(functools.partial(_layer, i=i, cfg=cfg, wrong=wrong))
        x, (top, top_ends, dt) = layer(x, m[f"layers_{i}"])
        if kind == "mamba":
            tops.append(top)
            tops_ends.append(top_ends)
            dts.append(dt)
    return (rms_norm(x, m["norm"]["weight"], float(cfg["rms_norm_eps"])),
            jnp.max(jnp.stack(tops)), jnp.max(jnp.stack(tops_ends)),
            jnp.mean(jnp.stack(dts)))


def _sequence_nll(params, ids, positions, cfg: dict, wrong):
    """One sequence ``ids [s]`` -> (the sum of its next-token losses,
    (logits ``[positions, vocab]``, largest |S|, at chunk ends, mean dt))."""
    x, top, top_ends, dt = hidden_states(params, ids, cfg, wrong)
    head = params["model"]["embed_tokens"]["embedding"].T
    scaling = float(cfg["logits_scaling"])

    def block(rows):
        xs, gold = rows
        lg = _mm(xs, head) / scaling
        return (jax.nn.logsumexp(lg, axis=-1)
                - jnp.take_along_axis(lg, gold[:, None], axis=-1)[:, 0])

    s = ids.shape[0]
    # the last position predicts nothing: its label is a filler, its loss dropped
    gold = jnp.concatenate([ids[1:], ids[:1]])
    blocks = SEQ_BLOCKS if s % SEQ_BLOCKS == 0 else 1
    nll = jax.lax.map(jax.checkpoint(block),
                      (x.reshape(blocks, s // blocks, -1), gold.reshape(blocks, -1)))
    nll = nll.reshape(s)[:-1]
    return jnp.sum(nll), (_mm(x[positions], head) / scaling, top, top_ends, dt)


@functools.lru_cache(maxsize=None)
def _compiled_pass(cfg_json: str, wrong: frozenset, gradients: bool):
    fn = functools.partial(_sequence_nll, cfg=json.loads(cfg_json), wrong=wrong)
    return jax.jit(jax.value_and_grad(fn, has_aux=True) if gradients else fn)


def step_parts(params, ids, cfg: dict, positions, wrong=frozenset(),
               gradients: bool = True) -> dict:
    """What one training step on ``ids [batch, seq]`` has to reproduce, one
    sequence at a time and each a single compiled pass: ``ce`` (token-mean
    next-token loss), ``grads`` (``jax.grad`` of ``ce``, the tree as numpy
    float32 summed on the host; None without ``gradients``), ``logits``
    ``[batch, len(positions), vocab]``, ``state_absmax`` (largest ``|S|`` of
    any token and layer), ``state_absmax_chunks`` (of the tokens that end a
    chunk) and ``dt_mean``."""
    with jax.default_matmul_precision("highest"):
        keys = sorted(k for k in cfg if k not in ("published", "assumed", "rehearse"))
        fn = _compiled_pass(json.dumps({k: cfg[k] for k in keys}), frozenset(wrong),
                            gradients)
        positions = jnp.asarray(positions, jnp.int32)
        tokens = ids.shape[0] * (ids.shape[1] - 1)
        nll, grads, logits, tops, tops_ends, dts = 0.0, None, [], [], [], []
        for row in range(ids.shape[0]):
            out = fn(params, ids[row], positions)
            (part, (lg, top, top_ends, dt)), grad = out if gradients else (out, None)
            nll += float(part)
            logits.append(np.asarray(lg))
            tops.append(float(top))
            tops_ends.append(float(top_ends))
            dts.append(float(dt))
            if gradients:
                grad = jax.tree_util.tree_map(lambda g: np.asarray(g) / tokens, grad)
                grads = grad if grads is None else jax.tree_util.tree_map(
                    np.add, grads, grad)
    return {"ce": nll / tokens, "grads": grads, "logits": np.stack(logits),
            "state_absmax": max(tops), "state_absmax_chunks": max(tops_ends),
            "dt_mean": float(np.mean(dts))}



# a standard normal truncated at +-2: its second and fourth moments
_TRUNC_M2, _TRUNC_M4 = 0.7737413, 1.4161745


def initialisation_readings(params, cfg: dict) -> dict:
    """How far the seeded Mamba-2 parameters of ``params`` lie from what the
    configuration's ``assumed`` states, one reading a rule, in standard errors
    of the rule's own statistic over the values there are (a sound draw reads
    a few at most, whatever the size); ``inf`` where a rule that is exact, or
    a range, is broken. The rules: ``A_log = log(1..heads)``; ``D = 1``;
    ``softplus(dt_bias)`` log-uniform in [1e-3, 1e-1]; the taps lecun-normal
    over the ``mamba_d_conv`` taps (a normal of variance 1 / taps, truncated at
    two of its standard deviations before the correction); the convolution's
    bias uniform in +-1 / sqrt(taps)."""
    layers = [lp["mamba"] for name, lp in sorted(params["model"].items())
              if name.startswith("layers_") and "mamba" in lp]
    stack = lambda key: np.stack([np.asarray(m[key], np.float64) for m in layers])  # noqa: E731
    heads, taps = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_conv"])
    inf = float("inf")

    def sigmas(values, mean, variance):
        return float(abs(values.mean() - mean) / np.sqrt(variance / values.size))

    out = {"A_log": 0.0 if np.allclose(np.exp(stack("A_log")), np.arange(1, heads + 1),
                                       rtol=1e-5, atol=0) else inf,
           "D": 0.0 if np.all(stack("D") == 1.0) else inf}
    dt = np.log1p(np.exp(stack("dt_bias")))
    lo, hi = np.log(1e-3), np.log(1e-1)
    t = (np.log(dt) - lo) / (hi - lo)           # uniform in [0, 1]
    if t.min() < -1e-3 or t.max() > 1 + 1e-3:
        out["dt_bias_mean"] = out["dt_bias_spread"] = inf
    else:
        out["dt_bias_mean"] = sigmas(t, 0.5, 1 / 12)
        out["dt_bias_spread"] = sigmas((t - 0.5)**2, 1 / 12, 1 / 180)
    w = stack("conv_weight") * np.sqrt(taps)    # unit variance
    if np.abs(w).max() > 2 / np.sqrt(_TRUNC_M2) * (1 + 1e-5):
        out["taps_mean"] = out["taps_spread"] = inf
    else:
        out["taps_mean"] = sigmas(w, 0.0, 1.0)
        out["taps_spread"] = sigmas(w**2, 1.0, _TRUNC_M4 / _TRUNC_M2**2 - 1)
    if "conv_bias" in layers[0]:
        u = stack("conv_bias") * np.sqrt(taps)  # uniform in [-1, 1]
        if np.abs(u).max() > 1 + 1e-6:
            out["conv_bias_mean"] = out["conv_bias_spread"] = inf
        else:
            out["conv_bias_mean"] = sigmas(u, 0.0, 1 / 3)
            out["conv_bias_spread"] = sigmas(u**2, 1 / 3, 4 / 45)
    return out
