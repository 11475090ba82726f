"""Plain reference of the Mistral decoder's forward pass and loss.

Straightforward ``jax.numpy`` in float32 with the matmul precision at
"highest" (on a TPU a float32 matmul otherwise runs in bf16 passes). No
kernels, no cache, no batching tricks, and nothing imported from the program
under test: RMSNorm, rotary embedding in the half-split layout, grouped-query
attention with a causal sliding window, SwiGLU, an untied head, token-mean
cross-entropy with the shift by one. It follows the published description
(arXiv:2310.06825 and the ``transformers`` MistralModel); the one departure
is that attention runs in blocks of queries so the score matrix of a
4,096-token sequence need not exist whole.

Weights come as the tree the program holds (``{"model": {"embed_tokens":
{"embedding"}, "layers_<i>": {...}, "norm": {"weight"}, "lm_head":
{"kernel"}}}``, kernels stored ``[in, out]``) and are cast to float32 one
matrix at a time, so the reference needs no second copy of the model.
"""

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


@jax.jit
def _mm(x, w):
    return jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32), precision=_HI)


@functools.partial(jax.jit, static_argnames="eps")
def rms_norm(x, weight, eps: float):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)


def _rope(x, positions, theta: float):
    """x: [batch, seq, heads, d]; rotate-half (x[i], x[i + d/2]) pairs."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq  # [b, s, d/2]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("window", "q_block"))
def _attend(q, k, v, window, q_block: int):
    """q: [b, s, H, d], k/v: [b, s, KV, d] -> [b, s, H*d]. Query ``i`` sees
    keys ``j`` with ``i - window < j <= i``."""
    b, s, H, d = q.shape
    groups = H // k.shape[2]
    k = jnp.repeat(k, groups, axis=2)
    v = jnp.repeat(v, groups, axis=2)
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    j = jnp.arange(s)[None, :]
    outs = []
    for start in range(0, s, q_block):
        qb = q[:, start:start + q_block]
        i = jnp.arange(start, start + qb.shape[1])[:, None]
        mask = j <= i
        if window:
            mask = mask & (j > i - window)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=_HI) * scale
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=_HI))
    return jnp.concatenate(outs, axis=1).reshape(b, s, H * d)


def hidden_states(params, ids, cfg: dict, q_block: int = 1024):
    """Final-norm hidden states ``[batch, seq, hidden]`` in float32."""
    m = params["model"]
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // H
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    b, s = ids.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = jnp.take(m["embed_tokens"]["embedding"], ids, axis=0).astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        lp = m[f"layers_{i}"]
        a = lp["self_attn"]
        h = rms_norm(x, lp["input_layernorm"]["weight"], eps)
        q = _rope(_mm(h, a["q_proj"]["kernel"]).reshape(b, s, H, d), positions, theta)
        k = _rope(_mm(h, a["k_proj"]["kernel"]).reshape(b, s, KV, d), positions, theta)
        v = _mm(h, a["v_proj"]["kernel"]).reshape(b, s, KV, d)
        att = _attend(q, k, v, cfg.get("sliding_window"), q_block)
        x = x + _mm(att, a["o_proj"]["kernel"])
        h = rms_norm(x, lp["post_attention_layernorm"]["weight"], eps)
        mlp = lp["mlp"]
        gated = jax.nn.silu(_mm(h, mlp["gate_proj"]["kernel"])) * _mm(h, mlp["up_proj"]["kernel"])
        x = x + _mm(gated, mlp["down_proj"]["kernel"])
    return rms_norm(x, m["norm"]["weight"], eps)


def logits(params, ids, cfg: dict, last: int = 0, vocab_chunk: int = 8000):
    """Logits ``[batch, positions, vocab]`` of the last ``last`` positions
    (all of them when 0). The head is applied in slices of the vocabulary so
    its float32 copy never exists whole."""
    x = hidden_states(params, ids, cfg)
    if last:
        x = x[:, -last:]
    head = params["model"]["lm_head"]["kernel"]
    return jnp.concatenate(
        [_mm(x, head[:, c:c + vocab_chunk])
         for c in range(0, head.shape[1], vocab_chunk)], axis=-1)


def cross_entropy(params, ids, cfg: dict) -> jax.Array:
    """Token-mean next-token loss: position ``t`` predicts ``ids[t + 1]``."""
    lg = logits(params, ids, cfg)[:, :-1]
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, ids[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
