"""Grouped (megablocks-style) MoE matmul.

Reference capability: ``deepspeed/inference/v2/kernels/cutlass_ops/moe_gemm/``
plus the ``moe_scatter``/``moe_gather`` ragged ops — tokens are routed to
experts and each expert multiplies only its own tokens, so per-token FLOPs
scale with top-k instead of the expert count E (the round-1 path computed
every expert for every token and masked: E/k× wasted FLOPs).

TPU design: sort the (token, choice) assignments by expert id (one XLA sort),
run the three expert MLPs as ragged grouped GEMMs with
``jax.lax.ragged_dot`` — on TPU/GPU this lowers to the native
``chlo.ragged_dot`` grouped-GEMM instruction (MXU, FLOPs ∝ top-k; the CPU
backend decomposes to a dense-masked form, which only the test harness
sees), the grouped-GEMM analog of the reference's CUTLASS kernel — then
combine with a weighted scatter-add back to token order. Fully differentiable (ragged_dot carries transpose rules), static
shapes throughout (T*k assignments regardless of routing), no capacity
factor and no token dropping: exact token-choice semantics.
"""

import jax
import jax.numpy as jnp


def moe_sort_tokens(top_idx):
    """Sort (token, choice) assignments by expert.

    Args:
      top_idx: ``[T, k]`` int32 expert id per (token, choice).
    Returns:
      (tok_sorted ``[T*k]`` source token per sorted assignment,
       order ``[T*k]`` the sort permutation over flattened assignments,
       group_sizes ``[E?]`` — caller computes via bincount; returned here
       as the sorted expert ids for convenience).
    """
    Tk = top_idx.size
    flat_e = top_idx.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    tok_sorted = (jnp.arange(Tk, dtype=jnp.int32) // top_idx.shape[1])[order]
    return tok_sorted, order, flat_e[order]


def expert_counts(top_idx, num_experts: int):
    """``[E]`` int32 (token, choice) assignments per expert. A compare and a
    column sum: on a v5e 0.33 ms for 131,072 assignments over 64 experts,
    against 1.34 ms for ``jnp.bincount``'s scatter-add."""
    return jnp.sum(top_idx.reshape(-1, 1) == jnp.arange(num_experts), axis=0,
                   dtype=jnp.int32)


def moe_grouped_mlp(x, w1, w3, w2, top_idx, top_w, *, activation=jax.nn.silu):
    """Token-choice MoE MLP via grouped GEMMs.

    ``y[t] = Σ_j top_w[t,j] · ffn_{top_idx[t,j]}(x[t])`` with
    ``ffn_e(h) = (act(h @ w1[e]) * (h @ w3[e])) @ w2[e]`` (SwiGLU).

    Args:
      x: ``[T, H]`` tokens.
      w1, w3: ``[E, H, F]``; w2: ``[E, F, H]`` expert weights.
      top_idx: ``[T, k]`` int32 chosen experts.
      top_w: ``[T, k]`` combine weights (already normalized).
    Returns:
      ``[T, H]`` in x.dtype.
    """
    T, H = x.shape
    E = w1.shape[0]
    k = top_idx.shape[1]

    tok_sorted, order, _ = moe_sort_tokens(top_idx)
    group_sizes = expert_counts(top_idx, E)

    xs = x[tok_sorted]  # [T*k, H] expert-contiguous
    h1 = jax.lax.ragged_dot(xs, w1, group_sizes,
                            preferred_element_type=jnp.float32).astype(x.dtype)
    h3 = jax.lax.ragged_dot(xs, w3, group_sizes,
                            preferred_element_type=jnp.float32).astype(x.dtype)
    act = activation(h1) * h3
    y = jax.lax.ragged_dot(act, w2, group_sizes,
                           preferred_element_type=jnp.float32)  # [T*k, H] fp32

    w_sorted = top_w.reshape(-1)[order].astype(jnp.float32)
    out = jnp.zeros((T, H), jnp.float32).at[tok_sorted].add(y * w_sorted[:, None])
    return out.astype(x.dtype)


def lora_sort_slots(slots, n_slots):
    """Sort per-token adapter slot ids for the grouped LoRA delta — the
    k=1 specialization of :func:`moe_sort_tokens` (every token has exactly
    one adapter). Hoist this ONCE per forward and reuse the (order,
    group_sizes) pair across every layer/target: the sort is a function of
    the batch's slot assignment only.

    Args:
      slots: ``[T]`` int32 adapter slot per token (0 = identity).
      n_slots: static slot-pool size (bank leading dim).
    Returns:
      (order ``[T]`` sort permutation, group_sizes ``[n_slots]`` int32).
    """
    order = jnp.argsort(slots, stable=True)
    group_sizes = jnp.bincount(slots, length=n_slots).astype(jnp.int32)
    return order, group_sizes


def lora_grouped_delta(x, a, b, scale_sorted, order, group_sizes):
    """Batched multi-LoRA delta ``y[t] += B[s_t] @ (A[s_t] @ x[t]) * scale``
    via the sort-by-slot ragged idiom — ONE pair of grouped GEMMs covers a
    mixed-adapter token wave, FLOPs ∝ rank regardless of how many adapters
    are live, and slot 0's zero factors make base-only tokens an exact
    no-op (delta ≡ 0.0, so streams stay bit-identical to the base model).

    Args:
      x: ``[T, in]`` tokens (original order).
      a: ``[n_slots, in, r]`` stacked down-projection factors.
      b: ``[n_slots, r, out]`` stacked up-projection factors.
      scale_sorted: ``[T]`` fp32 per-token ``alpha / sqrt(r)`` in SORTED
        order (``scale[slots][order]`` — the caller gathers once).
      order, group_sizes: from :func:`lora_sort_slots`.
    Returns:
      ``[T, out]`` fp32 delta in original token order.
    """
    xs = x[order]
    h = jax.lax.ragged_dot(xs, a, group_sizes,
                           preferred_element_type=jnp.float32).astype(x.dtype)
    y = jax.lax.ragged_dot(h, b, group_sizes,
                           preferred_element_type=jnp.float32)
    y = y * scale_sorted[:, None]
    return jnp.zeros((x.shape[0], b.shape[-1]), jnp.float32).at[order].set(y)


def lora_dense_delta(x, a, b, slots, scale):
    """Dense-gather reference for :func:`lora_grouped_delta` — the numerics
    oracle: per-token factor gather + two plain matmuls, no sort."""
    af = a[slots].astype(jnp.float32)        # [T, in, r]
    bf = b[slots].astype(jnp.float32)        # [T, r, out]
    h = jnp.einsum("ti,tir->tr", x.astype(jnp.float32), af)
    y = jnp.einsum("tr,tro->to", h, bf)
    return y * scale[slots][:, None]


def moe_dense_mlp(x, w1, w3, w2, top_idx, top_w, *, activation=jax.nn.silu):
    """Dense-over-experts reference (every expert for every token, masked
    combine) — the numerics oracle for tests and the fallback when an
    'expert'-sharded mesh axis makes the sort/a2a layout preferable."""
    E = w1.shape[0]
    cw = jnp.sum(top_w[..., None] * jax.nn.one_hot(top_idx, E, dtype=top_w.dtype),
                 axis=-2)  # [T, E]
    a = activation(jnp.einsum("th,ehf->tef", x, w1)) * jnp.einsum("th,ehf->tef", x, w3)
    y = jnp.einsum("tef,efh->teh", a, w2)
    return jnp.einsum("te,teh->th", cw.astype(y.dtype), y).astype(x.dtype)


from .registry import registry  # noqa: E402

registry.register("grouped_matmul", "xla", True,
                  "MoE grouped GEMM, FLOPs proportional to top-k (reference "
                  "cutlass_ops moe_gemm)")
