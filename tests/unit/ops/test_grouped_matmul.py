"""Grouped MoE matmul numerics vs the dense-over-experts oracle."""

import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops.grouped_matmul import (expert_counts, moe_combine,
                                              moe_dense_mlp, moe_dispatch,
                                              moe_grouped_mlp,
                                              moe_sort_permutation)


def _setup(rng, T=17, H=8, F=16, E=4, k=2, dtype=jnp.float32):
    x = jnp.asarray(rng.normal(size=(T, H)) * 0.3, dtype)
    w1 = jnp.asarray(rng.normal(size=(E, H, F)) * 0.2, dtype)
    w3 = jnp.asarray(rng.normal(size=(E, H, F)) * 0.2, dtype)
    w2 = jnp.asarray(rng.normal(size=(E, F, H)) * 0.2, dtype)
    logits = jnp.asarray(rng.normal(size=(T, E)), jnp.float32)
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
    w = (w / w.sum(-1, keepdims=True)).astype(dtype)
    return x, w1, w3, w2, idx, w


def test_grouped_matches_dense():
    rng = np.random.default_rng(0)
    x, w1, w3, w2, idx, w = _setup(rng)
    out_g = moe_grouped_mlp(x, w1, w3, w2, idx, w)
    out_d = moe_dense_mlp(x, w1, w3, w2, idx, w)
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_d),
                               rtol=1e-5, atol=1e-5)


def test_grouped_matches_dense_skewed_routing():
    """All tokens on one expert (worst-case group imbalance)."""
    rng = np.random.default_rng(1)
    x, w1, w3, w2, _, w = _setup(rng, T=9, k=2)
    idx = jnp.stack([jnp.full((9,), 3, jnp.int32), jnp.zeros((9,), jnp.int32)], -1)
    out_g = moe_grouped_mlp(x, w1, w3, w2, idx, w)
    out_d = moe_dense_mlp(x, w1, w3, w2, idx, w)
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_d),
                               rtol=1e-5, atol=1e-5)


def test_grouped_gradients_match_dense():
    rng = np.random.default_rng(2)
    x, w1, w3, w2, idx, w = _setup(rng, T=11)

    def loss(fn, x, w1, w3, w2):
        return (fn(x, w1, w3, w2, idx, w) ** 2).mean()

    g_g = jax.grad(lambda *a: loss(moe_grouped_mlp, *a), argnums=(0, 1, 2, 3))(x, w1, w3, w2)
    g_d = jax.grad(lambda *a: loss(moe_dense_mlp, *a), argnums=(0, 1, 2, 3))(x, w1, w3, w2)
    for a, b in zip(g_g, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5)


def test_grouped_lowers_to_native_ragged_dot_on_tpu():
    """The TPU lowering must emit the native chlo.ragged_dot grouped-GEMM
    instruction (FLOPs ∝ T*k) — NOT the dense-masked decomposition the CPU
    backend falls back to (which would be ∝ T*E). Checked via jax.export so
    no TPU hardware is needed."""
    rng = np.random.default_rng(3)
    x, w1, w3, w2, idx, w = _setup(rng, T=64, H=32, F=64, E=8, k=2)
    exp = jax.export.export(jax.jit(moe_grouped_mlp), platforms=["tpu"])(
        x, w1, w3, w2, idx, w)
    txt = exp.mlir_module()
    assert txt.count("chlo.ragged_dot") == 3, txt.count("chlo.ragged_dot")


def test_moe_block_grouped_vs_dense_end_to_end():
    """LlamaMoEBlock produces the same output under both compute paths."""
    from deepspeed_tpu.models import LlamaConfig
    from deepspeed_tpu.models.llama import LlamaMoEBlock
    import dataclasses

    cfg = LlamaConfig.tiny(num_local_experts=4, num_experts_per_tok=2,
                           dtype=jnp.float32)
    block = LlamaMoEBlock(cfg)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(2, 8, cfg.hidden_size)) * 0.3,
                    jnp.float32)
    params = block.init(jax.random.PRNGKey(0), x)
    out_g = block.apply(params, x)
    cfg_d = dataclasses.replace(cfg, moe_grouped=False)
    out_d = LlamaMoEBlock(cfg_d).apply(params, x)
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_d),
                               rtol=1e-5, atol=1e-5)


# ---- dispatch and combine as permutation gathers --------------------------

def _routing(rng, E, k, T, empty=None):
    """``[T, k]`` distinct experts a token, none of them ``empty``."""
    logits = rng.normal(size=(T, E))
    if empty is not None:
        logits[:, empty] = -np.inf
    return jnp.asarray(np.argsort(-logits, axis=1)[:, :k], jnp.int32)


@pytest.mark.parametrize("E,k,T,empty", [
    (64, 8, 24, 5),   # OLMoE's routing with one expert nobody chose
    (4, 1, 13, None),  # k = 1: the combine is a pure permutation
    (5, 3, 7, None),   # 21 rows: not a multiple of 8
    (8, 2, 16, 0),
], ids=["64x8_empty_expert", "k1", "rows_not_multiple_of_8", "8x2"])
def test_dispatch_and_combine_match_the_plain_gather_and_scatter_add(E, k, T, empty):
    """``moe_dispatch`` / ``moe_combine`` and their hand-written transposes
    against ``x[tok]`` / ``.at[tok].add`` under ``jax.grad``, float32: the
    values of each and the gradients of ``x``, ``y`` and ``top_w``."""
    rng = np.random.default_rng(E * 100 + k)
    H = 12
    idx = _routing(rng, E, k, T, empty)
    if empty is not None:
        assert not np.any(np.asarray(idx) == empty)
    order, inv = moe_sort_permutation(idx)
    tok = order // k
    x = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(T * k, H)), jnp.float32)
    top_w = jnp.asarray(rng.uniform(0.1, 1.0, size=(T, k)), jnp.float32)
    ct = jnp.asarray(rng.normal(size=(T, H)), jnp.float32)

    def plain_dispatch(x):
        return x[tok]

    def plain_combine(y, top_w):
        return jnp.zeros((T, H), jnp.float32).at[tok].add(
            y * top_w.reshape(-1)[order][:, None])

    def ours_dispatch(x):
        return moe_dispatch(x, order, inv, k)

    def ours_combine(y, top_w):
        return moe_combine(y, top_w, order, inv)

    np.testing.assert_array_equal(np.asarray(ours_dispatch(x)),
                                  np.asarray(plain_dispatch(x)))
    np.testing.assert_allclose(np.asarray(ours_combine(y, top_w)),
                               np.asarray(plain_combine(y, top_w)),
                               rtol=1e-6, atol=1e-6)

    def loss(dispatch, combine, x, y, top_w):
        # the dispatched rows meet y the way they meet the experts' output
        return jnp.sum(combine(jnp.tanh(dispatch(x)) * y, top_w) * ct)

    got = jax.grad(lambda *a: loss(ours_dispatch, ours_combine, *a),
                   argnums=(0, 1, 2))(x, y, top_w)
    want = jax.grad(lambda *a: loss(plain_dispatch, plain_combine, *a),
                    argnums=(0, 1, 2))(x, y, top_w)
    for name, a, b in zip(("x", "y", "top_w"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6, err_msg=name)


@pytest.mark.parametrize("routing", ["skewed", "tied", "random", "one_expert"])
def test_inverse_permutation_undoes_the_sort(routing):
    rng = np.random.default_rng(7)
    T, k, E = 37, 3, 8
    idx = {
        "skewed": np.stack([np.full(T, 3), np.zeros(T), rng.integers(4, 8, T)], -1),
        "tied": np.tile(np.array([[2, 5, 1]]), (T, 1)),  # every token alike
        "random": np.asarray(_routing(rng, E, k, T)),
        "one_expert": np.full((T, k), 6),
    }[routing]
    idx = jnp.asarray(idx, jnp.int32)
    order, inv = moe_sort_permutation(idx)
    order, inv = np.asarray(order), np.asarray(inv)
    np.testing.assert_array_equal(inv[order], np.arange(T * k))
    np.testing.assert_array_equal(order[inv], np.arange(T * k))
    sorted_e = np.asarray(idx).reshape(-1)[order]
    assert np.all(np.diff(sorted_e) >= 0)
    # stable: within an expert the assignments keep their order
    assert np.all((np.diff(sorted_e) > 0) | (np.diff(order) > 0))


def _row_scatters(mlir: str, rows: int):
    """Updates' shapes of the module's ``stablehlo.scatter`` ops that move
    ``rows`` rows of more than one element."""
    found = []
    for m in re.finditer(r'"stablehlo\.scatter".*?\}\) : \(([^)]*)\)', mlir, re.S):
        updates = m.group(1).split("tensor<")[-1].split(">")[0].split("x")[:-1]
        shape = [int(d) for d in updates]
        if len(shape) >= 2 and shape[0] == rows and shape[1] > 1:
            found.append(shape)
    return found


def test_gradient_program_moves_rows_with_gathers_only():
    """What proves the mechanism engaged: the StableHLO of
    ``jax.grad(moe_grouped_mlp)`` for a TPU holds no ``scatter`` whose
    updates are the block's ``T*k`` rows (the plain gather / scatter-add form
    holds two), and still nine grouped matmuls."""
    rng = np.random.default_rng(5)
    T, H, F, E, k = 64, 32, 64, 8, 2
    x, w1, w3, w2, idx, w = _setup(rng, T=T, H=H, F=F, E=E, k=k, dtype=jnp.bfloat16)

    def export(fn):
        def loss(x, w1, w3, w2, w):
            return jnp.sum(fn(x, w1, w3, w2, idx, w).astype(jnp.float32) ** 2)
        grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
        return jax.export.export(grad, platforms=["tpu"])(x, w1, w3, w2, w).mlir_module()

    def scatter_form(x, w1, w3, w2, idx, w):
        order, _ = moe_sort_permutation(idx)
        tok = order // k
        g = expert_counts(idx, E)
        xs = x[tok]
        a = jax.nn.silu(jax.lax.ragged_dot(xs, w1, g)) * jax.lax.ragged_dot(xs, w3, g)
        y = jax.lax.ragged_dot(a, w2, g, preferred_element_type=jnp.float32)
        ws = w.reshape(-1)[order].astype(jnp.float32)
        return jnp.zeros((T, H), jnp.float32).at[tok].add(y * ws[:, None]).astype(x.dtype)

    assert len(_row_scatters(export(scatter_form), T * k)) == 2  # the detector sees them
    txt = export(moe_grouped_mlp)
    assert _row_scatters(txt, T * k) == []
    assert txt.count("chlo.ragged_dot") == 9


def _ragged_dot_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        if "ragged_dot" in eqn.primitive.name:
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _ragged_dot_eqns(inner)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_rows_leave_the_grouped_matmuls_in_the_input_dtype(dtype):
    """``h1``, ``h3`` and ``y`` are written by ``ragged_dot`` in ``x.dtype``:
    no float32 ``[T*k, ·]`` array between the matmuls under bf16 and no cast
    pass of its own after the kernel."""
    rng = np.random.default_rng(6)
    args = _setup(rng, dtype=dtype)
    eqns = list(_ragged_dot_eqns(jax.make_jaxpr(moe_grouped_mlp)(*args).jaxpr))
    T, H, k, F = args[0].shape[0], args[0].shape[1], args[4].shape[1], args[1].shape[2]
    assert sorted(e.outvars[0].aval.shape for e in eqns) == sorted(
        [(T * k, F), (T * k, F), (T * k, H)])
    assert all(e.outvars[0].aval.dtype == dtype for e in eqns)
