"""Chunked (memory-efficient) unembed+CE vs the dense oracle.

The op must be a bit-for-policy drop-in: same loss and same gradients as
materializing the logits, across knobs that change logit semantics (bias,
Cohere logit_scale, Gemma-2 softcap), vocabulary sizes the chunk does not
divide, sequence lengths the derived chunk of positions does not divide, and
ignore_index masking. It must also keep its two promises about cost: the
transient logits stay under ``tokens x chunk`` elements, and the
differentiated program runs the vocabulary matmul three times in one scan.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.llama import cross_entropy_loss
from deepspeed_tpu.ops.chunked_ce import chunked_cross_entropy_loss, seq_chunk


def _dense_loss(x, w, bias, labels, logit_scale=None, softcap=None):
    logits = jnp.einsum("bsh,hv->bsv", x.astype(jnp.float32),
                        w.astype(jnp.float32))
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if logit_scale is not None:
        logits = logits * logit_scale
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    return cross_entropy_loss(logits, labels)


def _inputs(seed, B, S, H, V, use_bias=False):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(B, S, H)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(H, V)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.normal(size=(V, )), jnp.float32) if use_bias else None
    labels = jnp.asarray(rng.integers(0, V, size=(B, S)), jnp.int32)
    return x, w, bias, labels


def _assert_grads_close(got, want, atol=2e-5, rtol=2e-4):
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=atol, rtol=rtol)


def _walk(jaxpr, visit, inside_scan=False):
    """``visit(eqn, inside_scan)`` for every equation, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        visit(eqn, inside_scan)
        for pv in eqn.params.values():
            for sub in (pv if isinstance(pv, (list, tuple)) else [pv]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _walk(inner, visit,
                          inside_scan or eqn.primitive.name == "scan")


def _count(jaxpr, name):
    """(equations named ``name`` outside any scan, inside one)."""
    n = [0, 0]

    def visit(eqn, inside_scan):
        n[inside_scan] += eqn.primitive.name == name
    _walk(jaxpr, visit)
    return tuple(n)


# sc at S = 8: 2 (8*16//64), 2 (8*32//100, and 100 is no multiple of 32), 8
@pytest.mark.parametrize("V,chunk", [(64, 16), (100, 32), (64, 64)])
@pytest.mark.parametrize("scale,softcap,use_bias", [
    (None, None, False), (0.25, None, True), (None, 30.0, False),
    (0.5, 30.0, True),
])
def test_matches_dense(V, chunk, scale, softcap, use_bias):
    x, w, bias, labels = _inputs(0, 3, 8, 32, V, use_bias)
    argnums = (0, 1, 2) if use_bias else (0, 1)

    def loss_c(x, w, bias):
        return chunked_cross_entropy_loss(x, w, bias, labels, chunk,
                                          logit_scale=scale, softcap=softcap,
                                          compute_dtype=jnp.float32)

    def loss_d(x, w, bias):
        return _dense_loss(x, w, bias, labels, scale, softcap)

    lc, gc = jax.value_and_grad(loss_c, argnums=argnums)(x, w, bias)
    ld, gd = jax.value_and_grad(loss_d, argnums=argnums)(x, w, bias)
    np.testing.assert_allclose(float(lc), float(ld), rtol=1e-5)
    _assert_grads_close(gc, gd)
    # the primal (no gradient asked for) is the same number
    np.testing.assert_allclose(float(loss_c(x, w, bias)), float(ld), rtol=1e-5)


def test_model_level_equivalence_tied_and_untied():
    """LlamaForCausalLM with ce_chunk_size must match the dense CE loss and
    parameter gradients (tied and untied heads)."""
    from deepspeed_tpu.models import LlamaConfig, init_llama
    rng = np.random.default_rng(1)
    for tie in (True, False):
        kw = dict(vocab_size=160, hidden_size=32, intermediate_size=64,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=4, max_position_embeddings=64,
                  tie_word_embeddings=tie, dtype=jnp.float32)
        dense_cfg = LlamaConfig(**kw)
        chunk_cfg = LlamaConfig(**kw, ce_chunk_size=48)  # 160 % 48 != 0
        model_d, params = init_llama(dense_cfg, seed=2)
        model_c, _ = init_llama(chunk_cfg, seed=2)
        ids = jnp.asarray(rng.integers(0, 160, size=(2, 16)), jnp.int32)
        labels = ids.at[0, :3].set(-100)  # exercise ignore_index

        ld, gd = jax.value_and_grad(
            lambda p: model_d.apply({"params": p}, ids, labels=labels))(params)
        lc, gc = jax.value_and_grad(
            lambda p: model_c.apply({"params": p}, ids, labels=labels))(params)
        np.testing.assert_allclose(float(lc), float(ld), rtol=1e-5)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=3e-5, rtol=3e-4), gc, gd)


def test_never_materializes_logits():
    """``chunk`` is a bound: no intermediate with a vocabulary axis, in the
    loss or in its gradient, holds more than ``tokens x chunk`` elements (the
    weight's own gradient apart). The full logits not existing is the point."""
    B, S, H, V, chunk = 2, 64, 16, 4096, 512
    x, w, _, labels = _inputs(3, B, S, H, V)
    assert seq_chunk(S, chunk, V) == 8

    def loss(x, w):
        return chunked_cross_entropy_loss(x, w, None, labels, chunk,
                                          compute_dtype=jnp.float32)

    def visit(eqn, inside_scan):
        for v in eqn.outvars:
            shape = getattr(v.aval, "shape", ())
            if V in shape and set(shape) != {H, V}:   # dw, either way up
                assert int(np.prod(shape)) <= B * S * chunk, \
                    f"{shape} made by {eqn.primitive}"

    _walk(jax.make_jaxpr(loss)(x, w).jaxpr, visit)
    _walk(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, w).jaxpr, visit)


@pytest.mark.parametrize("S,V,chunk,sc", [
    (4096, 50304, 8384, 512),    # the OLMoE cell: 8 chunks
    (4096, 32000, 8000, 1024),   # the dense cell: 4 chunks a chip
    (8192, 256000, 8000, 256),   # Gemma-2's vocabulary: sc falls
    (16, 256000, 8000, 1),       # never under one position
    (12, 64, 64, 12), (12, 64, 100, 12),   # chunk covers the vocabulary
])
def test_positions_a_chunk_follow_from_the_shape(S, V, chunk, sc):
    assert seq_chunk(S, chunk, V) == sc
    if 1 < sc < S:   # the largest power of two under the bound
        assert sc * V <= S * chunk < 2 * sc * V


def test_one_scan_three_matmuls_differentiated_one_in_the_primal():
    """The gradient is formed in the sweep that forms the loss: one scan,
    three ``dot_general``s in it (logits, dx, dw), none outside it; the
    undifferentiated loss pays for the logits only."""
    x, w, bias, labels = _inputs(5, 2, 16, 8, 64, use_bias=True)

    def loss(x, w, bias):
        return chunked_cross_entropy_loss(x, w, bias, labels, 16,
                                          logit_scale=0.5, softcap=30.0)

    grad = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        x, w, bias).jaxpr
    assert _count(grad, "scan") == (1, 0)
    assert _count(grad, "dot_general") == (0, 3)
    primal = jax.make_jaxpr(loss)(x, w, bias).jaxpr
    assert _count(primal, "scan") == (1, 0)
    assert _count(primal, "dot_general") == (0, 1)


@pytest.mark.parametrize("g", [3.0, 0.25, 1.0 / 3.0, 65536.0])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_cotangent_scales_every_gradient_to_a_rounding(dtype, g):
    """The backward is ``g / N`` times the stored sums, formed in fp32 and
    rounded once to the gradient's dtype: a cotangent that the dtype cannot
    hold (2^16 in fp16, 1/3 in bf16) is never rounded to it."""
    x, w, bias, labels = _inputs(6, 2, 64, 16, 48, use_bias=True)
    x, w, bias = (a.astype(dtype) for a in (x, w, bias))

    def loss(x, w, bias):
        return chunked_cross_entropy_loss(x, w, bias, labels, 16,
                                          compute_dtype=dtype)

    one = jax.grad(loss, argnums=(0, 1, 2))(x, w, bias)
    scaled = jax.grad(lambda *a: g * loss(*a), argnums=(0, 1, 2))(x, w, bias)
    # a rounding is at most eps / 2: of g / N, of each product, and of the
    # unscaled gradient it is compared with (coarser where either is subnormal)
    eps, tiny = (float(v) for v in (jnp.finfo(dtype).eps, jnp.finfo(dtype).tiny))
    for a, b in zip(scaled, one):
        assert a.dtype == dtype and np.isfinite(np.asarray(a, np.float32)).all()
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   g * np.asarray(b, np.float64),
                                   rtol=2 * eps, atol=max(g, 1.0) * tiny * eps)


@pytest.mark.parametrize("dtype,g,tol", [
    (jnp.float16, 65536.0, 1e-3),    # the engine's first loss scale, 2^16
    (jnp.float16, 1.0, 1e-3),
    (jnp.bfloat16, 1.0 / 3.0, 8e-3),   # three accumulation steps
    (jnp.bfloat16, 1.0, 8e-3),
])
def test_low_precision_matches_dense_whatever_the_cotangent(dtype, g, tol):
    """fp16 and bf16 training: the gradients of ``g x loss`` are ``g x`` the
    dense oracle's to the operands' rounding, with nothing lost to the loss
    scale (fp16 holds neither 2^16 nor a softmax tail of 1 / 32768 divided by
    the token count) and no bias from rounding ``g``."""
    B, S, H, V, chunk = 2, 64, 32, 32768, 4096
    x, w, bias, labels = _inputs(9, B, S, H, V, use_bias=True)
    x, w, bias = (a.astype(dtype) for a in (x, w * 0.3, bias))
    assert seq_chunk(S, chunk, V) == 8

    def loss_c(x, w, bias):
        return g * chunked_cross_entropy_loss(x, w, bias, labels, chunk,
                                              compute_dtype=dtype)

    gc = jax.grad(loss_c, argnums=(0, 1, 2))(x, w, bias)
    gd = jax.grad(lambda *a: _dense_loss(*a, labels), argnums=(0, 1, 2))(
        *(a.astype(jnp.float32) for a in (x, w, bias)))
    for a, b in zip(gc, gd):
        a, b = np.asarray(a, np.float32) / g, np.asarray(b)
        assert np.isfinite(a).all()
        assert np.abs(a - b).max() <= tol * np.abs(b).max()
        # unbiased: the whole gradient is not a fraction of a percent long
        np.testing.assert_allclose((a * b).sum() / (b * b).sum(), 1.0,
                                   atol=tol / 4)


def test_sequence_not_a_multiple_of_the_chunk():
    """S = 12 with sc = 8: the tail chunk is padded with weight-0 positions
    and the gradient comes back at S."""
    x, w, bias, labels = _inputs(7, 2, 12, 16, 64, use_bias=True)
    assert seq_chunk(12, 48, 64) == 8
    labels = labels.at[1, 5:7].set(-100)

    def loss_c(x, w, bias):
        return chunked_cross_entropy_loss(x, w, bias, labels, 48,
                                          compute_dtype=jnp.float32)

    lc, gc = jax.value_and_grad(loss_c, argnums=(0, 1, 2))(x, w, bias)
    ld, gd = jax.value_and_grad(
        lambda *a: _dense_loss(*a, labels), argnums=(0, 1, 2))(x, w, bias)
    assert gc[0].shape == x.shape
    np.testing.assert_allclose(float(lc), float(ld), rtol=1e-5)
    _assert_grads_close(gc, gd)


def test_all_labels_ignored():
    """Nothing to predict: loss 0, every gradient 0, nothing NaN."""
    x, w, bias, labels = _inputs(8, 2, 8, 16, 64, use_bias=True)
    labels = jnp.full_like(labels, -100)
    loss, grads = jax.value_and_grad(
        lambda *a: chunked_cross_entropy_loss(*a, labels, 16),
        argnums=(0, 1, 2))(x, w, bias)
    assert float(loss) == 0.0
    for g in grads:
        assert not np.asarray(g).any()


@pytest.mark.world_size(8)
@pytest.mark.parametrize("axes,w_spec", [
    ({"data": 2, "fsdp": 4}, (None, "fsdp")),      # ZeRO-3, head split on V
    ({"data": 2, "fsdp": 2, "model": 2}, (None, "model")),   # + tensor parallel
    ({"data": 2, "seq": 2, "fsdp": 2}, ("fsdp", None)),      # + the sliced axis
    ({"data": 8}, ()),                                        # data parallel
])
def test_same_loss_and_gradients_under_a_mesh(axes, w_spec):
    """Under a mesh the sweep runs in a ``shard_map`` over the axes that
    shard the batch (the others stay GSPMD's): the loss and every gradient are
    the single-device ones, jitted or not, and so are they when the rows do
    not divide the devices and the whole is left to GSPMD."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deepspeed_tpu.comm.mesh import (MeshContext, reset_mesh_context,
                                         set_mesh_context)
    x, w, bias, labels = _inputs(10, 8, 16, 32, 96, use_bias=True)
    labels = labels.at[0, :5].set(-100)

    def loss(x, w, bias, labels):
        return chunked_cross_entropy_loss(x, w, bias, labels, 24,
                                          logit_scale=0.5, softcap=30.0,
                                          compute_dtype=jnp.float32)

    vg = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    reset_mesh_context()
    want, want6 = vg(x, w, bias, labels), vg(x[:6], w, bias, labels[:6])
    ctx = MeshContext.create(axis_sizes=axes)
    set_mesh_context(ctx)
    try:
        rows = P(tuple(a for a in ("data", "fsdp") if a in axes))
        args = [jax.device_put(a, NamedSharding(ctx.mesh, spec)) for a, spec in
                ((x, rows), (w, P(*w_spec)), (bias, P()), (labels, rows))]
        vg = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
        got, got6 = vg(*args), vg(x[:6], w, bias, labels[:6])
        primal = loss(*args)
    finally:
        reset_mesh_context()
    np.testing.assert_allclose(float(primal), float(want[0]), rtol=1e-6)
    for a, b in ((got, want), (got6, want6)):
        np.testing.assert_allclose(float(a[0]), float(b[0]), rtol=1e-6)
        _assert_grads_close(a[1], b[1], atol=2e-6, rtol=2e-5)


def test_loss_level_wrapper_shift_and_mask():
    rng = np.random.default_rng(4)
    B, S, H, V = 2, 8, 16, 64
    x = jnp.asarray(rng.normal(size=(B, S, H)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(H, V)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, size=(B, S)), jnp.int32)
    labels = labels.at[:, -2:].set(-100)
    got = chunked_cross_entropy_loss(x, w, None, labels, 16,
                                     compute_dtype=jnp.float32)
    # dense oracle with the same shift/mask
    logits = jnp.einsum("bsh,hv->bsv", x, w)[:, :-1]
    tg = labels[:, 1:]
    mask = (tg != -100)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.where(mask, tg, 0)[..., None],
                               axis=-1)[..., 0]
    want = ((lse - gold) * mask).sum() / mask.sum()
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    # the last position predicts nothing: its hidden state has no gradient,
    # whatever label sits there
    dx = jax.grad(lambda x: chunked_cross_entropy_loss(
        x, w, None, labels.at[:, -1].set(3), 16, compute_dtype=jnp.float32))(x)
    assert not np.asarray(dx[:, -1]).any() and np.asarray(dx[:, 0]).any()


# ---- given targets with float weights (the block-diffusion objective) ----


def _dense_weighted(x, w, bias, targets, weights):
    logits = jnp.einsum("bsh,hv->bsv", x.astype(jnp.float32), w.astype(jnp.float32))
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return ((logz - gold) * weights).sum() / targets.size


@pytest.mark.parametrize("S,V,chunk,use_bias", [(16, 64, 16, False), (24, 50, 8, True),
                                                (7, 33, 5, False)])
def test_weighted_unshifted_loss_and_gradients_match_the_dense_form(S, V, chunk,
                                                                    use_bias):
    x, w, bias, labels = _inputs(S + V, 2, S, 12, V, use_bias)
    rng = np.random.default_rng(S)
    # 1/t where masked, else 0: heavy, and zero on most positions
    t = rng.uniform(1e-3, 1.0, size=labels.shape)
    weights = jnp.asarray(np.where(rng.random(labels.shape) < t, 1.0 / t, 0.0),
                          jnp.float32)
    args = (x, w) if bias is None else (x, w, bias)

    def chunked(*a):
        return chunked_cross_entropy_loss(a[0], a[1], a[2] if use_bias else None,
                                          labels, chunk, compute_dtype=jnp.float32,
                                          weights=weights)

    def dense(*a):
        return _dense_weighted(a[0], a[1], a[2] if use_bias else None, labels, weights)

    argnums = tuple(range(len(args)))
    want, want_g = jax.value_and_grad(dense, argnums)(*args)
    got, got_g = jax.value_and_grad(chunked, argnums)(*args)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    _assert_grads_close(got_g, want_g, atol=5e-5, rtol=5e-4)
    # and the unchunked path of the model means the same loss
    logits = jnp.einsum("bsh,hv->bsv", x, w) + (0.0 if bias is None else bias)
    np.testing.assert_allclose(cross_entropy_loss(logits, labels, weights=weights),
                               want, rtol=2e-6)


def test_weights_of_zero_and_one_on_shifted_targets_are_the_shifted_form():
    """Fed the shifted targets and a 0/1 weight, the weighted form is the
    causal-LM loss but for its normaliser (all positions, not the live)."""
    x, w, bias, labels = _inputs(3, 2, 16, 12, 64)
    labels = labels.at[0, 5].set(-100).at[1, 9].set(-100)
    shifted = jnp.pad(labels[:, 1:], ((0, 0), (0, 1)), constant_values=-100)
    live = shifted != -100
    targets = jnp.where(live, shifted, 0)
    want = chunked_cross_entropy_loss(x, w, None, labels, 16,
                                      compute_dtype=jnp.float32)
    got = chunked_cross_entropy_loss(x, w, None, targets, 16,
                                     compute_dtype=jnp.float32,
                                     weights=live.astype(jnp.float32))
    np.testing.assert_allclose(got * labels.size / live.sum(), want, rtol=2e-6)
    g_want = jax.grad(lambda x: chunked_cross_entropy_loss(
        x, w, None, labels, 16, compute_dtype=jnp.float32))(x)
    g_got = jax.grad(lambda x: chunked_cross_entropy_loss(
        x, w, None, targets, 16, compute_dtype=jnp.float32,
        weights=live.astype(jnp.float32)))(x)
    np.testing.assert_allclose(np.asarray(g_got) * labels.size / live.sum(),
                               np.asarray(g_want), atol=2e-6)


def test_the_weighted_form_is_still_one_sweep():
    """Loss and gradients in one scan of three vocabulary matmuls a chunk."""
    x, w, _, labels = _inputs(1, 2, 32, 12, 64)
    weights = jnp.ones(labels.shape, jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda x, w: chunked_cross_entropy_loss(
        x, w, None, labels, 8, weights=weights), (0, 1)))(x, w)
    scans, dots = [], []
    _walk(jaxpr.jaxpr, lambda e, inside: (
        scans.append(e) if e.primitive.name == "scan" else None,
        dots.append(e) if e.primitive.name == "dot_general" and inside else None))
    assert len(scans) == 1 and len(dots) == 3
