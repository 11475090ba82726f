"""``chunked_exit_cross_entropy``: the loss of a model that reads its head T
times a step, its weights a differentiable argument. Against the unchunked
loss written out here (shifted by one, weighted), value and every gradient,
the weights' among them; T streams in one sweep against T sweeps of one."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.chunked_ce import (chunked_cross_entropy_loss,
                                          chunked_exit_cross_entropy, seq_chunk)

B, T, S, H, V = 2, 3, 24, 16, 96


def _inputs(seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    xs = jax.random.normal(ks[0], (B, T, S, H), dtype)
    w = jax.random.normal(ks[1], (H, V), jnp.float32) * 0.3
    bias = jax.random.normal(ks[2], (V, ), jnp.float32) * 0.1
    labels = jax.random.randint(ks[3], (B, S), 0, V)
    weights = jax.nn.softmax(jax.random.normal(ks[4], (B, T, S)), axis=1)
    return xs, w, bias, labels, weights


def plain(xs, w, bias, labels, weights, ignore_index=-100):
    """The loss written out: logits whole, shift by one, weighted."""
    logits = jnp.einsum("btsh,hv->btsv", xs.astype(jnp.float32), w)
    if bias is not None:
        logits = logits + bias
    targets = labels[:, 1:]
    counted = (targets != ignore_index).astype(jnp.float32)
    gold = jnp.take_along_axis(logits[:, :, :-1],
                               jnp.where(targets == ignore_index, 0, targets)[:, None, :, None],
                               axis=-1)[..., 0]
    nll = (jax.nn.logsumexp(logits[:, :, :-1], axis=-1) - gold) * counted[:, None]
    return (weights[:, :, :-1] * nll).sum() / counted.sum(), nll


@pytest.mark.parametrize("chunk,with_bias", [(V, True), (24, True), (12, False), (7, False)],
                         ids=["whole", "quarter", "eighth-no-bias", "ragged-no-bias"])
def test_value_and_every_gradient_against_the_unchunked_loss(chunk, with_bias):
    xs, w, bias, labels, weights = _inputs()
    bias = bias if with_bias else None
    labels = labels.at[0, 5].set(-100)          # an ignored target

    def ours(xs, w, bias, weights):
        return chunked_exit_cross_entropy(xs, w, bias, labels, weights, chunk,
                                          compute_dtype=jnp.float32)[0]

    def theirs(xs, w, bias, weights):
        return plain(xs, w, bias, labels, weights)[0]

    argnums = (0, 1, 2, 3) if with_bias else (0, 1, 3)
    got, got_grads = jax.value_and_grad(ours, argnums)(xs, w, bias, weights)
    want, want_grads = jax.value_and_grad(theirs, argnums)(xs, w, bias, weights)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for g, t in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, t, rtol=2e-5, atol=1e-7)
    # the weight's cotangent is its position's own CE over the counted positions
    _, nll, counted = chunked_exit_cross_entropy(xs, w, bias, labels, weights, chunk,
                                                 compute_dtype=jnp.float32)
    np.testing.assert_allclose(nll[:, :, :-1], plain(xs, w, bias, labels, weights)[1],
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got_grads[-1], nll / counted.sum(), rtol=1e-6, atol=1e-9)
    assert float(counted.sum()) == B * (S - 1) - 1 and not np.any(got_grads[-1][:, :, -1])


def test_t_streams_in_one_sweep_equal_t_sweeps_of_one_stream():
    """A one-hot weight on stream t gives that stream's plain shifted CE, to
    the last bit of its value; the gradient reaches that stream alone."""
    xs, w, bias, labels, _ = _inputs(3)
    for t in range(T):
        only = jnp.zeros((B, T, S)).at[:, t].set(1.0)
        one_sweep = jax.value_and_grad(
            lambda xs: chunked_exit_cross_entropy(xs, w, bias, labels, only, 48,
                                                  compute_dtype=jnp.float32)[0])
        alone = jax.value_and_grad(
            lambda x: chunked_cross_entropy_loss(x, w, bias, labels, 48,
                                                 compute_dtype=jnp.float32))
        (got, dxs), (want, dx) = one_sweep(xs), alone(xs[:, t])
        np.testing.assert_allclose(got, want, rtol=1e-6)
        np.testing.assert_allclose(dxs[:, t], dx, rtol=1e-5, atol=1e-8)
        assert not np.any(np.delete(np.asarray(dxs), t, axis=1))


def test_the_sweeps_chunk_leaves_the_transient_logits_where_one_stream_had_them():
    assert seq_chunk(16384, 3072 // 4, 49152) == 256        # the Ouro cell's: 1,024 rows
    assert seq_chunk(16384, 3072, 49152) == 1024            # one stream at the same chunk
    xs, w, bias, labels, weights = _inputs()
    jaxpr = str(jax.make_jaxpr(lambda xs: chunked_exit_cross_entropy(
        xs, w, bias, labels, weights, 24, compute_dtype=jnp.float32)[0])(xs))
    sc = seq_chunk(S, 24 // T, V)
    assert f"f32[{B * T * sc},{V}]" in jaxpr and f"f32[{B * T * S},{V}]" not in jaxpr


def test_bf16_streams_keep_their_dtype_and_the_weights_gradient_is_float32():
    xs, w, bias, labels, weights = _inputs(5, jnp.bfloat16)
    dxs, dweights = jax.grad(
        lambda xs, weights: chunked_exit_cross_entropy(xs, w, bias, labels, weights, 24)[0],
        (0, 1))(xs, weights)
    assert dxs.dtype == jnp.bfloat16 and dweights.dtype == jnp.float32
    want = jax.grad(lambda weights: plain(xs, w, bias, labels, weights)[0])(weights)
    np.testing.assert_allclose(dweights, want, rtol=3e-2, atol=1e-4)
