"""The chunked state-space scan (``ops/ssd.py``): the Pallas kernels,
interpreted on the CPU, against the token-by-token recurrence, forward and
backward. Tolerances: float32 differs only by the order of its sums (the
chunked form adds a chunk's tokens in a matmul, the recurrence one at a
time): 1e-5 covers sequences of a few hundred tokens; in bf16 the kernels
round their matmul operands (8 bits), the recurrence nothing but its inputs,
so they differ by a few bf16 steps: 2e-2."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import ssd
from deepspeed_tpu.ops.registry import registry
from deepspeed_tpu.ops.ssd import ssd_reference, ssd_scan


def inputs(seed, b, s, H, P, N, dtype, dt_scale=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, s, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, H)) - 2.0) * dt_scale
    A = -jnp.exp(jax.random.uniform(ks[2], (H, ), minval=0.0, maxval=2.5))
    B, C = jax.random.normal(ks[3], (b, s, N)), jax.random.normal(ks[4], (b, s, N))
    D = jax.random.normal(ks[5], (H, ))
    return x.astype(dtype), dt, A, B.astype(dtype), C.astype(dtype), D


def value_and_grads(fn, args):
    def loss(*a):
        y = fn(*a).astype(jnp.float32)
        return jnp.sum(y * jnp.cos(0.1 * jnp.arange(y.size).reshape(y.shape))), y
    (_, y), grads = jax.value_and_grad(loss, argnums=tuple(range(6)), has_aux=True)(*args)
    return y, grads


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("seq,chunk,dtype,tol", [
    (64, 32, jnp.float32, 1e-5),        # whole chunks
    (80, 32, jnp.float32, 1e-5),        # the last chunk padded with dt = 0
    (24, 32, jnp.float32, 1e-5),        # shorter than a chunk
    (64, 32, jnp.bfloat16, 2e-2),
    (80, 32, jnp.bfloat16, 2e-2),
])
def test_kernels_match_the_recurrence_forward_and_backward(seq, chunk, dtype, tol):
    args = inputs(0, 2, seq, 8, 16, 32, dtype)
    y, grads = value_and_grads(lambda *a: ssd_scan(*a, chunk, use_kernel=False,
                                                   interpret=True), args)
    want_y, want = value_and_grads(ssd_reference, args)
    assert y.dtype == want_y.dtype and rel(y, want_y) < tol
    for name, got, ref in zip("x dt A B C D".split(), grads, want):
        assert got.shape == ref.shape and got.dtype == ref.dtype, name
        assert rel(got, ref) < (tol if name != "A" else 4 * tol), name


def test_large_decay_underflows_and_never_overflows():
    """``dt * A`` down to -50 a token: within a chunk of 32 the running sum
    reaches -1,000 and a naive ``exp(c_t) / exp(c_s)`` is 0 / 0 or inf; only
    differences are exponentiated, so the chunked form stays the recurrence."""
    args = inputs(1, 1, 96, 8, 16, 32, jnp.float32, dt_scale=40.0)
    a = np.asarray(args[1] * args[2])
    assert a.min() < -40 and np.cumsum(a[0, :32], axis=0).min() < -500
    y, grads = value_and_grads(lambda *a: ssd_scan(*a, 32, use_kernel=False,
                                                   interpret=True), args)
    want_y, want = value_and_grads(ssd_reference, args)
    assert np.isfinite(np.asarray(y)).all() and rel(y, want_y) < 1e-5
    for got, ref in zip(grads, want):
        assert np.isfinite(np.asarray(got)).all() and rel(got, ref) < 2e-4


def test_head_sizes_that_fill_a_lane_tile_or_share_one():
    for heads, head in ((4, 128), (8, 64), (16, 8)):
        args = inputs(2, 1, 64, heads, head, 16, jnp.float32)
        y = ssd_scan(*args, 32, use_kernel=False, interpret=True)
        assert rel(y, ssd_reference(*args)) < 1e-5, (heads, head)


def test_the_state_reported_is_the_largest_at_the_chunks_ends():
    args = inputs(3, 2, 80, 8, 16, 32, jnp.float32)
    _, kernels = ssd_scan(*args, 32, use_kernel=False, interpret=True,
                          with_state_absmax=True)
    _, recurrence = ssd_scan(*args, 32, use_kernel=False, with_state_absmax=True)
    _, every_token = ssd_reference(*args, with_state_absmax=True)
    np.testing.assert_allclose(float(kernels), float(recurrence), rtol=1e-5)
    assert float(every_token) >= float(recurrence) > 0


def test_without_a_tpu_the_recurrence_runs_and_shapes_are_checked():
    args = inputs(4, 1, 40, 4, 8, 16, jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(ssd_scan(*args, 32, use_kernel=False)), np.asarray(ssd_reference(*args)))
    with pytest.raises(ValueError, match="one group"):
        ssd_scan(args[0], args[1], args[2], args[3][:, :, None], args[4], args[5], 32,
                 use_kernel=False)
    assert "ssd" in registry and registry.report()["ssd"].backend == "pallas"
    assert ssd._heads_per_block(64) == 32 and ssd._heads_per_block(6) == 6
