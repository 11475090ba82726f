"""Kimi Delta Attention's chunk kernels (``ops/kda.py``: ``kda_chunk_fwd``,
``kda_chunk_bwd``), interpreted, against the recurrence token by token
(``kda_reference`` under ``bounded_gate``): the forward and the gradient of
every input; a sequence of one chunk, of several and one the chunk does not
divide; a gate at its bound ``g = -5`` over a whole chunk; ``beta = 0`` and
``beta = 1`` rows; chunks of 16 and 64; one, two and four heads a grid step
(``kernel_dispatch.choose_kda_heads``) and a block of heads against one head
a step bit for bit; the largest ``|S|`` at the chunks' ends; what the call
refuses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import kda
from deepspeed_tpu.ops import kernel_dispatch as kd

H, D = 2, 128
NAMES = ("q", "k", "v", "pre", "rate", "bias", "beta")


def _operands(seq, seed=1, gate="random", beta="random", dtype=jnp.float32, H=H):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)     # noqa: E731
    q = unit(jax.random.normal(ks[0], (1, seq, H, D))) * D ** -0.5
    k = unit(jax.random.normal(ks[1], (1, seq, H, D)))
    v = jax.random.normal(ks[2], (1, seq, H, D))
    pre = 2.0 * jax.random.normal(ks[3], (1, seq, H, D)) - 2.0
    if gate == "floor":         # sigmoid(.) = 1 in float32: g = -5 at every token
        pre = jnp.full_like(pre, 40.0)
    b = jax.nn.sigmoid(jax.random.normal(ks[4], (1, seq, H)))
    if beta == "ends":          # rows that write nothing, rows that write all
        b = b.at[:, 0::3].set(0.0).at[:, 1::3].set(1.0)
    rate = jax.random.uniform(ks[5], (H, ), minval=1.0, maxval=4.0)
    bias = 0.3 * jax.random.normal(ks[6], (H * D, ))
    weight = jax.random.normal(ks[7], (1, seq, H, D))
    return [a.astype(dtype) for a in (q, k, v, pre)] + [rate, bias, b], weight


def _recurrence(q, k, v, pre, rate, bias, beta, **kw):
    return kda.kda_reference(q, k, v, kda.bounded_gate(pre, rate, bias), beta, **kw)


def _both(args, weight, chunk):
    def of(fn):
        def loss(*a):
            out = fn(*a)
            return jnp.sum(out.astype(jnp.float32) * weight), out
        return jax.value_and_grad(loss, argnums=tuple(range(7)), has_aux=True)(*args)
    return (of(lambda *a: kda.kda_scan(*a, chunk, use_kernel=False, interpret=True)),
            of(_recurrence))


def _pin(monkeypatch, block):
    """``block`` heads a grid step, whatever the rule would give the shape."""
    monkeypatch.setattr(kd, "choose_kda_heads", lambda *shape: block)


# heads, then the heads a grid step: the rule's own (2 of 2, 2 of 6) or pinned
@pytest.mark.parametrize("chunk,seq,gate,beta,heads,block", [
    (64, 64, "random", "random", 2, None), (64, 192, "random", "random", 2, None),
    (16, 48, "random", "random", 2, None), (64, 100, "random", "random", 2, None),
    (64, 128, "floor", "random", 2, None), (16, 32, "floor", "ends", 2, None),
    (64, 128, "random", "ends", 2, None), (64, 128, "random", "random", 4, 1),
    (64, 128, "random", "ends", 4, 2), (64, 100, "random", "random", 4, 4),
    (64, 128, "random", "random", 6, None)],
    ids=["one_chunk", "three_chunks", "chunk16", "padded", "gate_at_its_bound",
         "bound_chunk16_beta_ends", "beta_0_and_1", "four_heads_one_a_step",
         "four_heads_two_a_step", "four_heads_a_step_padded", "six_heads_fall_to_two"])
def test_forward_and_every_gradient_match_the_recurrence(chunk, seq, gate, beta, heads,
                                                         block, monkeypatch):
    if block is None:
        assert kda.grid_of(1, seq, heads, D, chunk, 4) == (2, heads // 2 * -(-seq // chunk))
    else:
        _pin(monkeypatch, block)
    args, weight = _operands(seq, gate=gate, beta=beta, H=heads)
    ((_, out), grads), ((_, want), want_grads) = _both(args, weight, chunk)
    assert out.shape == want.shape == (1, seq, heads, D)
    np.testing.assert_allclose(out, want, atol=2e-6, rtol=2e-5)
    for name, g, w in zip(NAMES, grads, want_grads):
        scale = max(float(jnp.abs(w).max()), 1e-3)
        np.testing.assert_allclose(g, w, atol=2e-5 * scale, rtol=2e-4, err_msg=name)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_a_block_of_heads_is_one_head_a_step_bit_for_bit(dtype, monkeypatch):
    """The mathematics of a head does not change with the heads a grid step
    takes: the outputs, the chunk states, the largest ``|S|`` a head and lane
    and the seven gradients at two and four heads a step are those of one."""
    args, weight = _operands(128, dtype=dtype, H=4)

    def loss(*a):
        out = kda.kda_scan(*a, 64, use_kernel=False, interpret=True)
        return jnp.sum(out.astype(jnp.float32) * weight), out

    def at(block):
        _pin(monkeypatch, block)
        (_, out), grads = jax.value_and_grad(loss, argnums=tuple(range(7)),
                                             has_aux=True)(*args)
        q, k, v, pre, rate, bias, beta = args
        flat = lambda a: a.reshape(1, 128, 4 * D)       # noqa: E731
        lanes = jnp.zeros((kda.SUBLANES, 4 * D)).at[0].set(jnp.repeat(rate, D)).at[1].set(bias)
        kernel = kda._fwd_call(flat(q), flat(k), flat((beta[..., None] * k).astype(dtype)),
                               flat((beta[..., None] * v).astype(dtype)), flat(pre), lanes,
                               4, 64, kda.GATE_FLOOR, True, block)
        return dict(zip(("out", *NAMES, "o", "states", "tops"), (out, *grads, *kernel)))

    one = at(1)
    assert one["states"].shape == (1, 2, D, 4 * D) and one["tops"].shape == (1, 4, 8, D)
    for block in (2, 4):
        for name, got in at(block).items():
            np.testing.assert_array_equal(np.asarray(got, np.float32),
                                          np.asarray(one[name], np.float32), err_msg=name)


def test_bf16_operands_stay_within_bf16_of_the_recurrence():
    """The training precision: operands of a matmul in bf16, the state, the
    running sum and ``(I + A)^{-1}`` float32."""
    args, weight = _operands(128, dtype=jnp.bfloat16)
    ((_, out), grads), ((_, want), want_grads) = _both(args, weight, 64)
    assert out.dtype == jnp.bfloat16
    f32 = lambda a: np.asarray(a, np.float32)       # noqa: E731
    assert np.abs(f32(out) - f32(want)).max() <= 3e-2 * np.abs(f32(want)).max()
    for name, g, w in zip(NAMES, grads, want_grads):
        err = np.linalg.norm(f32(g) - f32(w)) / np.linalg.norm(f32(w))
        # the gate's gradients are sums of signed differences along the
        # sequence: bias reads 0.11 here, 0.13-0.15 in the cell on the chip
        assert err <= (0.25 if name in ("pre", "rate", "bias") else 5e-2), (name, err)


def test_the_largest_state_is_read_at_the_chunks_ends_and_the_sum_is_untouched():
    args, _ = _operands(192)
    out, top = kda.kda_scan(*args, 64, use_kernel=False, interpret=True,
                            with_state_absmax=True)
    want, want_top = _recurrence(*args, with_state_absmax=True, stat_every=64)
    np.testing.assert_allclose(out, want, atol=2e-6, rtol=2e-5)
    np.testing.assert_allclose(top, want_top, rtol=1e-5)
    _, every_token = _recurrence(*args, with_state_absmax=True)
    assert float(every_token) >= float(want_top) > 0.0


def test_off_the_kernels_the_call_is_the_recurrence_and_bad_shapes_are_refused():
    args, _ = _operands(48)
    out = kda.kda_scan(*args, 16, use_kernel=False)
    np.testing.assert_array_equal(out, _recurrence(*args))
    with pytest.raises(ValueError, match="multiple of 128"):
        kda.kda_scan(*(a[..., :64] if a.ndim == 4 else a for a in args[:4]),
                     args[4], args[5][:H * 64], args[6], 16, use_kernel=False,
                     interpret=True)
    with pytest.raises(ValueError, match="floor"):
        kda.kda_scan(*args, 16, use_kernel=False, interpret=True, floor=-8.0)
    with pytest.raises(ValueError, match="want q, k, pre"):
        kda.kda_scan(args[0], args[1][:, :8], *args[2:], 16, use_kernel=False)
    assert kda.scan_bytes(1, 100, 2, 128, 128, 64, 2) == 2 * (128 * 128 * 2 + 2 * 128 * 128 * 4)
