"""The gated short-convolution kernels (Pallas, interpreted on the CPU)
against their ``jax.numpy`` oracle: the forward and every gradient, at
lengths that are and are not multiples of the row block, over several column
passes, and by hand at the left edge."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops import short_conv as sc
from deepspeed_tpu.ops.short_conv import short_conv, short_conv_reference


def _case(batch, seq, width, taps, dtype, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    bcx = jax.random.normal(k[0], (batch, seq, 3 * width), dtype)
    w = jax.random.normal(k[1], (taps, width), jnp.float32)
    dy = jax.random.normal(k[2], (batch, seq, width), dtype)
    return bcx, w, dy


def test_the_oracle_by_hand():
    # one channel, three taps: v = B * u, c[t] = w0 v[t-2] + w1 v[t-1] + w2 v[t]
    gate_b, gate_c, u = [1., 2., 3., 4.], [1., 1., 2., 2.], [1., 1., 1., 2.]
    bcx = jnp.asarray(np.stack([gate_b, gate_c, u], -1)[None], jnp.float32)
    w = jnp.asarray([[0.5], [1.0], [2.0]], jnp.float32)
    v = [1., 2., 3., 8.]
    c = [2 * v[0], v[0] + 2 * v[1], 0.5 * v[0] + v[1] + 2 * v[2],
         0.5 * v[1] + v[2] + 2 * v[3]]
    want = np.asarray(gate_c) * np.asarray(c)
    np.testing.assert_allclose(np.asarray(short_conv_reference(bcx, w))[0, :, 0], want)
    got = short_conv(jnp.tile(bcx.reshape(1, 4, 3, 1), (1, 1, 1, 128)).reshape(1, 4, 384),
                     jnp.tile(w, (1, 128)), use_kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got)[0, :, 5], want)


# 600 and 1000 are no multiple of the 256-row block (nor 40 of the 16-row
# tile); 512 is one; 1024 columns are two passes of 512, 384 one of 384
CASES = [(2, 40, 128, 3, jnp.float32), (2, 600, 1024, 3, jnp.float32),
         (1, 512, 384, 3, jnp.float32), (1, 1000, 128, 3, jnp.bfloat16),
         (1, 300, 128, 4, jnp.float32), (3, 16, 128, 1, jnp.float32)]


@pytest.mark.parametrize("batch,seq,width,taps,dtype", CASES)
def test_kernel_forward_matches_the_oracle(batch, seq, width, taps, dtype):
    bcx, w, _ = _case(batch, seq, width, taps, dtype)
    got = short_conv(bcx, w, use_kernel=True, interpret=True)
    want = short_conv_reference(bcx, w)
    assert got.shape == (batch, seq, width) and got.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 0     # the same float32, rounded once
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), rtol=tol, atol=tol)


@pytest.mark.parametrize("batch,seq,width,taps,dtype", CASES)
def test_kernel_backward_matches_autodiff_of_the_oracle(batch, seq, width, taps, dtype):
    bcx, w, dy = _case(batch, seq, width, taps, dtype, seed=1)

    def scalar(fn):
        return lambda x, t: jnp.sum(fn(x, t).astype(jnp.float32) * dy.astype(jnp.float32))

    got = jax.grad(scalar(lambda x, t: short_conv(x, t, use_kernel=True, interpret=True)),
                   (0, 1))(bcx, w)
    want = jax.grad(scalar(short_conv_reference), (0, 1))(bcx, w)
    assert got[0].dtype == dtype and got[1].dtype == jnp.float32
    assert got[0].shape == bcx.shape and got[1].shape == w.shape
    # dB, dC, du (thirds of the first gradient) and the taps' gradient, each
    # against its own largest value; bf16 rounds the row gradients once
    rtol = 2e-5 if dtype == jnp.float32 else 1e-2
    parts = list(np.split(np.asarray(got[0], np.float32), 3, -1)) + [np.asarray(got[1])]
    wants = list(np.split(np.asarray(want[0], np.float32), 3, -1)) + [np.asarray(want[1])]
    for name, a, b in zip(("dB", "dC", "du", "dw"), parts, wants):
        assert np.abs(b).max() > 0
        assert np.abs(a - b).max() <= rtol * np.abs(b).max(), name


def test_without_the_kernel_it_is_the_oracle_and_shapes_are_checked():
    bcx, w, _ = _case(1, 24, 128, 3, jnp.float32)
    np.testing.assert_array_equal(np.asarray(short_conv(bcx, w, use_kernel=False)),
                                  np.asarray(short_conv_reference(bcx, w)))
    with pytest.raises(ValueError, match="3C"):
        short_conv(bcx[..., :256], w, use_kernel=False)
    with pytest.raises(ValueError, match="taps"):
        short_conv(bcx, jnp.zeros((sc.TAP_ROWS + 1, 128)), use_kernel=True, interpret=True)


def test_a_block_sees_the_rows_before_it_and_nothing_left_of_the_sequence():
    """Two sequences in one batch: the second's first rows must not see the
    first's last (the block before row 0 is masked, not read)."""
    bcx, w, _ = _case(2, 512, 128, 3, jnp.float32, seed=2)
    both = short_conv(bcx, w, use_kernel=True, interpret=True)
    alone = short_conv(bcx[1:], w, use_kernel=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(both[1]), np.asarray(alone[0]))
    # rows 256 and 257 (the second block's first) read rows 254 and 255
    moved = bcx.at[0, 255].add(1.0)
    out = short_conv(moved, w, use_kernel=True, interpret=True)
    changed = np.abs(np.asarray(out[0] - both[0])).max(axis=-1) > 0
    assert list(np.nonzero(changed)[0]) == [255, 256, 257]
