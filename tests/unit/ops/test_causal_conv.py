"""The ungated causal convolution with bias and SiLU (``ops/short_conv.py``:
``causal_conv``): the Pallas kernels, interpreted on the CPU, against
``jax.numpy``, forward and backward. Tolerances: float32 sums of four taps in
another order, 1e-5; in bf16 both round the same output once, 1e-2."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import short_conv as sc
from deepspeed_tpu.ops.registry import registry
from deepspeed_tpu.ops.short_conv import causal_conv, causal_conv_reference


def plain(x, w, bias):
    """Written out once more, token by token."""
    b, s, c = x.shape
    taps = w.shape[0]
    out = np.zeros((b, s, c), np.float32)
    xf, wf = np.asarray(x, np.float32), np.asarray(w, np.float32)
    for t in range(s):
        acc = np.asarray(bias, np.float32).copy()[None].repeat(b, 0)
        for j in range(taps):
            src = t - (taps - 1) + j
            if src >= 0:
                acc += wf[j] * xf[:, src]
        out[:, t] = acc / (1.0 + np.exp(-acc))
    return out


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("batch,seq,width,taps,dtype,tol", [
    (2, 40, 256, 4, jnp.float32, 1e-5),         # one block, padded rows
    (1, 300, 384, 4, jnp.float32, 1e-5),        # two blocks: both halos
    (2, 528, 128, 2, jnp.float32, 1e-5),        # three blocks, two taps
    (1, 272, 640, 4, jnp.float32, 1e-5),        # a width 512 does not divide
    (2, 64, 256, 4, jnp.bfloat16, 1e-2),
])
def test_kernels_match_jnp_forward_and_backward(batch, seq, width, taps, dtype, tol):
    ks = jax.random.split(jax.random.PRNGKey(seq), 4)
    x = jax.random.normal(ks[0], (batch, seq, width)).astype(dtype)
    w, bias = jax.random.normal(ks[1], (taps, width)), jax.random.normal(ks[2], (width, ))
    cot = jax.random.normal(ks[3], (batch, seq, width))

    def run(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * cot), argnums=(0, 1, 2))(
                x, w, bias)

    got, grads = run(lambda *a: causal_conv(*a, use_kernel=False, interpret=True))
    want, want_grads = run(causal_conv_reference)
    assert abs(float(got - want)) <= tol * abs(float(want)) + tol
    for g, r in zip(grads, want_grads):
        assert g.shape == r.shape and g.dtype == r.dtype and rel(g, r) < tol
    assert rel(causal_conv_reference(x, w, bias), plain(x, w, bias)) < max(tol, 1e-5)


def test_blocking_bias_and_refusals():
    assert sc._conv_blocking(16384, 4352) == (256, 16384, 256)    # Granite's xBC
    assert sc._conv_blocking(100, 2048) == (112, 112, 512)
    x, w = jnp.ones((1, 8, 128)), jnp.ones((4, 128))
    zero = causal_conv(x, w, jnp.zeros((128, )), use_kernel=False, interpret=True)
    one = causal_conv(x, w, jnp.ones((128, )), use_kernel=False, interpret=True)
    np.testing.assert_allclose(np.asarray(zero[0, :4, 0]),
                               [v / (1 + np.exp(-v)) for v in (1.0, 2.0, 3.0, 4.0)],
                               rtol=1e-6)
    assert float(one[0, 0, 0]) == pytest.approx(2 / (1 + np.exp(-2.0)), rel=1e-6)
    with pytest.raises(ValueError, match="bias"):
        causal_conv(x, w, jnp.ones((64, )), use_kernel=False)
    with pytest.raises(ValueError, match="taps"):
        causal_conv(x, jnp.ones((8, 128)), jnp.ones((128, )), use_kernel=False)
    assert registry.report()["causal_conv"].backend == "pallas"
    # the gated kernels keep the names a trace reader counts their bytes by
    assert sc._fwd_call.__name__ == "_fwd_call" and "short_conv_fwd" in \
        jax.make_jaxpr(lambda a, b: sc._fwd_call(a, b, True))(
            jnp.ones((1, 16, 384)), jnp.ones((3, 128))).pretty_print()
