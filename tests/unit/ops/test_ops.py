"""Op-layer numerics tests (parity with reference ``tests/unit/ops``):
Pallas kernels in interpret mode vs the jnp reference path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import (apply_rotary_pos_emb, dequantize_int8_blockwise,
                               flash_attention, fused_adam_step, layer_norm, op_report,
                               quantize_int8_blockwise, rms_norm)
from deepspeed_tpu.ops.attention import _xla_attention
from deepspeed_tpu.ops.rope import precompute_rope_freqs


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_reference(causal):
    rng = jax.random.PRNGKey(0)
    ks = jax.random.split(rng, 3)
    B, S, H, D = 2, 64, 2, 32
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32) for kk in ks)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16, interpret=True)
    ref = _xla_attention(q, k, v, 1.0 / np.sqrt(D), causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_grad():
    rng = jax.random.PRNGKey(1)
    B, S, H, D = 1, 32, 2, 16
    q, k, v = (jax.random.normal(kk, (B, S, H, D)) for kk in jax.random.split(rng, 3))

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                                interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (_xla_attention(q, k, v, 1.0 / np.sqrt(D), True) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_attention_xla_fallback():
    q = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 2, 8))
    out = flash_attention(q, q, q, causal=True, force_pallas=False)
    assert out.shape == q.shape


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_gqa_in_kernel(causal):
    """G query heads share one KV head without expanding K/V."""
    rng = jax.random.PRNGKey(5)
    B, S, KV, G, D = 2, 48, 2, 4, 16
    kq, kk, kv_ = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (B, S, KV * G, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, KV, D), jnp.float32)
    v = jax.random.normal(kv_, (B, S, KV, D), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16, interpret=True)
    ref = _xla_attention(q, k, v, 1.0 / np.sqrt(D), causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_attention_gqa_grad_pallas_bwd():
    """The Pallas dq/dk/dv kernels (not an XLA recompute) must match the
    reference gradients, including the GQA head reduction into dk/dv."""
    rng = jax.random.PRNGKey(6)
    B, S, KV, G, D = 1, 32, 2, 2, 16
    kq, kk, kv_ = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (B, S, KV * G, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, KV, D), jnp.float32)
    v = jax.random.normal(kv_, (B, S, KV, D), jnp.float32)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                                interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (_xla_attention(q, k, v, 1.0 / np.sqrt(D), True) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("impl_bwd,calls", [(None, 2), ("fused", 2), ("pallas", 3)])
def test_flash_attention_bwd_is_pallas_not_recompute(impl_bwd, calls):
    """Lowering the grad must contain the backward's custom kernels (the
    forward and the fused dq/dk/dv kernel, which is what a small shape
    resolves to; or the forward, dq and dk/dv when the pair is pinned) —
    not an XLA softmax recompute."""
    q = jax.random.normal(jax.random.PRNGKey(7), (1, 16, 2, 8), jnp.float32)
    jaxpr = jax.make_jaxpr(
        jax.grad(lambda q: (flash_attention(q, q, q, causal=True, block_q=8,
                                            block_k=8, interpret=True,
                                            impl_bwd=impl_bwd) ** 2).sum()))(q)
    text = str(jaxpr)
    assert text.count("pallas_call") == calls, text.count("pallas_call")
    assert "softmax" not in text


def test_rms_norm():
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 6, 128))
    w = jax.random.normal(jax.random.PRNGKey(4), (128, )) + 1.0
    out = rms_norm(x, w, interpret=True)
    ref = rms_norm(x, w, force_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_layer_norm():
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 5, 64))
    w = jnp.ones((64, )) * 1.5
    b = jnp.ones((64, )) * 0.5
    out = layer_norm(x, w, b, interpret=True)
    ref = layer_norm(x, w, b, force_pallas=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    # matches plain normalization semantics
    mu = np.asarray(out).mean()
    assert np.isfinite(mu)


def test_rope_rotation_preserves_norm():
    cos, sin = precompute_rope_freqs(32, 128)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 16, 4, 32))
    out = apply_rotary_pos_emb(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(out), axis=-1),
        np.linalg.norm(np.asarray(x), axis=-1), atol=1e-4)
    # position 0 is identity
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(x[:, 0]), atol=1e-6)


@pytest.mark.parametrize("interp", [True, False])
def test_int8_quant_roundtrip(interp):
    x = jax.random.normal(jax.random.PRNGKey(7), (1000, )) * 5.0
    v, s = quantize_int8_blockwise(x, block_size=256, interpret=interp,
                                   force_pallas=interp)
    assert v.dtype == jnp.int8
    back = dequantize_int8_blockwise(v, s, x.shape, block_size=256)
    err = np.abs(np.asarray(back) - np.asarray(x)).max()
    scale_max = float(s.max())
    assert err <= scale_max * 0.51 + 1e-6  # within half an int8 step


def test_int8_quant_pallas_matches_xla():
    x = jax.random.normal(jax.random.PRNGKey(8), (4096, ))
    v1, s1 = quantize_int8_blockwise(x, block_size=512, interpret=True)
    v2, s2 = quantize_int8_blockwise(x, block_size=512, force_pallas=False)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


@pytest.mark.parametrize("interp", [True, False])
def test_fused_adam_step(interp):
    n = 5000
    p = jax.random.normal(jax.random.PRNGKey(9), (n, ))
    g = jax.random.normal(jax.random.PRNGKey(10), (n, ))
    m = jnp.zeros((n, ))
    v = jnp.zeros((n, ))
    p1, m1, v1 = fused_adam_step(p, g, m, v, lr=1e-2, step=1, interpret=interp,
                                 force_pallas=interp)
    # reference optax-style update
    mn = 0.1 * g
    vn = 0.001 * g * g
    upd = (mn / (1 - 0.9)) / (jnp.sqrt(vn / (1 - 0.999)) + 1e-8)
    pref = p - 1e-2 * upd
    np.testing.assert_allclose(np.asarray(p1), np.asarray(pref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(mn), atol=1e-6)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(vn), atol=1e-6)


@pytest.mark.parametrize("interp", [True, False])
def test_fused_lion_step(interp):
    import optax
    from deepspeed_tpu.ops import fused_lion_step
    n = 5000
    p = jax.random.normal(jax.random.PRNGKey(11), (n, ))
    g = jax.random.normal(jax.random.PRNGKey(12), (n, ))
    m = 0.3 * jax.random.normal(jax.random.PRNGKey(13), (n, ))
    p1, m1 = fused_lion_step(p, g, m, lr=1e-2, weight_decay=0.05,
                             interpret=interp, force_pallas=interp)
    tx = optax.lion(1e-2, b1=0.9, b2=0.99, weight_decay=0.05)
    state = tx.init(p)
    state = (state[0]._replace(mu=m), ) + tuple(state[1:])
    upd, _ = tx.update(g, state, p)
    pref = optax.apply_updates(p, upd)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(pref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(0.99 * m + 0.01 * g), atol=1e-6)


@pytest.mark.parametrize("interp", [True, False])
def test_fused_lamb_step_trust_ratio(interp):
    from deepspeed_tpu.ops import fused_lamb_step
    n1, n2 = 3000, 2096
    n = n1 + n2
    p = jax.random.normal(jax.random.PRNGKey(14), (n, ))
    g = jax.random.normal(jax.random.PRNGKey(15), (n, ))
    m = jnp.zeros((n, ))
    v = jnp.zeros((n, ))
    p1, m1, v1 = fused_lamb_step(p, g, m, v, lr=1e-2, step=1, weight_decay=0.01,
                                 segments=(0, n1, n), interpret=interp,
                                 force_pallas=interp)
    # per-segment oracle: adam update with bias correction, trust-scaled
    mn = 0.1 * g
    vn = 0.001 * g * g
    u = (mn / 0.1) / (jnp.sqrt(vn / 0.001) + 1e-6) + 0.01 * p
    outs = []
    for lo, hi in ((0, n1), (n1, n)):
        ps, us = p[lo:hi], u[lo:hi]
        trust = jnp.linalg.norm(ps) / jnp.linalg.norm(us)
        outs.append(ps - 1e-2 * trust * us)
    pref = jnp.concatenate(outs)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(pref), atol=1e-5)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(mn), atol=1e-6)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(vn), atol=1e-6)
    # whole-buffer trust differs from per-segment: segments must matter
    pw, _, _ = fused_lamb_step(p, g, m, v, lr=1e-2, step=1, weight_decay=0.01,
                               interpret=interp, force_pallas=interp)
    assert not np.allclose(np.asarray(pw), np.asarray(p1))


def test_op_report():
    rep = op_report()
    assert "flash_attention" in rep
    assert "quantizer_int8" in rep


def test_spatial_nhwc_bias_add_family():
    from deepspeed_tpu.ops.spatial import (nhwc_bias_add, nhwc_bias_add_add,
                                           nhwc_bias_add_bias_add)
    x = jax.random.normal(jax.random.PRNGKey(20), (2, 4, 4, 8), jnp.bfloat16)
    y = jax.random.normal(jax.random.PRNGKey(21), (2, 4, 4, 8), jnp.bfloat16)
    b = jax.random.normal(jax.random.PRNGKey(22), (8, ))
    b2 = jax.random.normal(jax.random.PRNGKey(23), (8, ))
    np.testing.assert_allclose(np.asarray(nhwc_bias_add(x, b), np.float32),
                               np.asarray(x + b.astype(jnp.bfloat16), np.float32))
    np.testing.assert_allclose(np.asarray(nhwc_bias_add_add(x, b, y), np.float32),
                               np.asarray(x + b.astype(jnp.bfloat16) + y, np.float32))
    np.testing.assert_allclose(
        np.asarray(nhwc_bias_add_bias_add(x, b, y, b2), np.float32),
        np.asarray(x + b.astype(jnp.bfloat16) + y + b2.astype(jnp.bfloat16), np.float32))
    with pytest.raises(ValueError):
        nhwc_bias_add(x, jnp.zeros((4, )))


def test_legacy_transformer_layer_api():
    from deepspeed_tpu.ops.transformer import (DeepSpeedTransformerConfig,
                                               DeepSpeedTransformerLayer)
    cfg = DeepSpeedTransformerConfig(batch_size=2, hidden_size=32, heads=4,
                                     num_hidden_layers=2)
    assert cfg.intermediate_size == 128  # reference default 4h
    layer = DeepSpeedTransformerLayer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 32))
    mask = jnp.ones((2, 8), jnp.int32)
    out = layer(x, mask)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
    with pytest.raises(NotImplementedError):
        DeepSpeedTransformerLayer(DeepSpeedTransformerConfig(
            hidden_size=32, heads=4, pre_layer_norm=True))


@pytest.mark.parametrize("window", [3, 8])
def test_flash_attention_sliding_window(window):
    """Windowed flash (Mistral local attention): values AND grads match the
    masked dense oracle; blocks fully outside the window are skipped."""
    rng = jax.random.PRNGKey(30)
    B, S, H, D = 1, 32, 2, 16
    q, k, v = (jax.random.normal(kk, (B, S, H, D)) for kk in jax.random.split(rng, 3))

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True, window=window, block_q=8,
                                block_k=8, interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (_xla_attention(q, k, v, 1.0 / np.sqrt(D), True, window) ** 2).sum()

    out = flash_attention(q, k, v, causal=True, window=window, block_q=8, block_k=8,
                          interpret=True)
    ref = _xla_attention(q, k, v, 1.0 / np.sqrt(D), True, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("window", [None, 24])
def test_flash_attention_softcap_values_and_grads(window):
    """Gemma-2 logit softcapping inside the kernel: cap*tanh(s/cap) BEFORE
    masking, gradient chained through (1 - tanh^2) — values and all three
    gradients must match the XLA oracle, incl. combined with local windows
    and GQA."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    B, S, H, KV, D = 2, 64, 4, 2, 32
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, KV, D), jnp.float32)
    cap = 5.0  # small cap so the tanh region is genuinely exercised

    out = flash_attention(q, k, v, causal=True, softcap=cap, window=window,
                          block_q=16, block_k=16, interpret=True)
    ref = _xla_attention(q, k, v, 1.0 / np.sqrt(D), True, window, cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def loss_k(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True, softcap=cap,
                                       window=window, block_q=16, block_k=16,
                                       interpret=True) ** 2)

    def loss_r(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, 1.0 / np.sqrt(D), True,
                                      window, cap) ** 2)

    g_k = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_k, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


class TestOnTpuGate:
    """The one is-this-a-TPU probe answers from the first device's platform
    and hides nothing: a backend that fails to come up raises."""

    def _probe(self, monkeypatch, devices):
        import importlib
        import jax
        reg = importlib.import_module("deepspeed_tpu.ops.registry")
        monkeypatch.setattr(jax, "devices", devices)
        reg.on_tpu.cache_clear()
        try:
            return reg.on_tpu()
        finally:
            reg.on_tpu.cache_clear()

    @staticmethod
    def _platform(name):
        import types
        return lambda *a: [types.SimpleNamespace(platform=name,
                                                 device_kind="")]

    def test_backend_error_propagates(self, monkeypatch):
        def broken(*a):
            raise RuntimeError("Unable to initialize backend 'tpu'")
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            self._probe(monkeypatch, broken)

    def test_tpu_backend_is_tpu(self, monkeypatch):
        assert self._probe(monkeypatch, self._platform("tpu")) is True

    def test_cpu_backend_is_not_tpu(self, monkeypatch):
        assert self._probe(monkeypatch, self._platform("cpu")) is False

    def test_platform_alone_decides(self, monkeypatch):
        """No second platform name: a device that is not platform "tpu" is
        not one, whatever its kind string says."""
        import types
        dev = types.SimpleNamespace(platform="weird",
                                    device_kind="TPU v5 lite")
        assert self._probe(monkeypatch, lambda *a: [dev]) is False
