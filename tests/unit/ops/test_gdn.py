"""Gated DeltaNet's chunk kernels (``ops/gdn.py``: ``gdn_chunk_fwd``,
``gdn_chunk_bwd``), interpreted, through the fused entry ``gdn_fused`` against
a recurrence written out here token by token (numpy-plain, its key heads
repeated as the published code repeats them): the forward and the gradient of
every input (the raw q, k and v, the log decay, beta, the output gate, the
norm's weight) at the published 16 key heads under 32 value heads; a sequence
of several chunks and one the chunk does not divide; a gate far below any
floor (``g = -300`` rows: KDA's kernels refuse anything under -5); ``beta =
0`` and ``beta = 1`` rows and rows of zeros in q and k; GDN tied to KDA where
KDA's decay is constant over a head's key channels; the statistics; what the
call refuses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import gdn, kda

D = 128
NAMES = ("q", "k", "v", "g", "beta", "z", "weight")
EPS = 1e-6


def _operands(seq, Hk, Hv, seed=1, deep=False, edges=False, dtype=jnp.float32):
    """q and k as a convolution leaves them (no row of unit length), and what
    multiplies the output in the loss."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    q = 0.7 * jax.random.normal(ks[0], (1, seq, Hk, D))
    k = 1.3 * jax.random.normal(ks[1], (1, seq, Hk, D))
    v = jax.random.normal(ks[2], (1, seq, Hv, D))
    g = -jax.nn.softplus(2.0 * jax.random.normal(ks[3], (1, seq, Hv)))
    b = jax.nn.sigmoid(jax.random.normal(ks[4], (1, seq, Hv)))
    if deep:                    # no floor: a state wiped within a chunk, and again
        g = g.at[:, 5::17].set(-300.0).at[:, 40:44].set(-90.0)
    if edges:                   # rows that write nothing, rows that write all; dead tokens
        b = b.at[:, 0::3].set(0.0).at[:, 1::3].set(1.0)
        q = q.at[:, 1::5].set(0.0)
        k = k.at[:, 2::5].set(0.0)
    z = jax.random.normal(ks[5], (1, seq, Hv, D))
    norm = 1.0 + 0.2 * jax.random.normal(ks[6], (D, ))
    weight = jax.random.normal(ks[7], (1, seq, Hv, D))
    return [q.astype(dtype), k.astype(dtype), v.astype(dtype), g, b, z.astype(dtype),
            norm], weight


def _by_hand(q, k, v, g, beta, z, weight):
    """The mixer between its convolution and ``out_proj``, one token after
    another, nothing shared with ``ops/``: the key heads repeated under the
    value heads, then ``S <- e^g S; S <- S + beta k (v - S^T k)^T; o = S^T q``."""
    f32 = jnp.float32
    ratio = v.shape[2] // q.shape[2]

    def unit(a):
        a = a.astype(f32)
        return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)

    qn = jnp.repeat(unit(q) * D ** -0.5, ratio, axis=2)[0]            # [s, Hv, d]
    kn = jnp.repeat(unit(k), ratio, axis=2)[0]

    def token(S, inp):
        qt, kt, vt, gt, bt = inp
        S = jnp.exp(gt)[:, None, None] * S
        S = S + (bt[:, None] * kt)[:, :, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt))[:, None]
        return S, jnp.einsum("hkv,hk->hv", S, qt)

    init = jnp.zeros((v.shape[2], D, D), f32)
    _, o = jax.lax.scan(token, init, (qn, kn, v[0].astype(f32), g[0], beta[0]))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + EPS)
    return (o * weight * jax.nn.silu(z[0].astype(f32)))[None]


def _both(ops, weight, chunk=64):
    def loss(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weight)

    fused = lambda *a: gdn.gdn_fused(*a, chunk, eps=EPS, use_kernel=False,     # noqa: E731
                                     interpret=True)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(loss(_by_hand), argnums=tuple(range(7))))(*ops)
        got = jax.jit(jax.value_and_grad(loss(fused), argnums=tuple(range(7))))(*ops)
        out = (jax.jit(_by_hand)(*ops), jax.jit(fused)(*ops))
    return want, got, out


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.mark.parametrize("seq,Hk,Hv,deep,edges", [
    (128, 16, 32, False, False), (144, 2, 4, True, False), (96, 1, 2, False, True)],
    ids=["published_heads", "no_floor_and_a_ragged_end", "edges"])
def test_kernels_match_the_recurrence_forward_and_backward(seq, Hk, Hv, deep, edges):
    """float32: the output to 2e-5 and every gradient to 2e-4 of its norm (the
    log decay's is a sum of differences along the sequence: 1e-3 where rows of
    ``g = -300`` cut it)."""
    ops, weight = _operands(seq, Hk, Hv, deep=deep, edges=edges)
    (want_loss, want), (got_loss, got), (o_want, o_got) = _both(ops, weight)
    assert _rel(o_got, o_want) <= 2e-5
    assert abs(float(got_loss) - float(want_loss)) <= 1e-4 * abs(float(want_loss)) + 1e-3
    for name, g, w in zip(NAMES, got, want):
        assert g.shape == w.shape and np.all(np.isfinite(np.asarray(g))), name
        assert _rel(g, w) <= (1e-3 if name == "g" else 2e-4), (name, _rel(g, w))


def test_bf16_operands_lie_within_bf16_of_the_recurrence():
    ops, weight = _operands(128, 2, 4, dtype=jnp.bfloat16)
    (_, want), (_, got), (o_want, o_got) = _both(ops, weight)
    assert _rel(o_got, o_want) <= 1e-2
    for name, g, w in zip(NAMES, got, want):
        assert _rel(g, w) <= 2e-2, (name, _rel(g, w))


@pytest.mark.parametrize("kernels", [False, True], ids=["recurrence", "kernels"])
def test_gdn_is_kda_where_kdas_decay_is_constant_over_a_heads_key_channels(kernels):
    """One test ties the operators: with the key heads repeated under the
    value heads and ``g`` spread over a head's 128 key channels,
    ``kda_reference`` gives what ``gdn_reference`` gives, forward and for the
    gradients of q, k, v, g (summed over the channels) and beta, and what the
    GDN kernels give (through ``gdn_fused`` at ``silu(z) = 1`` and a weight of
    ones: the normed rows). The other way round does not hold: KDA's gate is
    a channel's own."""
    Hk, Hv, seq = 2, 4, 80
    (q, k, v, g, beta, _, _), weight = _operands(seq, Hk, Hv, seed=5)
    qn, kn = kda.l2norm(q, D ** -0.5, jnp.float32), kda.l2norm(k, 1.0, jnp.float32)

    def as_kda(qn, kn, v, g, beta):
        return kda.kda_reference(jnp.repeat(qn, 2, axis=2), jnp.repeat(kn, 2, axis=2), v,
                                 jnp.broadcast_to(g[..., None], (*g.shape, D)), beta)

    with jax.default_matmul_precision("highest"):
        want = as_kda(qn, kn, v, g, beta)
        if kernels:
            z = jnp.full(v.shape, 1.2784645)        # silu(z) = 1
            got = gdn.gdn_fused(q, k, v, g, beta, z, jnp.ones((D, )), 64, eps=EPS,
                                use_kernel=False, interpret=True)
            want = want * jax.lax.rsqrt(jnp.mean(want * want, -1, keepdims=True) + EPS)
            assert _rel(got, want) <= 5e-5
            return
        assert _rel(gdn.gdn_reference(qn, kn, v, g, beta), want) <= 1e-6
        loss = lambda fn: lambda *a: jnp.sum(fn(*a) * weight)       # noqa: E731
        gw = jax.grad(loss(as_kda), argnums=(0, 1, 2, 3, 4))(qn, kn, v, g, beta)
        gg = jax.grad(loss(gdn.gdn_reference), argnums=(0, 1, 2, 3, 4))(qn, kn, v, g, beta)
        for name, a, b in zip(NAMES, gg, gw):
            assert _rel(a, b) <= 1e-5, name


def test_the_statistics_and_what_the_call_refuses():
    """``state_absmax`` is the largest ``|S|`` at the chunks' ends (the
    recurrence counts the same tokens), ``decay_mean`` the mean of ``exp(g)``
    over heads and tokens, ``fused_rows`` says who made the norms; the kernels
    want heads of a multiple of 128, alike for keys and values, a chunk that
    is a multiple of 16 and at most 128 value heads; every path wants the
    value heads a multiple of the key heads and ``g``, ``beta`` one a value
    head."""
    ops, _ = _operands(128, 1, 2, seed=3)
    _, plain = gdn.gdn_fused(*ops, 64, eps=EPS, use_kernel=False, with_stats=True)
    _, fused = gdn.gdn_fused(*ops, 64, eps=EPS, use_kernel=False, interpret=True,
                             with_stats=True)
    assert float(plain["fused_rows"]) == 0.0 and float(fused["fused_rows"]) == 1.0
    assert float(fused["state_absmax"]) == pytest.approx(float(plain["state_absmax"]),
                                                         rel=1e-5)
    assert float(fused["decay_mean"]) == pytest.approx(float(jnp.mean(jnp.exp(ops[3]))))
    assert gdn.grid_of(1, 128, 1, 2, D, 64, 4) == (2, 2)
    assert gdn.grid_of(1, 32768, 16, 32, D, 64, 2) == (4, 8 * 512)
    assert gdn.scan_bytes(1, 128, 2, D, D, 64, 2) == 2 * (128 * D * 2 + 2 * D * D * 4)
    q, k, v, g, beta, z, w = ops
    with pytest.raises(ValueError, match="multiple of 16"):
        gdn.gdn_fused(*ops, 40, eps=EPS, use_kernel=False, interpret=True)
    with pytest.raises(ValueError, match="multiple of 128"):
        gdn.gdn_fused(q[..., :64], k[..., :64], v, g, beta, z, w, 64, eps=EPS,
                      use_kernel=False, interpret=True)
    with pytest.raises(ValueError, match="gdn_fused"):
        gdn.gdn_fused(q, k, v, g[..., :1], beta, z, w, 64, eps=EPS, use_kernel=False)
    with pytest.raises(ValueError, match="gdn_fused"):
        gdn.gdn_fused(jnp.concatenate([q] * 3, 2), jnp.concatenate([k] * 3, 2), v, g, beta,
                      z, w, 64, eps=EPS, use_kernel=False)
