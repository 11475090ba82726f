"""Latent attention's kernels (``ops/attention.py`` at two widths: q and k
192 wide, v and o 128; ``mla_fwd`` / ``mla_bwd``), interpreted, against
XLA's attention with the scores by hand: forward and every gradient, the
fused backward and the pair, float32 and bf16, a sequence of two tiles; and
the kernels at ONE width unchanged by the generalisation, bit for bit
against what the parent commit gave."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import kernel_dispatch as kd
from deepspeed_tpu.ops.attention import _xla_attention, flash_attention

B, S, H, D_QK, D_V = 1, 256, 2, 192, 128
SCALE = 1.0 / np.sqrt(D_QK)


def _operands(dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shapes = ((B, S, H, D_QK), (B, S, H, D_QK), (B, S, H, D_V), (B, S, H, D_V))
    return [jax.random.normal(k, s, jnp.float32).astype(dtype) for k, s in zip(ks, shapes)]


def _kernel(bwd):
    return lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=True, block_q=128, block_k=128,
        impl_bwd=bwd)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-6), (jnp.bfloat16, 4e-2)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("bwd", ["fused", "pallas"])
def test_forward_and_every_gradient_match_xla_at_192_and_128(bwd, dtype, tol):
    """Two tiles of 128 in each direction (so the causal diagonal, an interior
    tile and a skipped one): o is 128 wide, dQ and dK 192, dV 128; the scale
    defaults to ``1 / sqrt(192)``."""
    q, k, v, g = _operands(dtype)
    out, vjp = jax.vjp(_kernel(bwd), q, k, v)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want, want_vjp = jax.vjp(lambda q, k, v: _xla_attention(q, k, v, SCALE, True), *f32)
    assert out.shape == (B, S, H, D_V) and out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want),
                               atol=tol, rtol=tol)
    for name, got, ref in zip("qkv", vjp(g), want_vjp(g.astype(jnp.float32))):
        assert got.shape == ref.shape, name
        scale = float(jnp.abs(ref).max())
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(ref),
                                   atol=tol * scale, rtol=tol, err_msg=f"d{name}")


@pytest.mark.parametrize("d_qk,d_v,window", [(64, 128, 100)], ids=["64_128_window"])
def test_the_table_of_live_tiles_equals_the_rectangle_at_two_widths(d_qk, d_v, window):
    """Values WIDER than keys (a differential layer's call: 64 | 128, group
    2, causal under a window that ends inside a tile): ``mla_fwd`` and
    ``mla_bwd`` on their tables of live tiles (6 of the 3 x 3 a KV head) give
    the clamped rectangle's o, dQ, dK and dV bit for bit. 192 | 128 walks its
    table in ``test_flash_ranged_bwd.py``."""
    rng = np.random.default_rng(60)
    q, k, v, g = (jnp.asarray(rng.normal(size=(1, 384, h, d)), jnp.bfloat16)
                  for h, d in ((4, d_qk), (2, d_qk), (2, d_v), (4, d_v)))

    def walk(table):
        out, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window, interpret=True, block_q=128,
            block_k=128, table=table), q, k, v)
        return [np.asarray(a, np.float32) for a in (out, *vjp(g))]

    for name, a, b in zip(("o", "dq", "dk", "dv"), walk(True), walk(False)):
        assert np.abs(b).max() > 0, name
        np.testing.assert_array_equal(a, b, err_msg=name)


def test_the_shared_rope_keys_gradient_is_the_sum_over_the_heads():
    """The operator's form: ``k = [k_nope | k_r]`` with ONE 64-wide ``k_r`` a
    token broadcast over the heads. Through the kernels its gradient is the
    sum over the heads of dK's rope slice."""
    q, k, v, g = _operands(jnp.float32, seed=1)
    k_nope, k_r = k[..., :128], k[:, :, :1, 128:]

    def through(fn):
        def loss(k_nope, k_r):
            k = jnp.concatenate([k_nope, jnp.broadcast_to(k_r, (B, S, H, 64))], -1)
            return jnp.sum(fn(q, k, v) * g)
        return jax.grad(loss, (0, 1))(k_nope, k_r)

    got = through(_kernel("fused"))
    want = through(lambda q, k, v: _xla_attention(q, k, v, SCALE, True))
    full = jnp.concatenate([k_nope, jnp.broadcast_to(k_r, (B, S, H, 64))], -1)
    dk = jax.grad(lambda k: jnp.sum(_kernel("fused")(q, k, v) * g))(full)
    assert got[1].shape == (B, S, 1, 64)
    np.testing.assert_allclose(got[1], dk[..., 128:].sum(axis=2, keepdims=True),
                               rtol=1e-5, atol=1e-6)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_the_calls_are_named_mla_where_the_widths_differ_and_flash_where_not():
    q, k, v, g = _operands(jnp.float32)
    for bwd, names in (("fused", {"mla_fwd", "mla_bwd"}),
                       ("pallas", {"mla_fwd", "mla_bwd_dq", "mla_bwd_dkdv"})):
        text = str(jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(_kernel(bwd)(q, k, v) * g), (0, 1, 2)))(q, k, v))
        import re
        assert set(re.findall(r"name=((?:mla|flash)_\w+)", text)) == names
    text = str(jax.make_jaxpr(jax.grad(
        lambda q, k: jnp.sum(_kernel("fused")(q, k, k)), (0, 1)))(q, k))
    assert "mla_" not in text and "flash_dkdv_dq" in text


def test_dispatch_keys_the_value_width_and_leaves_one_width_as_it_was():
    """(192, 128) at 8,192 keys: the per-head forward at 1,024 x 512 and the
    fused backward at 512 x 512. A call at one width has the signature and
    the VMEM estimate it had."""
    sig = kd.make_sig((4, 8192, 16, 192), 16, 8192, "bfloat16", True, None, None,
                      v_dim=128)
    fwd, bwd = kd.resolve(sig)
    assert fwd == kd.Decision("pallas", 1024, 512)
    assert bwd == kd.Decision("fused", 512, 512)
    assert sig.v_dim == 128 and kd.vmem_width(sig.head_dim, sig.v_dim) == 256
    assert kd.fused_vmem_bytes(sig) < kd.FUSED_VMEM_CAP_BYTES
    same = kd.make_sig((4, 8192, 16, 128), 16, 8192, "bfloat16", True, None, None,
                       v_dim=128)
    assert same == kd.make_sig((4, 8192, 16, 128), 16, 8192, "bfloat16", True, None, None)
    assert same.v_dim == 0 and kd.vmem_width(same.head_dim, same.v_dim) == 128
    assert kd.resolve(same)[0] == kd.Decision("pallas", 1024, 1024)


# sha256 over o, dq, dk, dv (as float32 bytes) of the PARENT commit's kernels
# (468c2d4) on seeded operands whose values are multiples of a quarter
_PARENT = {
    "d64_group2_fused_f32": (
        dict(H=4, KV=2, D=64, dtype="float32", window=None, bwd="fused"),
        "81cb69cc83ccf49d030e1a75fd1f9d05d1ee5f5d4ece295799da48e2b2bea709"),
    "d128_group1_window_pair_bf16": (
        dict(H=2, KV=2, D=128, dtype="bfloat16", window=96, bwd="pallas"),
        "d056be2384b8e1462698d04357b72a79b20a367486ade0272bceb8b88e22533d"),
}


@pytest.mark.parametrize("case", sorted(_PARENT))
def test_one_width_gives_what_the_parent_gave_bit_for_bit(case):
    """The value width went into the wrappers' specs, not the kernels'
    arithmetic: at ``d_v == d`` the traced program is the parent's."""
    (shape, want) = _PARENT[case]
    H_, KV, D, dtype, window, bwd = (shape[k] for k in ("H", "KV", "D", "dtype",
                                                        "window", "bwd"))
    rng = np.random.default_rng(40)

    def make(*s):
        return jnp.asarray(rng.integers(-2, 3, size=s) * 0.25, dtype)

    q, k, v, g = make(1, 256, H_, D), make(1, 256, KV, D), make(1, 256, KV, D), \
        make(1, 256, H_, D)
    out, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, interpret=True, block_q=128, block_k=128,
        impl_bwd=bwd), q, k, v)
    h = hashlib.sha256()
    for a in (out, *vjp(g)):
        h.update(np.asarray(a.astype(jnp.float32)).tobytes())
    assert h.hexdigest() == want
