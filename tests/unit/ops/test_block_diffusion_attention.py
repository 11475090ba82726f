"""Block-diffusion attention: the ``bdattn_*`` kernels (interpreted) and the
XLA path against the mask written out literally.

Positions [0, L) are the noised copy, [L, 2L) the clean one. Query i sees
key j iff noisy->noisy: same block; noisy->clean: a strictly earlier block;
clean->clean: block-causal; clean->noisy: never.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops import attention as A
from deepspeed_tpu.ops import kernel_dispatch as kd


def _literal_mask(seq, block):
    """The four rules, one (i, j) pair at a time."""
    mask = np.zeros((2 * seq, 2 * seq), bool)
    for i in range(2 * seq):
        for j in range(2 * seq):
            bi, bj = (i % seq) // block, (j % seq) // block
            if i < seq and j < seq:
                mask[i, j] = bi == bj
            elif i < seq:
                mask[i, j] = bj < bi
            elif j >= seq:
                mask[i, j] = bj <= bi
    return mask


def _inputs(seed, rows, seq, heads, kv, d, dtype=jnp.float32):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    return (draw(rows, 2 * seq, heads, d), draw(rows, 2 * seq, kv, d),
            draw(rows, 2 * seq, kv, d), draw(rows, 2 * seq, heads, d))


def _oracle(q, k, v, w, mask):
    """Softmax attention under ``mask``, one head at a time, and its loss
    against the cotangent ``w``."""
    group = q.shape[2] // k.shape[2]
    kk, vv = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    return (jnp.einsum("bhqk,bkhd->bqhd", p, vv) * w).sum()


@pytest.mark.parametrize("seq,block", [(8, 4), (16, 8), (64, 32), (24, 4)])
def test_the_mask_is_the_four_rules(seq, block):
    mask = A.block_diffusion_mask(seq, block)
    np.testing.assert_array_equal(mask, _literal_mask(seq, block))
    # L^2 + L * B live pairs: a quarter of the dense (2L)^2 and half of causal
    assert int(mask.sum()) == seq * seq + seq * block
    assert not mask[seq:, :seq].any()                   # clean -> noisy: never
    assert mask[np.arange(2 * seq), np.arange(2 * seq)].all()  # each sees itself


@pytest.mark.parametrize("seq,block,heads,kv,tiles", [
    (64, 4, 4, 4, (16, 32)),      # group 1
    (64, 8, 8, 1, (32, 16)),      # group 8, key tile under the query tile
    (96, 32, 2, 2, (32, 96)),     # one block a query tile, one key tile
    (128, 4, 8, 1, (32, 64)),     # group 8
    (64, 32, 4, 2, (64, 64)),     # a single tile of each
])
def test_kernels_match_the_literal_mask_forward_and_gradients(seq, block, heads,
                                                              kv, tiles):
    q, k, v, w = _inputs(seq + block, 2, seq, heads, kv, 32)
    mask = jnp.asarray(_literal_mask(seq, block))

    def kernel(q, k, v):
        return (A.block_diffusion_attention(q, k, v, block, blocks_fwd=tiles,
                                            blocks_bwd=tiles, interpret=True) * w).sum()

    with jax.default_matmul_precision("highest"):
        want, want_g = jax.value_and_grad(_oracle, (0, 1, 2))(q, k, v, w, mask)
        got, got_g = jax.value_and_grad(kernel, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-5)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5, rtol=1e-4)


@pytest.mark.parametrize("block", [4, 8, 32])
def test_xla_path_and_chosen_tiles_agree_with_the_oracle(block):
    seq = 128
    q, k, v, w = _inputs(block, 1, seq, 8, 1, 16)
    mask = jnp.asarray(_literal_mask(seq, block))
    with jax.default_matmul_precision("highest"):
        want = _oracle(q, k, v, w, mask)
        # off a TPU and not interpreted: the XLA path under the same mask
        xla = (A.block_diffusion_attention(q, k, v, block) * w).sum()
        # interpreted, tiles from kernel_dispatch
        chosen = (A.block_diffusion_attention(q, k, v, block, interpret=True) * w).sum()
    np.testing.assert_allclose(xla, want, rtol=2e-5)
    np.testing.assert_allclose(chosen, want, rtol=2e-5)


def test_bf16_operands_stay_close_to_the_float32_oracle():
    seq, block = 64, 4
    q, k, v, w = _inputs(3, 1, seq, 8, 2, 32, jnp.bfloat16)
    mask = jnp.asarray(_literal_mask(seq, block))
    f32 = [a.astype(jnp.float32) for a in (q, k, v, w)]
    want = jax.grad(_oracle, (0, 1, 2))(*f32, mask)
    got = jax.grad(lambda q, k, v: (A.block_diffusion_attention(
        q, k, v, block, blocks_fwd=(16, 32), blocks_bwd=(16, 32),
        interpret=True).astype(jnp.float32) * f32[3]).sum(), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        err = np.linalg.norm(np.asarray(a, np.float32) - b) / np.linalg.norm(b)
        assert err < 2e-2, err


@pytest.mark.parametrize("tiles,why", [
    ((48, 32), "divide"),         # 48 does not divide L = 64
    ((16, 6), "divide"),          # nor 6
    ((2, 32), "multiples"),       # a query tile that cuts a block of 4
    ((128, 32), "divide"),        # a tile over the copies' border
])
def test_tiles_that_straddle_the_border_or_cut_a_block_are_refused(tiles, why):
    q, k, v, _ = _inputs(0, 1, 64, 2, 2, 16)
    with pytest.raises(ValueError, match="straddle"):
        A.block_diffusion_attention(q, k, v, 4, blocks_fwd=tiles, blocks_bwd=tiles,
                                    interpret=True)


def test_odd_lengths_are_refused_with_the_reason():
    q, k, v, _ = _inputs(0, 1, 33, 2, 2, 16)       # L = 33 is not blocks of 4
    with pytest.raises(ValueError, match="block length"):
        A.block_diffusion_attention(q, k, v, 4, blocks_fwd=(8, 8), blocks_bwd=(8, 8),
                                    interpret=True)


@pytest.mark.parametrize("seq,bq,bk", [(8192, 128, 512), (8192, 64, 512),
                                       (4096, 512, 512), (1024, 128, 128)])
def test_dead_tile_counts(seq, bq, bk):
    """Live steps of a KV head's sweep: each query tile's own-block step and
    the clean key tiles that start before its end; never a noisy key tile
    off the diagonal, never a clean tile past it."""
    live, interior, steps = A.block_diffusion_live_tiles(seq, bq, bk)
    num_q, num_k = seq // bq, seq // bk
    want_live = num_q + sum((i * bq + bq - 1) // bk + 1 for i in range(num_q))
    assert (live, steps) == (want_live, num_q * (1 + num_k))
    assert interior == sum(i * bq // bk for i in range(num_q))
    # against the dense 2L x 2L pass in the same tiles (both copies' query
    # tiles against every key tile): nearer a quarter than a half
    dense = (2 * num_q) * (2 * num_k)
    assert 2 * (live - num_q) / dense < 0.3
    # the index-map clamp names a live tile on every dead step
    for i in (0, num_q // 2, num_q - 1):
        last = (i * bq + bq - 1) // bk
        for t in range(1 + num_k):
            assert int(A._bd_clean_tile(i, t, bq, bk)) == min(max(t - 1, 0), last)


def test_blocks_at_the_cell_shape_and_the_backward_cap():
    """Head 128 in groups of 8 at 2 x 8,192 positions a sequence: 2,048
    folded rows (both copies x 8 heads x 128 queries) by 512 keys on both
    legs; the pattern is a field of the signature; a sequence whose clean keys' dK
    and dV pass the cap is refused, there being no two-pass pair."""
    sig = kd.make_sig((2, 16384, 32, 128), 4, 16384, "bfloat16", False, None, None,
                      pattern="bd4")
    assert sig.pattern == "bd4"
    plain = kd.make_sig((2, 16384, 32, 128), 4, 16384, "bfloat16", True, None, None)
    assert plain.pattern == "" and plain != sig._replace(causal=True)
    for leg in ("fwd", "bwd"):
        assert kd.choose_block_diffusion_blocks(sig, leg, 4) == (128, 512)
    assert kd.bdattn_vmem_bytes("bwd", 8, 128, 2, 128, 512, 8192) < kd.FUSED_VMEM_CAP_BYTES
    long = sig._replace(seq_q=2 * 131072, seq_k=2 * 131072)
    kd.choose_block_diffusion_blocks(long, "fwd", 4)
    with pytest.raises(ValueError, match="no two-pass backward"):
        kd.choose_block_diffusion_blocks(long, "bwd", 4)
    # tiles hold whole blocks of any length that divides L
    odd = sig._replace(seq_q=2 * 96, seq_k=2 * 96)
    assert kd.choose_block_diffusion_blocks(odd, "fwd", 32) == (96, 96)
    assert kd.choose_block_diffusion_blocks(odd, "fwd", 8)[0] % 8 == 0


def test_the_kernels_carry_their_own_names():
    """The benchmark's flash readers credit ``%flash_*`` calls with causal
    work from their shape: these kernels must not match them."""
    q, k, v, _ = _inputs(0, 1, 32, 2, 2, 16)
    text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: A.block_diffusion_attention(
        q, k, v, 4, blocks_fwd=(16, 16), blocks_bwd=(16, 16), interpret=True).sum(),
        (0, 1, 2)))(q, k, v))
    # the calls' own names: a lowering's debug locations also hold the frames
    # of whatever traced a shared helper first in this process
    names = {word[len("name="):] for word in text.split() if word.startswith("name=")}
    assert {"bdattn_fwd", "bdattn_bwd"} <= names, names
    assert not any(n.startswith("flash_") for n in names), names
