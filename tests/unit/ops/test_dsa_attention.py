"""Learned sparse attention's kernels (``ops/dsa_attention.py``: ``dsa_index``,
``dsa_fwd``, the one-walk backward ``dsa_bwd``), interpreted, against the dense
form by hand (scores, ``lax.top_k``, a mask): the forward and every gradient,
the count a row chose and its smallest chosen score, a row with fewer than
``topk`` candidates, ties at the threshold, a tile with no chosen pair, a
group of eight; the one walk against the pinned pair (``dsa_bwd_dq``,
``dsa_bwd_dkdv``) bit for bit; the choice as ``dsa_index`` packs it (a bit
mask, read back in both orientations) and as ``dsa_mask`` makes it again; and
the blocks and the backward ``kernel_dispatch`` gives the call."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import dsa_attention as dsa
from deepspeed_tpu.ops import kernel_dispatch as kd

T, D, HI, DI, TOPK = 256, 32, 2, 16, 32
SCALE = 1.0 / np.sqrt(D)


def _operands(heads, kv, dtype=jnp.float32, seed=0, rows=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    shapes = ((rows, T, heads, D), (rows, T, kv, D), (rows, T, kv, D),
              (rows, T, HI, DI), (rows, T, DI), (rows, T, HI))
    q, k, v, qi, ki, w = (jax.random.normal(key, s, jnp.float32)
                          for key, s in zip(ks, shapes))
    return [a.astype(dtype) for a in (q, k, v, qi, ki)] + [w]


def _kernels(topk=TOPK, blocks=(64, 128)):
    return lambda *a: dsa.dsa_attention(*a, topk, blocks=blocks, interpret=True)


def _dense(topk=TOPK):
    return lambda *a: dsa.dense_dsa(*a, topk, SCALE)


@pytest.mark.parametrize("heads,kv", [(8, 1), (4, 2)], ids=["group8", "group2"])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 4e-2)],
                         ids=["float32", "bfloat16"])
def test_forward_and_every_gradient_match_the_dense_form(heads, kv, dtype, tol):
    """Four query tiles by two key tiles (a causal diagonal, an interior tile
    and a skipped one), rows 0-30 with fewer than ``topk`` candidates: the
    output, the pairs each row chose (exactly ``min(t + 1, topk)``), its
    smallest chosen score, dQ, dK and dV; the indexer's operands get zeros."""
    a = _operands(heads, kv, dtype)
    (out, chosen, kth), vjp = jax.vjp(_kernels(), *a)
    (want, want_chosen, want_kth), want_vjp = jax.vjp(_dense(), *a)
    assert out.shape == want.shape and out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_array_equal(chosen, want_chosen)
    np.testing.assert_array_equal(chosen[0], np.minimum(np.arange(T) + 1, TOPK))
    np.testing.assert_allclose(kth, want_kth, rtol=1e-6, atol=1e-6)
    g = jax.random.normal(jax.random.PRNGKey(9), out.shape, jnp.float32).astype(dtype)
    no = np.zeros(chosen.shape, jax.dtypes.float0)
    got, ref = vjp((g, no, jnp.zeros_like(kth))), want_vjp((g, no, jnp.zeros_like(kth)))
    for name, x, y in zip(("q", "k", "v"), got, ref):
        scale = float(jnp.abs(y.astype(jnp.float32)).max())
        np.testing.assert_allclose(np.asarray(x, np.float32), np.asarray(y, np.float32),
                                   atol=tol * scale, rtol=tol, err_msg=f"d{name}")
    for x in got[3:]:
        assert not np.any(np.asarray(x, np.float32))


def test_the_choice_is_lax_top_ks_and_ties_go_to_the_lower_position():
    """Scores that collide (small whole numbers: a dozen distinct values a
    row): the kernels' threshold and tie bound choose exactly ``topk`` a row,
    the very keys ``lax.top_k`` returns (the lower index first among equals)."""
    q, k, v, qi, ki, w = _operands(8, 1, seed=1, rows=2)
    qi, ki, w = jnp.round(qi), jnp.round(ki), jnp.round(2 * w)
    tau, tie, *_ = dsa.dsa_index(qi, ki, w, TOPK, (64, 128), interpret=True)
    scores = dsa.index_scores(qi, ki, w)
    causal = np.tril(np.ones((T, T), bool))
    assert len(np.unique(np.asarray(scores)[0, 200, :201])) < 40   # they do collide
    key = np.asarray(dsa._sortable(scores))
    pos = np.arange(T)
    mine = causal & ((key > np.asarray(tau)[..., None])
                     | ((key == np.asarray(tau)[..., None])
                        & (pos[None, None, :] <= np.asarray(tie)[..., None])))
    _, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), TOPK)
    want = np.zeros_like(mine)
    np.put_along_axis(want, np.asarray(idx), True, axis=-1)
    np.testing.assert_array_equal(mine, want & causal)
    assert (np.asarray(tie) < T).any()      # the tie path ran
    out, chosen, _ = _kernels()(q, k, v, qi, ki, w)
    np.testing.assert_array_equal(chosen, mine.sum(-1))
    np.testing.assert_allclose(out, _dense()(q, k, v, qi, ki, w)[0], atol=2e-5, rtol=2e-5)


def _colliding(rows=2):
    """Operands whose scores collide (small whole numbers): the tie path."""
    q, k, v, qi, ki, w = _operands(8, 1, seed=1, rows=rows)
    return q, k, v, jnp.round(qi), jnp.round(ki), jnp.round(2 * w)


def _unpacked(mask, blocks):
    """The whole choice [B, T, T] bool from ``dsa_index``'s words, by the
    layout ``mask_layout`` documents: bit ``t % bits`` of word [b, s // BK,
    t // bits, s % BK]."""
    bits = dsa.mask_layout(T, blocks[0])[0]
    t, s = np.arange(T)[:, None], np.arange(T)[None, :]
    words = np.asarray(mask)[:, s // blocks[1], t // bits, s % blocks[1]]
    return ((words >> (t % bits)) & 1).astype(bool)


@pytest.mark.parametrize("transposed", [False, True], ids=["forward", "transposed"])
@pytest.mark.parametrize("case", ["ties", "short_rows"])
def test_the_packed_mask_expands_to_the_choice_in_both_orientations(case, transposed):
    """``dsa_index``'s words, expanded a tile at a time as ``dsa_fwd`` does
    and transposed as the backward pair does, are ``_chosen`` under its
    ``tau`` and ``tie``: where scores collide and the tie bound decides, and
    in the first query tile, whose rows 0-30 have fewer than ``topk``
    candidates and take them all. (Whole-number operands: the host's scores
    are then the kernel's to the bit.)"""
    blocks = (64, 128)
    _, _, _, qi, ki, w = _colliding()
    tau, tie, _, _, mask = dsa.dsa_index(qi, ki, w, TOPK, blocks, interpret=True)
    assert (np.asarray(tie) < T).any()
    key = dsa._sortable(dsa.index_scores(qi, ki, w))
    bits, rows, block = dsa.mask_layout(T, blocks[0])
    assert (bits, rows, block) == (32, 2, 8) and mask.shape == (2, 2, T // 32, 128)
    tiles = [(0, 0)] if case == "short_rows" else [(1, 0), (3, 0), (3, 1), (2, 1)]
    for i, j in tiles:
        qs, ks = slice(i * 64, i * 64 + 64), slice(j * 128, j * 128 + 128)
        q_pos, k_pos = dsa._positions(i, j, *blocks)
        want = dsa._chosen(key[:, qs, ks], tau[:, qs, None], tie[:, qs, None], q_pos, k_pos)
        for b in range(2):
            got = dsa._expand(mask[b, j, i * rows:(i + 1) * rows], bits)
            if case == "short_rows":
                np.testing.assert_array_equal(got[:TOPK - 1] != 0, (k_pos <= q_pos)[:TOPK - 1])
            got, ref = (got.T, want[b].T) if transposed else (got, want[b])
            np.testing.assert_array_equal(got != 0, ref)
    # and the whole array, dead tiles zero
    causal = np.tril(np.ones((T, T), bool))
    whole = dsa._chosen(key, tau[..., None], tie[..., None],
                        jnp.arange(T)[:, None], jnp.arange(T)[None, :])
    np.testing.assert_array_equal(_unpacked(mask, blocks), np.asarray(whole) & causal)


@pytest.mark.parametrize("blocks", [(64, 128), (128, 256), (256, 128)],
                         ids=["two_word_rows", "four_word_rows", "a_block_a_tile"])
@pytest.mark.parametrize("case", ["ties", "seeded"])
def test_the_index_kernels_count_and_smallest_score_are_the_dense_forms(case, blocks):
    """What ``dsa_stats`` reads comes from ``dsa_index`` alone: the pairs a
    row chose and its smallest chosen score equal ``dense_dsa``'s, at every
    layout of the words (2 and 4 word rows of a block of 8, a block a tile)."""
    a = _colliding() if case == "ties" else _operands(8, 1, rows=2)
    _, _, chosen, kth, mask = dsa.dsa_index(*a[3:], TOPK, blocks, interpret=True)
    _, want_chosen, want_kth = _dense()(*a)
    np.testing.assert_array_equal(chosen, want_chosen)
    np.testing.assert_array_equal(chosen[0], np.minimum(np.arange(T) + 1, TOPK))
    np.testing.assert_allclose(kth, want_kth, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(_unpacked(mask, blocks).sum(-1), want_chosen)
    out, same, kth_out = _kernels(blocks=blocks)(*a)
    np.testing.assert_array_equal(same, chosen)
    np.testing.assert_array_equal(kth_out, kth)
    np.testing.assert_allclose(out, _dense()(*a)[0], atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("heads,kv", [(8, 1), (4, 2)], ids=["group8", "group2"])
@pytest.mark.parametrize("case", ["ties", "seeded"])
def test_a_backward_that_makes_the_mask_again_is_the_kept_paths_bit_for_bit(case, heads, kv):
    """``keep_mask=False`` (a recomputed layer that did not keep
    ``ds.dsa.mask``): the backward's ``dsa_mask`` gives ``dsa_index``'s very
    words from its ``tau`` and ``tie``, and dQ, dK, dV are the kept path's
    to the bit; the traced gradient holds one ``dsa_mask`` and no second
    ``dsa_index``."""
    a = _operands(heads, kv, seed=4, rows=2)
    if case == "ties":
        a[3:] = _colliding()[3:]
    tau, tie, _, _, mask = dsa.dsa_index(*a[3:], TOPK, (64, 128), interpret=True)
    np.testing.assert_array_equal(
        dsa.dsa_mask(*a[3:], tau, tie, (64, 128), interpret=True), mask)

    def loss(keep):
        return lambda *x: jnp.sum(jnp.square(dsa.dsa_attention(
            *x, TOPK, blocks=(64, 128), interpret=True, keep_mask=keep)[0]))

    kept = jax.grad(loss(True), argnums=(0, 1, 2))(*a)
    again = jax.grad(loss(False), argnums=(0, 1, 2))(*a)
    for x, y in zip(kept, again):
        np.testing.assert_array_equal(x, y)
    for keep, masks in ((True, 0), (False, 1)):
        text = str(jax.make_jaxpr(jax.grad(loss(keep), argnums=(0, 1, 2)))(*a))
        assert text.count("name=dsa_mask") == masks, keep
        assert text.count("name=dsa_index") == 1, keep


def test_a_tile_with_no_chosen_pair_is_skipped_and_changes_nothing():
    """``_skipping``'s indexer: the second key tile holds no chosen pair (its
    matmuls are skipped), and output and gradients are the dense form's."""
    a = q, k, v, qi, ki, w = _skipping(_operands(8, 1, seed=2))
    loss = lambda f: lambda *x: jnp.sum(jnp.square(f(*x)[0]))    # noqa: E731
    scores = np.asarray(dsa.index_scores(qi, ki, w))[0]
    assert (np.argsort(-scores[200, :201])[:TOPK] < 64).all()
    np.testing.assert_allclose(_kernels()(*a)[0], _dense()(*a)[0], atol=2e-5, rtol=2e-5)
    for x, y in zip(jax.grad(loss(_kernels()), argnums=(0, 1, 2))(*a),
                    jax.grad(loss(_dense()), argnums=(0, 1, 2))(*a)):
        np.testing.assert_allclose(x, y, atol=2e-4, rtol=2e-4)
    assert not np.any(jax.grad(loss(_kernels()), argnums=1)(*a)[0, 128:])


def _skipping(a):
    """An indexer that scores the first 64 keys far above the rest: every
    query past them chooses among those alone, and the second key tile holds
    no chosen pair."""
    q, k, v, qi, ki, w = a
    ki = jnp.abs(ki) * jnp.where(jnp.arange(T) < 64, 50.0, 1e-3)[None, :, None]
    return q, k, v, jnp.abs(qi), ki.astype(qi.dtype), jnp.abs(w)


@pytest.mark.parametrize("heads,kv,dtype,case", [
    (8, 1, jnp.float32, "seeded"), (4, 2, jnp.bfloat16, "seeded"),
    (8, 1, jnp.float32, "a_dead_tile")], ids=["group8_float32", "group2_bfloat16",
                                              "a_dead_tile"])
def test_the_one_walk_is_the_pinned_pair_bit_for_bit(heads, kv, dtype, case):
    """``dsa_bwd`` (what the rule gives every shape whose dK and dV fit VMEM)
    against ``bwd="pair"``: dQ, dK and dV equal to the bit (the sums run in
    the pair's order), with a tile the walk visits and skips; the traced
    gradient holds the one call or the two."""
    a = _operands(heads, kv, dtype, seed=5, rows=2)
    if case == "a_dead_tile":
        a = _skipping(a)

    def loss(bwd, blocks=(64, 128)):
        return lambda *x: jnp.sum(jnp.square(dsa.dsa_attention(
            *x, TOPK, blocks=blocks, interpret=True, bwd=bwd)[0].astype(jnp.float32)))

    walk = jax.grad(loss(None), argnums=(0, 1, 2))(*a)
    pair = jax.grad(loss("pair"), argnums=(0, 1, 2))(*a)
    for name, x, y in zip(("q", "k", "v"), walk, pair):
        assert x.dtype == dtype and np.any(np.asarray(x, np.float32)), name
        np.testing.assert_array_equal(np.asarray(x, np.float32), np.asarray(y, np.float32),
                                      err_msg=f"d{name}")
    if dtype == jnp.bfloat16:      # one case traces the two programs again
        for bwd, names in ((None, ["dsa_bwd"]), ("pair", ["dsa_bwd_dq", "dsa_bwd_dkdv"])):
            text = str(jax.make_jaxpr(jax.grad(loss(bwd), argnums=(0, 1, 2)))(*a))
            assert [n for n in text.split() if n.startswith("name=dsa_bwd")] == [
                f"name={n}" for n in names], bwd
        with pytest.raises(ValueError, match="bwd="):
            dsa.dsa_attention(*a, TOPK, blocks=(64, 128), interpret=True, bwd="two")
    if case == "a_dead_tile":
        assert not np.any(np.asarray(walk[1])[:, 128:])
        # at the rule's own tiles the walk's query tile (256) is two of the
        # forward's (128), whose log-sum-exp it lays out again
        sig = kd.make_sig(a[0].shape, kv, T, a[0].dtype, True, None, None)
        assert kd.choose_dsa_blocks(sig, HI, DI) == (128, 256)
        assert kd.resolve_dsa_bwd(sig, (128, 256)) == (kd.IMPL_FUSED, 256)
        for x, y in zip(jax.grad(loss(None, None), argnums=(0, 1, 2))(*a), walk):
            np.testing.assert_allclose(x, y, atol=2e-5 * float(jnp.abs(y).max()), rtol=2e-5)


def test_a_sequence_shorter_than_topk_is_causal_attention():
    from deepspeed_tpu.ops.attention import _xla_attention
    q, k, v, qi, ki, w = _operands(4, 2, seed=3)
    out, chosen, _ = _kernels(topk=4 * T)(q, k, v, qi, ki, w)
    np.testing.assert_array_equal(chosen[0], np.arange(T) + 1)
    np.testing.assert_allclose(out, _xla_attention(q, k, v, SCALE, True),
                               atol=2e-5, rtol=2e-5)


def test_the_sortable_key_keeps_float32s_order():
    x = jnp.asarray([-np.inf, -3.5, -1e-30, 0.0, 1e-30, 2.0, np.inf], jnp.float32)
    key = np.asarray(dsa._sortable(x))
    assert (np.diff(key.astype(np.int64)) > 0).all() and key.min() > dsa.INT_MIN
    np.testing.assert_array_equal(dsa.sortable_to_float(jnp.asarray(key)), x)


def test_blocks_and_vmem_of_the_cells_call():
    """1 x 32,768 tokens, 32 heads in groups of 8, head 128, a 16 x 64
    indexer: 128 queries (1,024 folded rows a KV head) by 512 keys; the
    indexer kernel's row scores are 16 MiB and every call carries its own
    limit; a group of 1 is capped by the row scores, not by MAX_ROWS."""
    sig = kd.make_sig((1, 32768, 32, 128), 4, 32768, "bfloat16", True, None, None,
                      pattern="dsa2048")
    assert kd.choose_dsa_blocks(sig, 16, 64) == (128, 512)
    index = kd.dsa_vmem_bytes("index", 1, 1, 64, 2, 128, 512, 32768, 16)
    assert 16 * 2**20 < index < 24 * 2**20
    # the words: 32 queries each, 4 word rows a query tile in blocks of 8,
    # 1 MiB a row of key tiles in VMEM, T * T / 8 bytes in HBM
    assert dsa.mask_layout(32768, 128) == (32, 4, 8)
    assert np.prod(dsa._mask_shape(1, 32768, (128, 512))) * 4 == 32768**2 // 8
    assert index - kd.dsa_vmem_bytes("mask", 1, 1, 64, 2, 128, 512, 32768, 16) == 16 * 2**20
    for leg in ("fwd", "bwd", "fused"):
        need = kd.dsa_vmem_bytes(leg, 4, 8, 128, 2, 128, 512, 32768)
        assert kd.VMEM_SCOPED_DEFAULT_BYTES < need < kd.FUSED_VMEM_CAP_BYTES, (leg, need)
    # the one walk holds ONE KV head's tiles and its float32 dK and dV of
    # every key (32 MiB here); where those pass the cap, 65,536 keys at head
    # 128, the rule hands back the pair, which a pin names at any shape
    fused = kd.dsa_vmem_bytes("fused", 4, 8, 128, 2, 128, 512, 32768)
    assert fused == kd.dsa_vmem_bytes("fused", 1, 8, 128, 2, 128, 512, 32768)
    assert 32 * 2**20 < fused < 48 * 2**20
    # its query tile: FUSED_MAX_ROWS folded rows (one block of the words'
    # rows) where the tiles are the rule's own and the estimate allows
    wide = kd.dsa_vmem_bytes("fused", 1, 8, 128, 2, 256, 512, 32768)
    assert fused < wide < kd.FUSED_VMEM_CAP_BYTES
    assert dsa.mask_layout(32768, 256) == (32, 8, 8)
    assert kd.resolve_dsa_bwd(sig, (128, 512)) == (kd.IMPL_FUSED, 256)
    assert kd.resolve_dsa_bwd(sig, (128, 512), widen=False) == (kd.IMPL_FUSED, 128)
    assert kd.resolve_dsa_bwd(sig, (128, 512), kd.DSA_BWD_PAIR) == (kd.DSA_BWD_PAIR, 128)
    for seq, want in ((49152, (kd.IMPL_FUSED, 128)), (65536, (kd.DSA_BWD_PAIR, 128))):
        longer = kd.make_sig((1, seq, 32, 128), 4, seq, "bfloat16", True, None, None,
                             pattern="dsa2048")
        assert kd.resolve_dsa_bwd(longer, (128, 512)) == want, seq
        assert kd.resolve_dsa_bwd(longer, (128, 512), kd.IMPL_FUSED) == (
            kd.IMPL_FUSED, 128)
    mha = kd.make_sig((1, 32768, 8, 128), 8, 32768, "bfloat16", True, None, None)
    assert kd.choose_dsa_blocks(mha, 16, 64) == (256, 512)
    with pytest.raises(ValueError, match="shorten the sequence"):
        kd.choose_dsa_blocks(kd.make_sig((1, 2**21, 8, 128), 8, 2**21, "bfloat16", True,
                                         None, None), 16, 64)
