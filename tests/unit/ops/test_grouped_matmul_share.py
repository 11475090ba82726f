"""The grouped MLP of a chip that holds a share of the experts
(``moe_grouped_mlp_share``) against the dense oracle: every share's part,
their sum, the gradients, the static rows array and the exact pass over all
rows when a step outruns it."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.grouped_matmul import (SHARE_ROWS_FACTOR, expert_counts,
                                              moe_dense_mlp, moe_grouped_mlp,
                                              moe_grouped_mlp_share,
                                              moe_share_permutation, share_rows)

T, H, F, E, K, HELD = 64, 32, 16, 16, 4, 4


def _layer(skew=0.0, seed=1):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (T, H))
    w1, w3 = (jax.random.normal(k, (E, H, F)) * 0.2 for k in ks[1:3])
    w2 = jax.random.normal(ks[3], (E, F, H)) * 0.2
    logits = jax.random.normal(ks[4], (T, E)) + skew * (jnp.arange(E) < HELD)
    p, idx = jax.lax.top_k(jax.nn.sigmoid(logits), K)
    return x, w1, w3, w2, idx, p / p.sum(-1, keepdims=True)


def _share(x, w1, w3, w2, idx, p, s):
    own = slice(s * HELD, (s + 1) * HELD)
    return moe_grouped_mlp_share(x, w1[own], w3[own], w2[own], idx, p,
                                 first_expert=s * HELD, num_experts=E)


def test_the_rows_array_is_twice_the_even_share_in_whole_tiles():
    assert SHARE_ROWS_FACTOR == 2
    assert share_rows(131072, 8, 64) == 32768        # the cell: a quarter
    assert share_rows(T * K, HELD, E) == 128
    assert share_rows(100, 1, 16) == 16              # 2 * ceil(6.25) = 14 -> 16
    assert share_rows(256, 8, 16) == 256 == share_rows(256, 16, 16)   # never more
    assert share_rows(1024, 2, 16) == 256


def test_the_permutation_puts_the_rows_held_first_by_expert():
    idx = jnp.asarray([[5, 0], [1, 9], [4, 5], [7, 4]], jnp.int32)
    order, inv, sizes = moe_share_permutation(idx, first_expert=4, held=2)
    # flat assignments 0..7 choose 5,0,1,9,4,5,7,4: expert 4's (4, 7), then
    # expert 5's (0, 5), then the others in their order
    assert list(np.asarray(order)) == [4, 7, 0, 5, 1, 2, 3, 6]
    assert list(np.asarray(sizes)) == [2, 2]
    assert list(np.asarray(inv)[np.asarray(order)]) == list(range(8))


@pytest.mark.parametrize("skew,fallback", [(0.0, False), (3.0, True)])
def test_shares_add_up_to_the_dense_oracle_with_gradients(skew, fallback):
    args = _layer(skew)
    x, w1, w3, w2, idx, p = args
    want = moe_dense_mlp(*args)
    parts = [_share(*args, s) for s in range(E // HELD)]
    counts = np.asarray(expert_counts(idx, E))
    for s, (_, rows, fell) in enumerate(parts):
        assert int(rows) == counts[s * HELD:(s + 1) * HELD].sum()
        assert int(fell) == int(int(rows) > share_rows(T * K, HELD, E))
    assert bool(parts[0][2]) == fallback and sum(int(r) for _, r, _ in parts) == T * K
    np.testing.assert_allclose(np.asarray(sum(y for y, _, _ in parts)),
                               np.asarray(want), rtol=0, atol=2e-6)

    def total(x, w1, w3, w2, p):
        return jnp.sum(jnp.sin(sum(_share(x, w1, w3, w2, idx, p, s)[0]
                                   for s in range(E // HELD))))

    def dense(x, w1, w3, w2, p):
        return jnp.sum(jnp.sin(moe_dense_mlp(x, w1, w3, w2, idx, p)))

    got = jax.jit(jax.grad(total, (0, 1, 2, 3, 4)))(x, w1, w3, w2, p)
    ref = jax.grad(dense, (0, 1, 2, 3, 4))(x, w1, w3, w2, p)
    for name, a, b in zip(("dx", "dw1", "dw3", "dw2", "dp"), got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=2e-5,
                                   err_msg=name)


def test_a_share_adds_nothing_for_experts_it_does_not_hold():
    x, w1, w3, w2, idx, p = _layer()
    y, rows, _ = _share(x, w1, w3, w2, idx, p, 1)
    chosen_held = np.asarray((idx >= HELD) & (idx < 2 * HELD))
    untouched = ~chosen_held.any(axis=1)
    assert untouched.sum() > 5 and int(rows) == chosen_held.sum()
    assert not np.asarray(y)[untouched].any()            # exact zeros
    only = jnp.where(jnp.asarray(chosen_held), p, 0.0)   # the oracle, others' weights zero
    np.testing.assert_allclose(np.asarray(y), np.asarray(
        moe_dense_mlp(x, w1, w3, w2, idx, only)), rtol=0, atol=2e-6)


def test_a_share_that_is_everything_equals_the_all_experts_function():
    args = _layer()
    y, rows, fell = moe_grouped_mlp_share(*args, first_expert=0, num_experts=E)
    assert int(rows) == T * K and int(fell) == 0
    np.testing.assert_allclose(np.asarray(y), np.asarray(moe_grouped_mlp(*args)),
                               rtol=0, atol=2e-6)


def test_bf16_rows_cross_in_bf16_and_stay_close():
    x, w1, w3, w2, idx, p = (a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a
                             for a in _layer())
    y, _, _ = _share(x, w1, w3, w2, idx, p, 0)
    assert y.dtype == jnp.bfloat16
    f32 = [a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a
           for a in (x, w1, w3, w2, idx, p)]
    want, _, _ = _share(*f32, 0)
    err = np.abs(np.asarray(y, np.float32) - np.asarray(want)).max()
    assert err <= 3e-2 * np.abs(np.asarray(want)).max()
