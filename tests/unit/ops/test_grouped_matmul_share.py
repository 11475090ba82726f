"""The grouped MLP of a chip that holds a share of the experts
(``moe_grouped_mlp_share``) against the dense oracle: every share's part,
their sum, the gradients, the static rows array and the exact pass over all
rows when a step outruns it."""

import re

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
    order, sizes = moe_share_permutation(idx, first_expert=4, held=2)
    # flat assignments 0..7 choose 5,0,1,9,4,5,7,4: expert 4's (4, 7), then
    # expert 5's (0, 5), then the others in their order
    assert list(np.asarray(order)) == [4, 7, 0, 5, 1, 2, 3, 6]
    assert list(np.asarray(sizes)) == [2, 2]


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


# --- sorted rows back to their tokens, by the rows held (PR 36) -------------

from deepspeed_tpu.ops import grouped_matmul as gm  # noqa: E402


def _choices(tokens, k, experts, rule):
    """``[tokens, k]`` distinct experts a token, by a rule of the test."""
    rng = np.random.default_rng(tokens + k)
    if rule == "random":
        idx = np.stack([rng.permutation(experts)[:k] for _ in range(tokens)])
    elif rule == "ladder":
        # token t has t % (k + 1) of its k choices among experts 0..k-1 (held
        # when held >= k): tokens with 0, 1, 2, .. k live rows
        idx = np.stack([np.concatenate([np.arange(t % (k + 1)),
                                        experts - 1 - np.arange(k - t % (k + 1))])
                        for t in range(tokens)])
    elif rule == "none_held":
        idx = np.stack([experts - 1 - rng.permutation(k) for _ in range(tokens)])
    elif rule == "skip_expert_1":
        pool = np.asarray([e for e in range(experts) if e != 1])
        idx = np.stack([rng.permutation(pool)[:k] for _ in range(tokens)])
    return jnp.asarray(idx, jnp.int32)


# (tokens, k, experts, held, rows or None for share_rows, rule)
_CASES = {
    "0_1_2_and_k_live_rows_a_token": (96, 4, 16, 4, 384, "ladder"),
    "an_empty_held_expert": (200, 2, 16, 4, None, "skip_expert_1"),
    "n_held_is_0": (64, 4, 16, 4, None, "none_held"),
    "n_held_is_R": (128, 2, 8, 8, None, "random"),
    "R_is_all_the_assignments": (300, 4, 16, 4, 1200, "random"),
    "a_block_of_tokens_over_two_chunks": (256, 4, 8, 4, None, "random"),
}


def _case(name, width=32, dtype=jnp.float32):
    tokens, k, experts, held, rows, rule = _CASES[name]
    idx = _choices(tokens, k, experts, rule)
    order, sizes = moe_share_permutation(idx, 0, held)
    rows = rows or share_rows(tokens * k, held, experts)
    n_held = int(sizes.sum())
    assert n_held <= rows
    ks = jax.random.split(jax.random.PRNGKey(len(name)), 3)
    garbage = jnp.where(jnp.arange(rows)[:, None] % 2 == 0, jnp.nan, jnp.inf)
    y = jnp.where(jnp.arange(rows)[:, None] < n_held,
                  jax.random.normal(ks[0], (rows, width)), garbage).astype(dtype)
    top_w = jax.random.uniform(ks[1], (tokens, k), minval=0.1)
    x = jax.random.normal(ks[2], (tokens, width)).astype(dtype)
    return dict(tokens=tokens, k=k, order_r=order[:rows], n_held=n_held, y=y,
                top_w=top_w, x=x, idx=idx, held=held,
                inv=jnp.argsort(order).astype(jnp.int32))


def _oracle(rows, scale, tok, n_held, tokens):
    live = (jnp.arange(rows.shape[0]) < n_held)[:, None]
    terms = jnp.where(live, rows.astype(jnp.float32), 0.0)
    if scale is not None:
        terms = jnp.where(live, terms * scale[:, None], 0.0)
    return jnp.zeros((tokens, rows.shape[1]), jnp.float32).at[tok].add(terms)


@pytest.mark.parametrize("kernel", [False, True], ids=["scatter", "kernel"])
@pytest.mark.parametrize("weighted", [False, True], ids=["plain", "weighted"])
@pytest.mark.parametrize("name", sorted(_CASES))
def test_rows_go_back_to_their_tokens_as_a_plain_scatter_add_says(
        name, weighted, kernel):
    c = _case(name)
    tok = c["order_r"] // c["k"]
    scale = c["top_w"].reshape(-1)[c["order_r"]] if weighted else None
    got = jax.jit(lambda y, s: gm._rows_to_tokens(
        y, s, tok, c["n_held"], c["tokens"], c["k"], use_kernel=kernel))(c["y"], scale)
    want = _oracle(c["y"], scale, tok, c["n_held"], c["tokens"])
    assert got.dtype == c["y"].dtype and got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=2e-6)
    # a token with no live row: exact zeros; NaN and Inf past n_held unread
    live_tok = np.asarray(tok)[:c["n_held"]]
    untouched = np.setdiff1d(np.arange(c["tokens"]), live_tok)
    assert not np.asarray(got)[untouched].any()
    assert np.isfinite(np.asarray(got)).all()
    if name.startswith("0_1_2"):
        per_token = np.bincount(live_tok, minlength=c["tokens"])
        assert set(per_token) == set(range(c["k"] + 1))


@pytest.mark.parametrize("name", sorted(_CASES))
def test_the_primitive_under_grad_is_the_oracle_under_grad(name):
    c = _case(name)
    tok = c["order_r"] // c["k"]
    scale = c["top_w"].reshape(-1)[c["order_r"]]
    probe = jax.random.normal(jax.random.PRNGKey(3), (c["tokens"], 32))

    def through(fn):
        return jax.grad(lambda y, s: jnp.sum(fn(y, s) * probe), (0, 1))(c["y"], scale)

    got = through(lambda y, s: gm._rows_to_tokens(
        y, s, tok, c["n_held"], c["tokens"], c["k"], use_kernel=False))
    want = through(lambda y, s: _oracle(y, s, tok, c["n_held"], c["tokens"]))
    for g, w in zip(got, want):
        assert np.isfinite(np.asarray(g)).all()
        assert not np.asarray(g)[c["n_held"]:].any()       # nothing for a dead row
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0, atol=2e-6)


@pytest.mark.parametrize("side", ["scatter", "kernel", "positions"])
@pytest.mark.parametrize("name", sorted(_CASES))
def test_dispatch_and_combine_transposes_against_plain_ops(name, side, monkeypatch):
    """By the rows (``inv`` None; the kernel's sum or the scatter-add) and by
    the token positions (the exact pass over all rows)."""
    monkeypatch.setattr(gm, "_kernel_here", lambda: side == "kernel")
    c = _case(name)
    inv = c["inv"] if side == "positions" else None
    k, order_r, n_held, tokens = c["k"], c["order_r"], c["n_held"], c["tokens"]
    tok = order_r // k
    live = (jnp.arange(order_r.size) < n_held)[:, None]
    probe_rows = jnp.where(live, jax.random.normal(jax.random.PRNGKey(5), c["y"].shape), jnp.nan)
    probe_tok = jax.random.normal(jax.random.PRNGKey(6), c["x"].shape)

    def plain_dispatch(x):
        return jnp.where(live, x[tok], 0)

    def plain_combine(y, top_w):
        return _oracle(y, top_w.reshape(-1)[order_r], tok, n_held, tokens)

    # the dispatch's transpose: the cotangent rows past n_held are garbage
    _, pull = jax.vjp(lambda x: gm.share_dispatch(x, order_r, inv, n_held, k), c["x"])
    _, pull_plain = jax.vjp(plain_dispatch, c["x"])
    dx, = pull(probe_rows)
    dx_plain, = pull_plain(jnp.where(live, probe_rows, 0))
    assert np.isfinite(np.asarray(dx)).all()
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_plain), rtol=0, atol=2e-6)
    # the combine, forward and both gradients
    out, pull = jax.vjp(lambda y, w: gm.share_combine(y, w, order_r, inv, n_held),
                        c["y"], c["top_w"])
    out_plain, pull_plain = jax.vjp(plain_combine, jnp.where(live, c["y"], 0), c["top_w"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_plain), rtol=0, atol=2e-6)
    for g, w in zip(pull(probe_tok), pull_plain(probe_tok)):
        assert np.isfinite(np.asarray(g)).all()
        np.testing.assert_allclose(np.asarray(g), np.asarray(jnp.where(
            live, w, 0) if w.shape == c["y"].shape else w), rtol=0, atol=2e-6)
    # the weight of an assignment that is not held gets no gradient
    dw = np.asarray(pull(probe_tok)[1]).reshape(-1)
    assert not dw[np.asarray(c["idx"]).reshape(-1) >= c["held"]].any()


@pytest.mark.parametrize("kernel", [False, True], ids=["scatter", "kernel"])
def test_the_share_with_either_sum_is_the_dense_oracle_in_bf16_too(kernel, monkeypatch):
    monkeypatch.setattr(gm, "_kernel_here", lambda: kernel)
    args = _layer()
    x, w1, w3, w2, idx, p = args

    def total(x, w1, w3, w2, p):
        return jnp.sum(jnp.sin(sum(_share(x, w1, w3, w2, idx, p, s)[0]
                                   for s in range(E // HELD))))

    def dense(x, w1, w3, w2, p):
        return jnp.sum(jnp.sin(moe_dense_mlp(x, w1, w3, w2, idx, p)))

    got = jax.jit(jax.value_and_grad(total, (0, 1, 2, 3, 4)))(x, w1, w3, w2, p)
    ref = jax.value_and_grad(dense, (0, 1, 2, 3, 4))(x, w1, w3, w2, p)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=3e-5)
    # weighted bf16 rows through the kernel's three exact pieces: the same
    # bits as the float32 sum cast once
    c = _case("0_1_2_and_k_live_rows_a_token", width=128, dtype=jnp.bfloat16)
    tok, scale = c["order_r"] // c["k"], c["top_w"].reshape(-1)[c["order_r"]]
    got = gm._rows_to_tokens(c["y"], scale, tok, c["n_held"], c["tokens"], c["k"])
    want = _oracle(c["y"], scale, tok, c["n_held"], c["tokens"]).astype(jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    off = np.asarray(got, np.float32) != np.asarray(want, np.float32)
    # the order of a token's float32 terms may differ: a rare last bit
    assert off.mean() < 1e-2
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               rtol=2**-7, atol=1e-6)


def _ops_moving_rows(text, entries, width):
    """The gathers and scatters of a StableHLO text whose index operand has
    ``entries`` index vectors and whose rows are ``width`` wide."""
    found = []
    for line in text.splitlines():
        if "stablehlo.gather" not in line and "stablehlo.scatter" not in line:
            continue
        types = re.findall(r"tensor<([0-9x]+)x(i32|i64|f32|bf16|f16)>", line)
        index = [np.prod([int(d) for d in dims.split("x")]) for dims, dt in types
                 if dt.startswith("i")]
        wide = [dims for dims, dt in types
                if not dt.startswith("i") and int(dims.split("x")[-1]) == width]
        if wide and any(n == entries for n in index):
            found.append(line.strip()[:160])
    return found


def test_the_program_walks_the_rows_held_not_the_token_positions():
    """Lowered for a TPU (the CPU's lowering decomposes the grouped matmul):
    the share's branch over R < T*k rows has no row-wide gather or scatter
    with T*k indices (the token-side form had two), and nine grouped matmuls
    (three forward, six in the backward); the whole path, every row live,
    keeps its gathers."""
    x, w1, w3, w2, idx, p = _layer()
    order, sizes = moe_share_permutation(idx, 0, HELD)
    bound = share_rows(T * K, HELD, E)
    assert bound < T * K

    def loss(x, w1, w3, w2, p):
        y = gm._share_rows_mlp(x, w1, w3, w2, p, order, sizes, rows=bound,
                               activation=jax.nn.silu)
        return jnp.sum(y ** 2)

    text = jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3, 4))).trace(
        x, w1[:HELD], w3[:HELD], w2[:HELD], p).lower(
            lowering_platforms=("tpu", )).as_text()
    assert sum("chlo.ragged_dot" in line for line in text.splitlines()) == 9
    assert not _ops_moving_rows(text, T * K, H)
    assert _ops_moving_rows(text, bound, H)             # by the rows held

    def whole(x, w1, w3, w2, p):
        return jnp.sum(moe_grouped_mlp(x, w1, w3, w2, idx, p) ** 2)

    text = jax.jit(jax.value_and_grad(whole, (0, 1, 2, 3, 4))).trace(
        x, w1, w3, w2, p).lower(lowering_platforms=("tpu", )).as_text()
    assert len(_ops_moving_rows(text, T * K, H)) >= 4   # the control


@pytest.mark.parametrize("kernel", [False, True], ids=["scatter", "kernel"])
def test_two_calls_on_the_same_inputs_give_the_same_bits(kernel, monkeypatch):
    monkeypatch.setattr(gm, "_kernel_here", lambda: kernel)
    x, w1, w3, w2, idx, p = _layer(seed=4)

    def run():
        fn = jax.jit(jax.value_and_grad(
            lambda x, w1, w3, w2, p: jnp.sum(_share(x, w1, w3, w2, idx, p, 0)[0] ** 2),
            (0, 1, 2, 3, 4)))
        return jax.tree_util.tree_leaves(fn(x, w1, w3, w2, p))

    for a, b in zip(run(), run()):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_which_side_walks_is_a_rule_of_the_shape_alone():
    # the cell: a quarter of the assignments, by the rows; its exact pass
    # over all of them, and a share of half the experts, by the positions
    assert gm.share_walks_rows(share_rows(131072, 8, 64), 131072)
    assert gm.share_walks_rows(share_rows(131072, 16, 64), 131072)
    assert not gm.share_walks_rows(131072, 131072)
    assert not gm.share_walks_rows(share_rows(131072, 32, 64), 131072)


@pytest.mark.parametrize("crowding,fallback", [(1, True), (2, False)])
def test_a_share_inside_one_routing_group_has_rows_for_every_token_keeping_it(
        crowding, fallback):
    """Every token keeps the half of the experts that holds the share's (a
    group-limited router with 2 groups of which 1 is kept, ``crowding`` 2):
    the share gets twice its even share and more, which outruns twice the
    even share and not twice what such a router sends at most."""
    assert share_rows(1024, 2, 16, 2) == 512 == share_rows(1024, 4, 16)
    assert share_rows(256, 8, 16, 2) == 256                  # never more than all
    x, w1, w3, w2, _, _ = _layer()
    logits = jax.random.normal(jax.random.PRNGKey(9), (T, E))
    logits = jnp.where(jnp.arange(E) < E // 2, logits, -jnp.inf)     # group 0 of 2
    p, idx = jax.lax.top_k(jax.nn.sigmoid(logits), K)
    p = p / p.sum(-1, keepdims=True)
    y, rows, fell = moe_grouped_mlp_share(x, w1[:HELD], w3[:HELD], w2[:HELD], idx, p,
                                          first_expert=0, num_experts=E,
                                          crowding=crowding)
    assert share_rows(T * K, HELD, E) < int(rows) <= share_rows(T * K, HELD, E, 2)
    assert bool(fell) == fallback
    held = jnp.where(idx < HELD, p, 0.0)                     # the others add nothing
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(moe_dense_mlp(x, w1, w3, w2, idx, held)),
                               rtol=0, atol=2e-6)

