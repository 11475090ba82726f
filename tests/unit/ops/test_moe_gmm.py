"""The experts' grouped matmul as the program's own kernel (``moe_gmm``,
``ops/grouped_matmul.py``), interpreted on the CPU at small shapes: each pass
against ``jax.lax.ragged_dot`` and its ``jax.vjp`` bit for bit, and the two
MLPs that call it, whole and as a share (both branches of its ``cond``),
against themselves with ``ragged_dot``.

Bit for bit needs sums that no order of addition can round differently (the
CPU's ``ragged_dot`` is a dense masked form, not the chip's kernel): the
operands are small integers, or integers over a power of two, so every
float32 sum is exact and the one rounding to bfloat16 falls the same way. On
the chip the comparison is made with normal operands
(``tests/perf/run_moe_gmm_sweep.py``, ``max_abs_diff`` 0.0).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops import grouped_matmul as gm
from deepspeed_tpu.ops import kernel_dispatch as kd

R, K, N, TILE = 96, 128, 256, 16

# sorted rows by expert: what a grid of (tile, expert) visits has to get right
GROUPS = {
    "straddling_tiles_and_an_empty_expert": [10, 0, 33, 16, 37],
    "shorter_than_a_tile": [3, 5, 1, 2, 4],
    "empty_first_and_between_rows_left_over": [0, 0, 50, 0, 10],
    "no_row_held": [0, 0, 0, 0, 0],
    "one_expert_holds_all": [0, 96, 0, 0, 0],
    "whole_tiles": [16, 16, 32, 16, 16],
    "rows_left_over_from_a_tile_edge": [16, 0, 16, 0, 0],
}


def _integers(key, shape, span=2):
    return jax.random.randint(key, shape, -span, span + 1).astype(jnp.bfloat16)


def _ragged(x, w, gs):
    return jax.lax.ragged_dot(x, w, gs, preferred_element_type=x.dtype)


def _same(got, want, what):
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32), err_msg=what)


@pytest.mark.parametrize("rows", [R, R - 8], ids=["whole_tiles_of_rows", "a_last_tile_cut"])
@pytest.mark.parametrize("groups", list(GROUPS))
def test_each_pass_is_ragged_dot_bit_for_bit(groups, rows):
    gs = jnp.asarray(GROUPS[groups], jnp.int32)
    if int(gs.sum()) > rows:
        gs = gs.at[jnp.argmax(gs)].add(rows - int(gs.sum()))
    held = int(gs.sum())
    keys = jax.random.split(jax.random.PRNGKey(len(groups)), 3)
    x, dy = _integers(keys[0], (rows, K)), _integers(keys[2], (rows, N))
    w = _integers(keys[1], (gs.size, K, N))
    # what lies past the rows held is no expert's: never read as a number
    x = x.at[held:].set(jnp.nan)
    dy = dy.at[held:].set(jnp.inf)
    got, pull = jax.vjp(lambda x, w: gm.moe_gmm(x, w, gs, TILE), x, w)
    want, ref_pull = jax.vjp(lambda x, w: _ragged(x, w, gs), x.at[held:].set(0), w)
    _same(got, want, "rows")
    assert not np.asarray(got[held:], np.float32).any()
    (dx, dw), (ref_dx, ref_dw) = pull(dy), ref_pull(dy.at[held:].set(0))
    _same(dx, ref_dx, "d_rows")
    assert not np.asarray(dx[held:], np.float32).any()
    _same(dw, ref_dw, "weights")
    assert not np.asarray(dw, np.float32)[np.asarray(gs) == 0].any()


def test_the_weights_sums_grow_the_same_rows_at_a_time_whatever_the_tile():
    """Float32 sums are the order they are added in: the weights' pass adds
    ``GMM_SUM_ROWS`` rows at a time at any tile (XLA's order on the chip), so
    with operands whose sums do round, a tile of 256 rows gives the bits a
    tile of 128 gives."""
    rows, k, n = 512, 128, 128
    gs = jnp.asarray([200, 0, 290], jnp.int32)          # 22 rows are no expert's
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    x = jax.random.normal(keys[0], (rows, k), jnp.bfloat16)
    dy = jax.random.normal(keys[1], (rows, n), jnp.bfloat16)
    by_tile = [gm._gmm_leg("weights", x, dy, gs, tile)
               for tile in (gm.GMM_SUM_ROWS, 2 * gm.GMM_SUM_ROWS)]
    _same(by_tile[1], by_tile[0], "weights")
    assert np.asarray(by_tile[0], np.float32)[0].any()
    assert not np.asarray(by_tile[0], np.float32)[1].any()


def test_the_visit_table_walks_every_tile_once_and_a_straddled_one_twice():
    e, t, out, lo, hi, first = (np.asarray(a) for a in gm._gmm_visits(
        jnp.asarray([10, 0, 33, 16, 37], jnp.int32), 112, 16, False))
    assert len(e) == 7 + 5 - 1
    # experts 0 | 2 2 2 | 3 3 | 4 4 4 over tiles 0 | 0 1 2 | 2 3 | 3 4 5, then
    # the one tile past the 96 rows held, then a step that does nothing
    assert list(e[:9]) == [0, 2, 2, 2, 3, 3, 4, 4, 4]
    assert list(t[:9]) == [0, 0, 1, 2, 2, 3, 3, 4, 5] == list(out[:9])
    assert list(lo[:9]) == [0, 10, 16, 32, 43, 48, 59, 64, 80]
    assert list(hi[:9]) == [10, 16, 32, 43, 48, 59, 64, 80, 96]
    assert list(first[:9]) == [1, 0, 1, 1, 0, 1, 0, 1, 1]
    assert (out[9], first[9], hi[9] - lo[9]) == (6, 1, 0)      # written as zeros
    assert (e[10], t[10], out[10], first[10], hi[10] - lo[10]) == (4, 5, 6, 0, 0)
    # the weights' pass visits the empty expert too, and no tile past the rows
    e, t, _, lo, hi, flags = (np.asarray(a) for a in gm._gmm_visits(
        jnp.asarray([10, 0, 33, 16, 37], jnp.int32), 112, 16, True))
    assert list(e[:10]) == [0, 1, 2, 2, 2, 3, 3, 4, 4, 4]
    assert list(flags[:10]) == [3, 3, 1, 0, 2, 1, 2, 1, 0, 2]
    assert hi[1] == lo[1] and flags[10] == 0 and hi[10] == lo[10]


T, H, F, E, TOPK, HELD = 64, 128, 128, 16, 4, 4


def _layer(skew, seed=3):
    """Tokens, experts and a routing whose every product and sum is exact:
    entries of -1, 0, 1 and combine weights that are eighths."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = _integers(ks[0], (T, H), 1)
    w1, w3 = (_integers(k, (E, H, F), 1) * (jax.random.uniform(k, (E, H, F)) < 0.1)
              for k in ks[1:3])
    w2 = _integers(ks[3], (E, F, H), 1) * (jax.random.uniform(ks[3], (E, F, H)) < 0.1)
    logits = jax.random.normal(ks[4], (T, E)) + skew * (jnp.arange(E) < HELD)
    _, idx = jax.lax.top_k(logits, TOPK)
    p = jnp.tile(jnp.asarray([0.5, 0.25, 0.125, 0.125], jnp.bfloat16), (T, 1))
    g = _integers(ks[5], (T, H), 1).astype(jnp.float32)
    return (x, w1.astype(jnp.bfloat16), w3.astype(jnp.bfloat16),
            w2.astype(jnp.bfloat16), idx, p), g


@pytest.fixture
def small_rule(monkeypatch):
    """The rule's row floor and tile brought down to the test's rows; where a
    kernel can run stays the rule's to ask."""
    monkeypatch.setattr(kd, "GMM_MIN_ROWS", 64)
    monkeypatch.setattr(kd, "GMM_ROW_TILE", 32)


def _both_ways(monkeypatch, fn, *args):
    """``fn``'s value and gradients with ``ragged_dot`` (a CPU: the rule's
    answer), then with the kernel (the placement answered as one TPU device)."""
    out = []
    for here in (False, True):
        monkeypatch.setattr(gm, "_kernel_here", lambda here=here: here)
        out.append(jax.jit(jax.value_and_grad(fn, argnums=(0, 1, 2, 3, 5),
                                              has_aux=True))(*args))
    return out


def test_the_whole_mlp_with_the_kernel_is_the_mlp_with_ragged_dot(
        monkeypatch, small_rule):
    args, g = _layer(0.0)

    def loss(*a):
        y = gm.moe_grouped_mlp(*a, activation=jax.nn.relu)
        return jnp.sum(y.astype(jnp.float32) * g), y

    ((_, y0), grads0), ((_, y1), grads1) = _both_ways(monkeypatch, loss, *args)
    assert np.asarray(y0, np.float32).any()
    _same(y1, y0, "y")
    for name, a, b in zip(("dx", "dw1", "dw3", "dw2", "dp"), grads1, grads0):
        assert np.asarray(b, np.float32).any(), name
        _same(a, b, name)


@pytest.mark.parametrize("skew,fallback", [(0.0, 0), (4.0, 1)],
                         ids=["the_static_rows", "the_exact_pass_in_windows"])
def test_a_share_with_the_kernel_is_the_share_with_ragged_dot(
        monkeypatch, small_rule, skew, fallback):
    args, g = _layer(skew)
    x, w1, w3, w2, idx, p = args
    held = (x, w1[:HELD], w3[:HELD], w2[:HELD], idx, p)

    def loss(*a):
        y, rows, fell = gm.moe_grouped_mlp_share(
            *a, first_expert=0, num_experts=E, activation=jax.nn.relu)
        return jnp.sum(y.astype(jnp.float32) * g), (y, rows, fell)

    ((_, (y0, rows0, fell0)), grads0), ((_, (y1, rows1, fell1)), grads1) = \
        _both_ways(monkeypatch, loss, *held)
    assert int(fell0) == int(fell1) == fallback and int(rows0) == int(rows1)
    assert np.asarray(y0, np.float32).any()
    _same(y1, y0, "y")
    for name, a, b in zip(("dx", "dw1", "dw3", "dw2", "dp"), grads1, grads0):
        assert np.asarray(b, np.float32).any(), name
        _same(a, b, name)


def test_the_route_taken_is_counted_by_pass_and_implementation(monkeypatch, small_rule):
    from deepspeed_tpu.observability import get_registry

    def count(leg, impl):
        return get_registry().counter("ds_moe_gmm_traced_total", labels={
            "leg": leg, "impl": impl}).value

    args, _ = _layer(0.0, seed=5)
    before = {(leg, impl): count(leg, impl) for leg in ("rows", "d_rows", "weights")
              for impl in ("moe_gmm", "ragged_dot")}

    def loss(*a):
        return jnp.sum(gm.moe_grouped_mlp(*a).astype(jnp.float32))

    monkeypatch.setattr(gm, "_kernel_here", lambda: True)
    jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*args)
    monkeypatch.setattr(gm, "_kernel_here", lambda: False)
    jax.jit(jax.grad(loss, argnums=(0, 1))).lower(*args)
    after = {key: count(*key) - n for key, n in before.items()}
    assert after == {("rows", "moe_gmm"): 3, ("d_rows", "moe_gmm"): 3,
                     ("weights", "moe_gmm"): 3, ("rows", "ragged_dot"): 3,
                     ("d_rows", "ragged_dot"): 0, ("weights", "ragged_dot"): 0}
    note = gm.traced_note()
    assert note.startswith("grouped_matmul[") and "rows=moe_gmm:" in note \
        and "rows=ragged_dot:" in note
