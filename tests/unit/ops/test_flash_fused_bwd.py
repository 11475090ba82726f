"""The fused flash backward (``flash_dkdv_dq``: dQ, dK and dV from one walk
over the score tiles) against the pair it replaces (``flash_dq`` +
``flash_dkdv``) and against ``jax.vjp`` of the XLA reference, interpreted on
the CPU. Both backwards run on the same per-head forward's residuals, so in
float32 they agree to rounding: the fused kernel sums dQ over the kv blocks
in the order the dq kernel does. The same kernel walked a query range at a
time is ``test_flash_ranged_bwd.py``'s (a file of its own: a worker's share
under ``--dist loadfile``)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops import kernel_dispatch as kd
from deepspeed_tpu.ops.attention import _xla_attention, flash_attention


def _case(s, h, kv, d, blocks, *, b=1, sk=None, causal=True, window=None,
          softcap=None, dv=None):
    return dict(b=b, s=s, sk=sk or s, h=h, kv=kv, d=d, dv=dv or d, blocks=blocks,
                causal=causal, window=window, softcap=softcap)


CASES = {
    # groups 1 / 4 / 8 and head sizes 64 / 128 / 256, several q blocks in
    # the dQ accumulator, key blocks smaller and larger than the q block
    "g1_d64": _case(512, 2, 2, 64, (128, 128), b=2),
    "g4_d64": _case(512, 4, 1, 64, (128, 256)),
    "g8_d128": _case(512, 8, 1, 128, (64, 256)),
    "g2_d256_softcap": _case(512, 4, 2, 256, (128, 128), softcap=30.0),
    "g1_d128_keys_past_queries": _case(1024, 1, 1, 128, (256, 512), softcap=50.0),
    # a window: steps dead before it and after the diagonal, on both sides of
    # a kv block's sweep; an edge inside a block
    "g4_d128_window": _case(768, 4, 1, 128, (128, 128), window=200),
    "g2_d64_window_in_block": _case(1024, 4, 2, 64, (128, 512), window=300),
    "g1_d64_window_softcap": _case(512, 2, 2, 64, (128, 128), window=96,
                                   softcap=20.0),
    # a sequence 1,024 does not divide, with the blocks the dispatcher picks
    # for the fused kernel: (512, 512) and (384, 384)
    "g1_d64_seq1536": _case(1536, 1, 1, 64, None),
    "g4_d64_seq384": _case(384, 4, 1, 64, None),
    # no mask, more keys than queries, a window with no causal mask
    "g2_d64_full_more_keys": _case(256, 4, 2, 64, (128, 512), sk=1024, causal=False),
    "g2_d128_window_not_causal": _case(512, 2, 1, 128, (128, 128), causal=False,
                                       window=64),
    # one q block and one kv block: init, compute and write in one step
    "g4_d64_one_step": _case(128, 4, 1, 64, (128, 128)),
}
# cases of the walks' comparison alone (``_walks``)
WALK_CASES = {
    # a q block of two kv blocks: two kv sweeps open on the same q block,
    # and a q block's dQ leaves in the second kv block of its diagonal
    "g1_d128_queries_past_keys": _case(512, 1, 1, 128, (256, 128)),
}

# test_flash_ranged_bwd.py's cases (the same kernel walked a query range at a
# time) register here, so that ``_grads`` serves both files
RANGED_CASES = {}


@functools.lru_cache(maxsize=None)
def _results(name, dtype, impl_bwd, ranges=None, table=None):
    """o, dq, dk, dv of case ``name``, one compiled program a call (op by op,
    every small op of the regrouping and of the reference's backward is a
    compile of its own); kept, so the float32 reference serves every test
    that asks. ``table`` pins a masked call's walk: its table of live tiles
    wherever a tile is dead, or ``False`` the clamped rectangle; unpinned,
    the rule leaves most of these small calls the rectangle (few of their
    tiles are dead) and gives the table to four blocks a side."""
    case = CASES.get(name) or WALK_CASES.get(name) or RANGED_CASES[name]
    rng = np.random.default_rng(11)
    shape_q = (case["b"], case["s"], case["h"], case["d"])
    shape_kv = (case["b"], case["sk"], case["kv"], case["d"])
    q = jnp.asarray(rng.normal(size=shape_q), dtype)
    g = jnp.asarray(rng.normal(size=shape_q[:3] + (case["dv"], )), dtype)
    k = jnp.asarray(rng.normal(size=shape_kv), dtype)
    v = jnp.asarray(rng.normal(size=shape_kv[:3] + (case["dv"], )), dtype)
    # no blocks given: those the dispatcher picks for the fused kernel, for
    # the pair too (other blocks sum in another order)
    bq, bk = case["blocks"] or kd.choose_blocks(kd.make_sig(
        shape_q, case["kv"], case["sk"], "float32", case["causal"],
        case["window"], case["softcap"]), "fused")
    if impl_bwd == "reference":
        scale = 1.0 / np.sqrt(case["d"])

        def fn(q, k, v):
            return _xla_attention(q, k, v, scale, case["causal"], case["window"],
                                  case["softcap"])
    else:
        def fn(q, k, v):
            return flash_attention(
                q, k, v, causal=case["causal"], window=case["window"],
                softcap=case["softcap"], interpret=True,
                impl_bwd=impl_bwd, block_q=bq, block_k=bk, ranges=ranges,
                table=table)

    def run(q, k, v, g):
        o, pull = jax.vjp(fn, q, k, v)
        return (o, ) + pull(g)

    return [np.asarray(x, np.float32) for x in jax.jit(run)(q, k, v, g)]


def _grads(name, dtype, impl_bwd, ranges=None):
    """dq, dk, dv of case ``name``."""
    return _results(name, dtype, impl_bwd, ranges)[1:]


def assert_the_walks_are_equal(name, dtype, ranges=None):
    """o, dQ, dK and dV of the fused call on its table of live tiles equal
    the clamped rectangle's BIT FOR BIT: the table lists the rectangle's live
    steps in its order, so every sum takes the same terms in turn."""
    table = _results(name, dtype, "fused", ranges, True)
    grid = _results(name, dtype, "fused", ranges, False)
    for leaf, a, b in zip(("o", "dq", "dk", "dv"), table, grid):
        assert np.abs(b).max() > 0, leaf
        np.testing.assert_array_equal(a, b, err_msg=leaf)


@pytest.mark.parametrize("name,dtype", [
    ("g1_d64", jnp.float32),                    # BQ = BK, group 1, two rows
    ("g8_d128", jnp.bfloat16),                  # BQ < BK, group 8
    ("g1_d128_queries_past_keys", jnp.float32),     # BQ > BK
], ids=lambda x: x if isinstance(x, str) else x.__name__)
def test_the_tables_walk_equals_the_rectangles_bit_for_bit(name, dtype):
    """Causal calls, one range; under a window (narrower than a block, across
    blocks, without ``causal``), in ranges and at two widths the walks are
    compared in ``test_flash_ranged_bwd.py`` and ``test_mla_attention.py``."""
    assert_the_walks_are_equal(name, dtype)


@pytest.mark.parametrize("name", CASES)
def test_fused_backward_equals_the_pair_in_float32(name):
    fused = _grads(name, jnp.float32, "fused")
    pair = _grads(name, jnp.float32, "pallas")
    reference = _grads(name, jnp.float32, "reference")
    for leaf, a, b, c in zip(("dq", "dk", "dv"), fused, pair, reference):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=0, err_msg=leaf)
        np.testing.assert_allclose(a, c, atol=5e-5, rtol=5e-4, err_msg=leaf)


@pytest.mark.parametrize("name", CASES)
def test_fused_backward_in_bfloat16(name):
    """bf16 operands, float32 accumulators and softmax, as on the training
    path: the pair's results to a rounding of the outputs, the reference's
    to bf16's."""
    fused = _grads(name, jnp.bfloat16, "fused")
    pair = _grads(name, jnp.bfloat16, "pallas")
    reference = _grads(name, jnp.float32, "reference")
    for leaf, a, b, c in zip(("dq", "dk", "dv"), fused, pair, reference):
        np.testing.assert_allclose(a, b, atol=2e-2 * np.abs(b).max(), rtol=0,
                                   err_msg=leaf)
        assert np.abs(a - c).max() <= 4e-2 * np.abs(c).max(), leaf


def test_the_unpinned_backward_is_the_fused_kernel():
    """What ``flash_attention`` resolves to with nothing pinned is the fused
    backward at a shape that fits, and its gradient is the pinned one's."""
    case = CASES["g4_d64"]
    sig = kd.make_sig((1, 512, 4, 64), 1, 512, "float32", True, None, None)
    assert kd.resolve(sig)[1].impl == kd.IMPL_FUSED
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 512, 4, 64)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, 512, 1, 64)), jnp.float32)
            for _ in range(2))

    def loss(impl_bwd):
        return lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, interpret=True, force_pallas=True,
            impl_bwd=impl_bwd, block_q=case["blocks"][0],
            block_k=case["blocks"][1]) ** 2)

    auto = jax.grad(loss(None), argnums=(0, 1, 2))(q, k, v)
    pinned = jax.grad(loss("fused"), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(auto, pinned):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
