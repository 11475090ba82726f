"""The fused flash backward (``flash_dkdv_dq``) walked a query RANGE at a
time (``ranges``: what ``kernel_dispatch.choose_ranges`` gives a sequence
whose float32 dQ does not fit in VMEM whole), interpreted on the CPU with the
count pinned at small shapes: the whole walk's dQ bit for bit, its dK and dV
to the rounding of one float32 sum over the ranges' partials, and the pair's
and ``jax.vjp(_xla_attention)``'s as the whole walk's are. A file of its own
beside ``test_flash_fused_bwd.py`` (a worker's share under ``--dist
loadfile``), whose cases' form and ``_grads`` it uses."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from aot_v5e import _pallas_calls
from deepspeed_tpu.ops.attention import flash_attention
from test_flash_fused_bwd import (RANGED_CASES, _case, _grads,
                                  assert_the_walks_are_equal)

# The walk by query ranges, (case, ranges): groups 1 and 8, heads 64 / 128 /
# 256 and the latent 192 | 128, and every mask. Four q blocks in four ranges,
# or three in three, is a range of ONE q block.
RANGED = {
    "g1_d64": (_case(512, 2, 2, 64, (128, 128), b=2), (2, 4)),
    "g8_d128": (_case(512, 8, 1, 128, (64, 256)), (2, 8)),
    "g8_d256": (_case(256, 8, 1, 256, (64, 128)), (2, 4)),
    "g2_d256_softcap": (_case(512, 4, 2, 256, (128, 128), softcap=30.0), (2, 4)),
    "g1_latent_192_128": (_case(512, 2, 2, 192, (128, 256), dv=128), (2, 4)),
    # a range boundary at 256 (and at 128 and 384): the second range's first
    # kv blocks are dead before its window, the first range's last ones dead
    # past its diagonal, and the kv block that holds a range's last query is
    # where its dQ leaves
    "g4_d128_window_across_a_boundary": (
        _case(512, 4, 1, 128, (64, 64), window=100), (2, 4)),
    "g2_d64_window_in_block_softcap": (
        _case(512, 4, 2, 64, (64, 256), window=150, softcap=20.0), (2, 8)),
    "g2_d128_window_not_causal": (
        _case(512, 2, 1, 128, (128, 128), causal=False, window=64), (2, 4)),
    # no mask, seq_q != seq_k both ways: every range walks every kv block
    "g2_d64_full_more_keys": (
        _case(256, 4, 2, 64, (128, 512), sk=1024, causal=False), (2, )),
    "g2_d64_full_fewer_keys": (
        _case(512, 2, 1, 64, (128, 128), sk=256, causal=False), (2, 4)),
    # one kv block, a q block a range: zeroed, summed and written in a sweep
    "g4_d64_one_q_block_a_range": (_case(384, 4, 1, 64, (128, 384)), (3, )),
}
RANGED_CASES.update({f"ranged_{name}": case for name, (case, _) in RANGED.items()})
RANGED_PARAMS = [pytest.param(f"ranged_{name}", r, id=f"{name}-r{r}")
                 for name, (_, ranges) in RANGED.items() for r in ranges]
# bf16 operands: the widest head at group 8, the two widths, a window across
# a boundary, a q block a range
BF16_PARAMS = [pytest.param(f"ranged_{name}", r, id=f"{name}-r{r}") for name, r in (
    ("g8_d256", 4), ("g1_latent_192_128", 2),
    ("g4_d128_window_across_a_boundary", 2), ("g4_d64_one_q_block_a_range", 3))]


@pytest.mark.parametrize("name,ranges", RANGED_PARAMS)
def test_the_walk_by_ranges_equals_the_whole_walk_in_float32(name, ranges):
    """dQ is the whole walk's bit for bit (a range's sum over the kv blocks
    is the sum the whole sequence's accumulator held for those q blocks);
    dK and dV are a float32 sum of the ranges' float32 partials, the whole
    walk's to a rounding of their size; and both are the pair's and the
    reference's as the whole walk is."""
    ranged = _grads(name, jnp.float32, "fused", ranges)
    whole = _grads(name, jnp.float32, "fused", 1)
    pair = _grads(name, jnp.float32, "pallas")
    reference = _grads(name, jnp.float32, "reference")
    np.testing.assert_array_equal(ranged[0], whole[0], err_msg="dq")
    for leaf, a, b, c, d in zip(("dq", "dk", "dv"), ranged, whole, pair, reference):
        size = max(1.0, np.abs(b).max())
        np.testing.assert_allclose(a, b, atol=1e-6 * size, rtol=0, err_msg=leaf)
        np.testing.assert_allclose(a, c, atol=1e-6 * size, rtol=0, err_msg=leaf)
        np.testing.assert_allclose(a, d, atol=5e-5, rtol=5e-4, err_msg=leaf)


@pytest.mark.parametrize("name,ranges", BF16_PARAMS)
def test_the_walk_by_ranges_in_bfloat16(name, ranges):
    """bf16 operands: dQ leaves a range in the input dtype as it leaves the
    whole walk, equal bit for bit; a partial of dK and dV is not rounded
    before the sum, so they differ from the whole walk's by one rounding."""
    ranged = _grads(name, jnp.bfloat16, "fused", ranges)
    whole = _grads(name, jnp.bfloat16, "fused", 1)
    reference = _grads(name, jnp.float32, "reference")
    np.testing.assert_array_equal(ranged[0], whole[0], err_msg="dq")
    for leaf, a, b, c in zip(("dq", "dk", "dv"), ranged, whole, reference):
        np.testing.assert_allclose(a, b, atol=8e-3 * np.abs(b).max(), rtol=0,
                                   err_msg=leaf)
        assert np.abs(a - c).max() <= 4e-2 * np.abs(c).max(), leaf


@pytest.mark.parametrize("name,ranges,dtype", [
    ("g1_latent_192_128", 2, jnp.float32),      # widths 192 | 128, BQ < BK
    # eight blocks a side in two ranges: (range, kv block) pairs with no live
    # tile past the first range's diagonal and before the second's window,
    # each kept as one step that writes its zeros
    ("g4_d128_window_across_a_boundary", 2, jnp.bfloat16),
], ids=lambda x: x if isinstance(x, str) else getattr(x, "__name__", f"r{x}"))
def test_the_ranged_tables_walk_equals_the_rectangles_bit_for_bit(name, ranges,
                                                                  dtype):
    """The walk by ranges on its table: dQ, and the float32 partials' sums
    dK and dV, equal the clamped ``(KV head, range, kv block, q block)``
    grid's bit for bit."""
    assert_the_walks_are_equal(f"ranged_{name}", dtype, ranges)


def _backward_call(ranges, **pins):
    """The ``pallas_call`` of the fused backward as ``flash_attention``
    traces it at ``[1, 512, 4/1, 64]``, float32, causal."""
    q = jax.ShapeDtypeStruct((1, 512, 4, 64), jnp.float32)
    k = jax.ShapeDtypeStruct((1, 512, 1, 64), jnp.float32)

    def attend(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True,
                               ranges=ranges, **pins)

    found = _pallas_calls(jax.make_jaxpr(
        lambda q, k, v, g: jax.vjp(attend, q, k, v)[1](g))(q, k, k, q).jaxpr)
    call, = [e for e in found if e.params["name"] == "flash_dkdv_dq"]
    assert [e.params["name"] for e in found] == ["flash_fwd", "flash_dkdv_dq"]
    mapping = call.params["grid_mapping"]
    blocks = [tuple(getattr(b, "block_size", b) for b in m.block_shape)
              for m in mapping.block_mappings]
    scratch = [v.aval.inner_aval.shape for v in
               call.params["jaxpr"].invars[-mapping.num_scratch_operands:]]
    results = [(a.shape, str(a.dtype)) for a in call.params["out_avals"]]
    return mapping.grid, blocks, scratch, results


def test_one_range_is_the_call_it_was_before_the_ranges():
    """At ``ranges=1`` (every shape whose whole-sequence dQ fits) the traced
    call is the one PR 34 wrote: grid (KV head, kv block, q block), the
    operands' and results' blocks, dk and dv then dq in the input dtype, the
    float32 dQ of the whole sequence in scratch, under the name the trace
    readers match. Pinned or resolved from the shape, it is the same call."""
    q_blk, kv_blk, row = (1, 4, 128, 64), (1, 256, 64), (1, 1, 1, 512)
    # the table (PR 60) changes the grid alone: 6 live tiles of the 2 x 4,
    # and 6 of the two ranges' 2 x 2 x 2 (the first range's second kv block
    # has no live tile and keeps one step)
    one = _backward_call(1, block_q=128, block_k=256, table=True)
    assert one[0] == (1, 6) and one[1:] == _backward_call(
        1, block_q=128, block_k=256, table=False)[1:]
    two = _backward_call(2, block_q=128, block_k=256, table=True)
    assert two[0] == (1, 7) and two[1:] == _backward_call(
        2, block_q=128, block_k=256, table=False)[1:]
    # with two dead tiles to six live the rule leaves this call the rectangle
    assert _backward_call(1, block_q=128, block_k=256)[0] == (1, 2, 4)
    for pins in (dict(block_q=128, block_k=256, impl_bwd="fused", table=False),
                 dict(block_q=128, block_k=256, table=False)):
        grid, blocks, scratch, results = _backward_call(1, **pins)
        assert grid == (1, 2, 4)
        assert blocks == [q_blk, kv_blk, kv_blk, q_blk, row, row,   # q k v do lse delta
                          kv_blk, kv_blk, q_blk]                    # dk dv dq
        assert scratch == [(256, 64), (256, 64), (4, 512, 64)]
        assert results == [((1, 512, 64), "float32"), ((1, 512, 64), "float32"),
                           ((1, 4, 512, 64), "float32")]
    assert _backward_call(None, block_q=128, block_k=256, table=False) == (
        grid, blocks, scratch, results)
    # two ranges: the range an axis of the same grid, dq first, the ranges'
    # float32 partials of dk and dv after it, half the sequence's dQ in scratch
    grid, blocks, scratch, results = _backward_call(2, block_q=128, block_k=256,
                                                    table=False)
    part = (1, 1, 256, 64)
    assert grid == (1, 2, 2, 2)
    assert blocks == [q_blk, kv_blk, kv_blk, q_blk, row, row, q_blk, part, part]
    assert scratch == [(256, 64), (256, 64), (2, 512, 64)]
    assert results == [((1, 4, 512, 64), "float32"), ((1, 2, 512, 64), "float32"),
                       ((1, 2, 512, 64), "float32")]
