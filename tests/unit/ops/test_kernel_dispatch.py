"""Attention dispatch: the per-head forward and both backwards (the dq +
dk/dv pair, the fused kernel) against the XLA oracle, what ``resolve`` makes
of a shape (the fused kernel at every shape, in the fewest query ranges whose
dQ accumulator fits, blocks from ``choose_blocks``), what a caller may pin,
the masked call the kernels refuse, and the sweep tool end-to-end on CPU.

All kernel execution is Pallas interpret mode (CPU).
"""

import importlib.util
import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deepspeed_tpu.ops import kernel_dispatch as kd
from deepspeed_tpu.ops.attention import flash_attention, _xla_attention

BWD_IMPLS = (kd.IMPL_PALLAS, kd.IMPL_FUSED)   # the pair, the one-pass kernel


def _qkv(b=2, s=128, h=4, kv=2, d=32, seed=7, dtype=jnp.float32, sk=None):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, sk or s, kv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, sk or s, kv, d)), dtype)
    return q, k, v


def _ref(q, k, v, causal=True, window=None, softcap=None):
    scale = 1.0 / np.sqrt(q.shape[-1])

    def loss(q, k, v):
        out = _xla_attention(q, k, v, scale, causal, window, softcap)
        return (out.astype(jnp.float32) ** 2).mean(), out

    (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                   has_aux=True)(q, k, v)
    return o, g


def _route(q, k, v, bwd, causal=True, window=None, softcap=None):
    # 64x64 blocks pin both legs to a multi-block grid even at the small
    # parity shapes, so the online-softmax accumulation across k-blocks
    # stays covered without paying interpret-mode cost for big sequences
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, interpret=True,
                              block_q=64, block_k=64, impl_bwd=bwd)
        return (out.astype(jnp.float32) ** 2).mean(), out

    (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                   has_aux=True)(q, k, v)
    return o, g


def _assert_parity(got, want, atol_o=2e-5, atol_g=5e-5):
    (o, g), (o_ref, g_ref) = got, want
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               atol=atol_o, rtol=atol_o)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=atol_g, rtol=atol_g)


# ---------------------------------------------------------------------------
# route parity: the forward with each backward vs the XLA oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("bwd", BWD_IMPLS)
def test_route_parity_causal(bwd):
    """The custom_vjp must produce oracle values AND oracle grads under both
    backwards: each reads the forward's residuals (o, and the log-sum-exp
    without its unit dimension) in its own block order."""
    q, k, v = _qkv()
    _assert_parity(_route(q, k, v, bwd), _ref(q, k, v))


@pytest.mark.parametrize("bwd", BWD_IMPLS)
@pytest.mark.parametrize("window,softcap", [(64, None), (None, 20.0),
                                            (64, 20.0)])
def test_route_parity_window_softcap(bwd, window, softcap):
    """Mask variants: sliding window and Gemma-2 softcap change both the
    forward math and the lse the bwd consumes."""
    q, k, v = _qkv(s=128, d=32)
    _assert_parity(_route(q, k, v, bwd, window=window, softcap=softcap),
                   _ref(q, k, v, window=window, softcap=softcap))


def test_route_parity_gqa_mixed():
    """GQA head grouping survives the regrouping of the residuals: the pair
    reads the lse a query block at a time ([B*KV, G, Sq, 1]) in one kernel
    and as rows of the transposed tile in the other."""
    q, k, v = _qkv(h=8, kv=2, d=32, s=128)
    _assert_parity(_route(q, k, v, "pallas"), _ref(q, k, v))


def test_bfloat16_route_parity():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    _assert_parity(_route(q, k, v, "pallas"), _ref(q, k, v), 3e-2, 3e-2)


@pytest.mark.parametrize("impl_bwd", ["pallas", "fused"])
@pytest.mark.parametrize("s,h,kv,fwd,bwd", [
    (2048, 2, 2, (1024, 1024), (1024, 512)),   # MHA, as the OLMoE cell
    (1024, 4, 2, (512, 512), (512, 512)),      # group 2
    (1024, 4, 1, (256, 512), (256, 512)),      # group 4, as the dense cell
])
def test_shape_chosen_blocks_match_the_dense_reference(s, h, kv, fwd, bwd,
                                                       impl_bwd):
    """The blocks ``choose_blocks`` gives each group (1024 folded rows a
    step), run as the dispatcher runs them: causal, bf16 operands, float32
    accumulation, against the dense reference in float32 on the same
    (bf16-rounded) inputs. bf16's unit roundoff is 3.9e-3 and p and ds are
    rounded to it before their matmuls: the relative L2 error reads
    2.0e-3 to 3.1e-3 at every block pair here, (256, 512) included."""
    q, k, v = _qkv(b=1, s=s, h=h, kv=kv, d=128, dtype=jnp.bfloat16)
    f_dec, b_dec = kd.resolve(kd.make_sig(q.shape, kv, s, q.dtype, True,
                                          None, None), impl_bwd=impl_bwd)
    assert (f_dec.block_q, f_dec.block_k) == fwd
    # the fused backward's own: (512, 512) at these groups
    assert (b_dec.block_q, b_dec.block_k) == (
        bwd if impl_bwd == "pallas" else (512, 512))
    scale = 1.0 / np.sqrt(q.shape[-1])
    w = jnp.asarray(np.random.default_rng(3).normal(size=q.shape), jnp.float32)

    def ref_loss(q, k, v):
        out = _xla_attention(q, k, v, scale, True)
        return (out * w).sum(), out

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, interpret=True,
                              impl_bwd=impl_bwd)
        return (out.astype(jnp.float32) * w).sum(), out

    (_, o_ref), g_ref = jax.value_and_grad(ref_loss, (0, 1, 2), has_aux=True)(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    (_, o), g = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(q, k, v)
    for name, got, ref in zip(("o", "dq", "dk", "dv"), (o, ) + g,
                              (o_ref, ) + g_ref):
        got, ref = np.asarray(got, np.float32), np.asarray(ref)
        assert np.isfinite(got).all(), name
        err = np.linalg.norm(got - ref) / np.linalg.norm(ref)
        assert err < 1e-2, (name, err)


# ---------------------------------------------------------------------------
# the dispatch table itself
# ---------------------------------------------------------------------------


def _bench_sig(**over):
    base = dict(q_shape=(8, 1024, 16, 64), kv_heads=16, seq_k=1024,
                dtype="bfloat16", causal=True, window=None, softcap=None)
    base.update(over)
    return kd.make_sig(base["q_shape"], base["kv_heads"], base["seq_k"],
                       base["dtype"], base["causal"], base["window"],
                       base["softcap"])


def test_bench_shape_routes_per_head_fwd_fused_bwd():
    """The 0.4B preset's shape (head 64, 1,024 keys, group 1), which a rule
    on the head size once sent to an XLA forward: the per-head forward at
    the shape's blocks and the fused backward at (512, 512). On the chip
    the XLA forward lost there as everywhere (PERF.md, PR 44)."""
    fwd, bwd = kd.resolve(_bench_sig())
    assert fwd == kd.Decision(kd.IMPL_PALLAS, 1024, 1024)
    assert (fwd.block_q, fwd.block_k) == kd.choose_blocks(_bench_sig(), "fwd")
    assert bwd == kd.Decision(kd.IMPL_FUSED, 512, 512)   # the fused kernel's
    assert (bwd.block_q, bwd.block_k) == kd.choose_blocks(_bench_sig(), "fused")


def _sig(seq, heads, kv, d, dtype="bfloat16", seq_k=None, window=None,
         batch=1):
    return kd.make_sig((batch, seq, heads, d), kv, seq_k or seq, dtype, True,
                       window, None)


@pytest.mark.parametrize("name,sig,fwd,bwd", [
    # each chip's call in train-zero3-seq4k (Mistral-7B widths)
    ("cell", _sig(4096, 32, 8, 128, window=4096), (256, 512), (256, 512)),
    ("chip_smoke", _sig(2048, 32, 8, 128, window=4096), (256, 512), (256, 512)),
    # train-lfm2moe-1chip-seq8k: head size 64 is laid out in 128 lanes
    ("lfm2_cell", _sig(8192, 32, 8, 64, batch=4), (256, 512), (256, 512)),
    # train-olmoe-1chip-seq4k, MHA: 1024 rows a step are 1024 queries, the
    # keys rise to them, and the backward's tiles pass the default at 1024
    # keys, so it keeps 512 (the PR 32 sweep's winners under the default)
    ("olmoe_cell", _sig(4096, 16, 16, 128, batch=4), (1024, 1024),
     (1024, 512)),
    ("group2", _sig(4096, 16, 8, 128, batch=4), (512, 512), (512, 512)),
    # the 0.4B preset: group 1 at head size 64 follows the same rule
    ("hd64_1024", _bench_sig(), (1024, 1024), (1024, 512)),
    ("mha_2048", _sig(2048, 16, 16, 128), (1024, 1024), (1024, 512)),
    ("mha_1536", _sig(1536, 16, 16, 128), (768, 768), (768, 768)),
    ("mha_512", _sig(512, 16, 16, 128), (512, 512), (512, 512)),
    ("llama3_70b", _sig(4096, 64, 8, 128), (128, 512), (128, 512)),
    ("group16", _sig(4096, 32, 2, 128), (128, 256), (128, 128)),
    # Gemma-2 is group 2 (16 / 8 heads of 256)
    ("gemma2_hd256", _sig(4096, 16, 8, 256), (512, 512), (512, 512)),
    ("ulysses_32k", _sig(32768, 8, 2, 128), (256, 512), (256, 512)),
    # fp32 operands: the backward's tiles pass the default, so it halves
    ("fp32", _sig(4096, 32, 8, 128, "float32"), (256, 512), (128, 512)),
    # at group 1 the tie of 1024 rows and 1024 keys gives up the keys first
    ("fp32_mha", _sig(4096, 16, 16, 128, "float32"), (1024, 512),
     (512, 512)),
    ("seq128", _sig(128, 8, 8, 64), (128, 128), (128, 128)),
    ("seq384", _sig(384, 8, 2, 64), (128, 384), (128, 384)),
    ("seq64", _sig(64, 4, 4, 16), (64, 64), (64, 64)),
    ("keys_ne_queries", _sig(256, 8, 2, 128, seq_k=1536), (256, 512),
     (256, 512)),
])
def test_blocks_follow_from_the_shape(name, sig, fwd, bwd):
    """``choose_blocks`` alone, no kernel: divisors of the sequences, a
    VMEM estimate under the limit it assumes (so the call keeps the
    compiler's default), and grid steps big enough to feed the MXU wherever
    the sequence allows."""
    group = sig.heads // sig.kv_heads
    itemsize = 4 if sig.dtype == "float32" else 2
    for leg, want in (("fwd", fwd), ("bwd", bwd)):
        bq, bk = kd.choose_blocks(sig, leg)
        assert (bq, bk) == want, (name, leg)
        assert sig.seq_q % bq == 0 and sig.seq_k % bk == 0
        est = kd.flash_vmem_bytes(leg, group, sig.head_dim, itemsize, bq, bk)
        assert est <= kd.VMEM_SCOPED_DEFAULT_BYTES, (name, leg, est)
        assert kd.vmem_limit_bytes(est) is None
        if sig.seq_k >= 512 and sig.seq_q >= 512 and group <= 8:
            assert bk >= 512 and group * bq >= 256, (name, leg)
        # a step never folds more than MAX_ROWS rows unless the 128-query
        # floor does it, and bf16 operands reach MAX_ROWS where they can
        assert group * bq <= max(kd.MAX_ROWS, 128 * group), (name, leg)
        if itemsize == 2 and sig.seq_q % kd.MAX_ROWS == 0:
            assert group * bq >= kd.MAX_ROWS, (name, leg)
    if name == "cell":
        bq, bk = kd.choose_blocks(sig, "bwd")
        assert bk >= 512 and 512 <= group * bq <= 1024
    # and it is what the legs resolve to: unpinned forward, and the pair
    # where it is pinned (the backward of every shape is the fused kernel,
    # with blocks of its own: below)
    fwd_dec, dec = kd.resolve(sig)
    assert fwd_dec == kd.Decision(kd.IMPL_PALLAS, *fwd)
    assert dec.impl == kd.IMPL_FUSED
    assert kd.resolve(sig, impl_bwd="pallas")[1] == kd.Decision("pallas", *bwd)


@pytest.mark.parametrize("name,sig,ranges", [
    # the benchmark's four cells
    ("cell", _sig(4096, 32, 8, 128, window=4096), 1),
    ("lfm2_cell", _sig(8192, 32, 8, 64, batch=4), 1),
    ("granite_cell", _sig(16384, 32, 8, 64), 1),
    ("olmoe_cell", _sig(4096, 16, 16, 128, batch=4), 1),
    ("chip_smoke", _sig(2048, 32, 8, 128, window=4096), 1),
    ("hd64_1024", _bench_sig(), 1),
    ("llama3_70b", _sig(4096, 64, 8, 128), 1),
    ("gemma2_hd256", _sig(4096, 16, 8, 256), 1),
    ("fp32", _sig(4096, 32, 8, 128, "float32"), 1),
    ("seq64", _sig(64, 4, 4, 16), 1),
    # the float32 dQ of a KV head's sequence grows with group x seq_q:
    # 16,384 tokens at group 4 are 55 MiB with the tiles, 24,576 are 71 and
    # walk in two ranges of 12,288 (47 MiB)
    ("group4_24k", _sig(24576, 32, 8, 128), 2),
    ("group4_32k_hd64", _sig(32768, 32, 8, 64), 2),
    ("ulysses_32k", _sig(32768, 8, 2, 128), 2),
    ("group8_16k", _sig(16384, 64, 8, 128), 2),
    ("mha_64k", _sig(65536, 16, 16, 128), 1),
    ("mha_128k", _sig(131072, 16, 16, 128), 2),
    # train-qwen3next-1chip-gdn-longseq: group 8 at head 256, 268 MB of dQ
    ("qwen3next_cell", _sig(32768, 16, 2, 256), 8),
    # the accumulator follows the queries, not the keys
    ("keys_ne_queries", _sig(256, 8, 2, 128, seq_k=65536), 1),
])
def test_the_backward_is_fused_in_the_fewest_ranges_whose_accumulator_fits(
        name, sig, ranges):
    """The backward's rule: one kernel for dQ, dK and dV at every shape; its
    walk whole where the VMEM estimate, whole-sequence dQ accumulator
    included, is within the cap, and past it in the fewest query ranges
    (whole q blocks each, the sequence divisible) that bring the estimate
    back within it. What a call asks of the chip stays well inside the
    core's 128 MiB."""
    fwd, dec = kd.resolve(sig)
    assert (dec.impl, dec.ranges) == (kd.IMPL_FUSED, ranges), name
    assert kd.choose_ranges(sig) == ranges
    whole, est = kd.fused_vmem_bytes(sig), kd.fused_vmem_bytes(sig, ranges)
    assert (whole <= kd.FUSED_VMEM_CAP_BYTES) == (ranges == 1), whole
    assert est <= kd.FUSED_VMEM_CAP_BYTES
    # no fewer would do
    num_q = sig.seq_q // dec.block_q
    assert num_q % ranges == 0 and sig.seq_q % ranges == 0
    for fewer in range(1, ranges):
        assert num_q % fewer or kd.fused_vmem_bytes(sig, fewer) > kd.FUSED_VMEM_CAP_BYTES
    # a range's accumulator, at 128 lanes a row at least, is inside the estimate
    assert est >= (sig.heads // sig.kv_heads * (sig.seq_q // ranges)
                   * max(sig.head_dim, 128) * 4)
    assert (kd.vmem_limit_bytes(est) or 0) <= 80 * 2**20
    # the forward never resolves to it; each backward has its own blocks
    assert fwd.impl == kd.IMPL_PALLAS and fwd.ranges == 1
    assert (dec.block_q, dec.block_k) == kd.choose_blocks(sig, "fused")
    # the pair: only where it is pinned, in one walk, with its own blocks
    pair = kd.resolve(sig, impl_bwd="pallas")[1]
    assert pair == kd.Decision(kd.IMPL_PALLAS, *kd.choose_blocks(sig, "bwd"))


def test_the_qwen3_next_cells_call_walks_in_eight_ranges_of_4096_queries():
    """``[1, 32768, 16/2, 256]``: the tiles at (256, 512), group 8 and 256
    lanes are 27.25 MiB of the estimate; the whole sequence's dQ makes it
    283.25, a range of 8,192 queries 91.25, one of 4,096 59.25: R = 8. The
    three shapes the docs name past the cap walk in two (46.75, 54.75 and
    54.75 MiB)."""
    mib = 2**20
    cell = _sig(32768, 16, 2, 256)
    assert kd.choose_blocks(cell, "fused") == (256, 512)
    assert [kd.fused_vmem_bytes(cell, r) / mib for r in (1, 4, 8)] == [
        283.25, 91.25, 59.25]
    assert kd.fused_vmem_bytes(cell, 8) - 8 * 4096 * 256 * 4 == 27.25 * mib
    assert kd.resolve(cell)[1] == kd.Decision(kd.IMPL_FUSED, 256, 512, ranges=8)
    assert [kd.fused_vmem_bytes(s, 2) / mib for s in (
        _sig(24576, 32, 8, 128), _sig(32768, 32, 8, 64), _sig(32768, 8, 2, 128))
            ] == [46.75, 54.75, 54.75]
    # pinned blocks move the count with the tiles: (128, 512) halves them
    assert kd.resolve(cell, blocks=(128, 512))[1].ranges == 8
    assert kd.resolve(cell, blocks=(512, 512))[1].ranges == 32


def test_ranges_are_pinned_as_blocks_are_and_only_for_the_fused_backward():
    """``ranges=`` beats the shape: any count that leaves a range whole q
    blocks of a divisible sequence (one q block a range at the most); the
    pair has no ranges to pin; tiles that alone pass the cap take a q block
    a range, and a q block that does not divide the sequence one range."""
    sig = _sig(4096, 32, 8, 128)
    assert kd.resolve(sig)[1].ranges == 1
    for r in (1, 2, 4, 8):
        assert kd.resolve(sig, ranges=r)[1] == kd.Decision("fused", 512, 512, r)
    assert kd.resolve(sig, blocks=(128, 256), ranges=32)[1].ranges == 32
    for bad in (0, 3, 16):          # 8 q blocks of 512
        with pytest.raises(ValueError, match="ranges"):
            kd.resolve(sig, ranges=bad)
    with pytest.raises(ValueError, match="ranges"):
        kd.resolve(sig, impl_bwd="pallas", ranges=2)
    assert kd.resolve(sig, impl_bwd="pallas", ranges=1)[1].ranges == 1
    # (2048, 2048) at group 4: 16,384 folded rows' tiles are past the cap
    assert kd.fused_vmem_bytes(sig, 2, (2048, 2048)) > kd.FUSED_VMEM_CAP_BYTES
    assert kd.resolve(sig, blocks=(2048, 2048))[1].ranges == 2
    assert kd.choose_ranges(_sig(1536, 16, 16, 128), (1024, 512)) == 1
    assert kd.resolve(_sig(1536, 16, 16, 128), blocks=(1024, 512), ranges=1)[1].ranges == 1
    # the call takes the pin, and refuses a count the sequence does not take
    q, k, v = _qkv(s=128)
    grads = [jax.grad(lambda q: flash_attention(
        q, k, v, causal=True, interpret=True, block_q=32, block_k=64,
        ranges=r).sum())(q) for r in (1, 2, 4)]
    for g in grads[1:]:
        np.testing.assert_array_equal(np.asarray(g), np.asarray(grads[0]))
    with pytest.raises(ValueError, match="ranges=3"):
        flash_attention(q, k, v, causal=True, interpret=True, block_q=32,
                        block_k=64, ranges=3)


@pytest.mark.parametrize("name,sig,blocks", [
    # 2,048 folded rows a step, 512 queries at most, by 512 keys
    ("cell", _sig(4096, 32, 8, 128, window=4096), (512, 512)),
    ("lfm2_cell", _sig(8192, 32, 8, 64, batch=4), (512, 512)),
    ("granite_cell", _sig(16384, 32, 8, 64), (512, 512)),
    ("olmoe_cell", _sig(4096, 16, 16, 128, batch=4), (512, 512)),
    ("group2", _sig(4096, 16, 8, 128, batch=4), (512, 512)),
    ("hd64_1024", _bench_sig(), (512, 512)),
    ("llama3_70b", _sig(4096, 64, 8, 128), (256, 512)),
    ("group16", _sig(4096, 32, 2, 128), (128, 512)),
    ("gemma2_hd256", _sig(4096, 16, 8, 256), (512, 512)),
    ("fp32", _sig(4096, 32, 8, 128, "float32"), (512, 512)),
    ("mha_1536", _sig(1536, 16, 16, 128), (512, 512)),
    ("seq384", _sig(384, 8, 2, 64), (384, 384)),
    ("seq64", _sig(64, 4, 4, 16), (64, 64)),
    ("keys_ne_queries", _sig(256, 8, 2, 128, seq_k=1536), (256, 512)),
])
def test_the_fused_backwards_blocks_follow_from_the_shape(name, sig, blocks):
    """The fused kernel carries its own VMEM limit, so its step is not cut
    to the compiler's default: the PR 34 sweep's (512, 512), the queries
    halved past 2,048 folded rows."""
    assert kd.choose_blocks(sig, "fused") == blocks, name
    assert sig.seq_q % blocks[0] == 0 and sig.seq_k % blocks[1] == 0
    assert kd.resolve(sig)[1] == kd.Decision(kd.IMPL_FUSED, *blocks)
    # the pair, pinned, keeps the blocks it had
    pair = kd.resolve(sig, impl_bwd="pallas")[1]
    assert (pair.block_q, pair.block_k) == kd.choose_blocks(sig, "bwd")


def _sweep_rows():
    """The chip sweep that took the other routes out (PERF.md §6, PR 44): a
    row a point, its timings in ms by candidate."""
    path = os.path.join(os.path.dirname(__file__), "..", "..", "..", "docs",
                        "readings", "attn_sweep_pr44.jsonl")
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return [r for r in rows if r["kind"] == "main"]


@pytest.mark.parametrize("row", _sweep_rows(),
                         ids=lambda r: "b{}s{}h{}kv{}d{}".format(*r["point"]))
def test_the_sweep_timed_the_kernels_and_blocks_a_shape_resolves_to(row):
    """The record the deletion rests on is of THESE choices: at each of its
    nineteen points (causal bf16) ``resolve`` gives the per-head forward and
    the fused backward at the very blocks the sweep timed, the pair's blocks
    are the ones it timed beside them, and that route's forward + backward
    is the fastest the sweep read there. A change to ``choose_blocks`` or to
    the fused cap that leaves the table behind fails here."""
    b, s, h, kv, d = row["point"]
    sig = kd.make_sig((b, s, h, d), kv, s, "bfloat16", True, None, None)
    fwd, bwd = kd.resolve(sig)
    pair = kd.resolve(sig, impl_bwd=kd.IMPL_PALLAS)[1]
    assert bwd.impl == kd.IMPL_FUSED
    for leg, dec in (("fwd", fwd), ("bwd", bwd), ("bwd", pair)):
        assert f"{leg}:{dec.impl}@{dec.block_q}x{dec.block_k}" in row["legs"]
    steps = {route: sorted(runs)[len(runs) // 2]
             for route, runs in row["steps"].items() if isinstance(runs, list)}
    assert min(steps, key=steps.get) == "pallas+fused", steps


def test_fused_is_a_backward_implementation_only():
    """``impl_bwd=`` pins either backward against the shape, "fused" and
    "pallas" (the pair), and nothing else; the forward has one kernel and no
    argument to pin."""
    sig = _sig(32768, 32, 8, 64)        # from the shape: fused, in two ranges
    fwd, bwd = kd.resolve(sig, impl_bwd="fused")
    assert (fwd.impl, bwd.impl) == ("pallas", "fused")
    assert (fwd, bwd) == kd.resolve(sig)
    assert (bwd.block_q, bwd.block_k) == kd.choose_blocks(sig, "fused")
    small = _sig(4096, 32, 8, 128)      # from the shape: fused, in one
    assert kd.resolve(small, impl_bwd="pallas")[1].impl == kd.IMPL_PALLAS
    for unknown in ("xla", "folded", "fwd"):
        with pytest.raises(ValueError, match="impl_bwd"):
            kd.resolve(small, impl_bwd=unknown)
    q, k, v = _qkv(s=64)
    with pytest.raises(TypeError):
        flash_attention(q, k, v, causal=True, interpret=True, impl_fwd="xla")
    assert kd.describe(fwd, bwd) == "attn[fwd=pallas@256x512,bwd=fused@512x512/r2]"
    assert kd.describe(*kd.resolve(small)) == "attn[fwd=pallas@256x512,bwd=fused@512x512]"
    assert kd.describe(*kd.resolve(sig, impl_bwd="pallas")) == (
        "attn[fwd=pallas@256x512,bwd=pallas@256x512]")


def test_the_fused_estimate_grows_with_the_queries():
    est = [kd.flash_vmem_bytes("fused", 4, 64, 2, 256, 512, seq_q=s)
           for s in (4096, 8192, 16384)]
    assert est[1] - est[0] == 4 * 4096 * 128 * 4      # head 64 in 128 lanes
    assert est[2] - est[1] == 4 * 8192 * 128 * 4
    # without the accumulator it is the pair's order of size
    tiles = est[0] - 4 * 4096 * 128 * 4
    assert 0 < tiles <= 1.2 * kd.flash_vmem_bytes("bwd", 4, 64, 2, 256, 512)


def test_blocks_past_the_default_vmem_ask_for_their_own_limit():
    """Explicit blocks (the sweep tool's (1024, 1024)) are not shrunk: the
    call carries a limit a quarter above the estimate instead."""
    est = kd.flash_vmem_bytes("bwd", 4, 128, 2, 1024, 1024)
    assert est > kd.VMEM_SCOPED_DEFAULT_BYTES
    assert kd.vmem_limit_bytes(est) == est * 5 // 4
    # the estimate grows with every tile dimension
    assert est > kd.flash_vmem_bytes("bwd", 4, 128, 2, 512, 1024)
    assert est > kd.flash_vmem_bytes("fwd", 4, 128, 2, 1024, 1024)


def test_heuristic_boundaries():
    """The one rule left: the fused backward in one walk up to
    FUSED_VMEM_CAP_BYTES of its estimate, in ranges past it. At group 4 and
    head 128 the float32 dQ is 2 KiB a token: 20,480 tokens are inside,
    24,576 outside (two ranges); head size, sequence length of the keys,
    window and softcap do not move the forward, which is one kernel."""
    inside, outside = _sig(20480, 32, 8, 128), _sig(24576, 32, 8, 128)
    assert kd.fused_vmem_bytes(inside) <= kd.FUSED_VMEM_CAP_BYTES
    assert kd.fused_vmem_bytes(outside) > kd.FUSED_VMEM_CAP_BYTES
    assert kd.resolve(inside)[1] == kd.Decision(kd.IMPL_FUSED, 512, 512, 1)
    assert kd.resolve(outside)[1] == kd.Decision(kd.IMPL_FUSED, 512, 512, 2)
    for sig in (_bench_sig(q_shape=(8, 512, 16, 64), seq_k=512),
                _bench_sig(q_shape=(8, 1024, 8, 128)), _bench_sig(window=256),
                _bench_sig(), inside, outside):
        assert kd.resolve(sig)[0].impl == kd.IMPL_PALLAS


def test_resolution_reads_the_shape_and_nothing_else(monkeypatch):
    """No switch is left to move a decision: resolving reads no environment
    variable and opens no file, and the module imports neither ``os`` nor a
    file reader."""
    reads = []
    getitem = os._Environ.__getitem__
    monkeypatch.setattr(os._Environ, "__getitem__", lambda self, key: (
        reads.append(key), getitem(self, key))[1])
    monkeypatch.setattr("builtins.open", lambda *a, **kw: reads.append(a))
    decisions = [kd.resolve(s) for s in (_bench_sig(), _sig(32768, 32, 8, 64))]
    notes = kd.resolved_note()
    monkeypatch.undo()
    assert not reads and notes
    assert [(d[1].impl, d[1].ranges) for d in decisions] == [("fused", 1), ("fused", 2)]
    assert not {"os", "json", "open"} & set(vars(kd))


def test_describe_and_resolved_note():
    """Since PR 60 a note says each leg's grid steps a KV head, live tiles of
    the rectangle's; legs no ``walked`` counted print as they did."""
    note = kd.resolved_note()
    assert note == "attn[fwd=pallas@1024x1024 tiles=1/1, bwd=fused@512x512 tiles=3/4(grid)]"
    sig = _bench_sig()
    fwd, bwd = kd.resolve(sig)
    assert kd.describe(fwd, bwd) == "attn[fwd=pallas@1024x1024,bwd=fused@512x512]"
    assert kd.describe(kd.walked(sig, fwd, "fwd"), kd.walked(sig, bwd, "bwd")) == note
    assert kd.resolved_note(batch=1, seq=32768, heads=32, kv_heads=8) == (
        "attn[fwd=pallas@256x512 tiles=4160/8192, "
        "bwd=fused@512x512/r2 tiles=2112/4096]")
    assert kd.resolved_note(batch=1, seq=32768, heads=16, kv_heads=2,
                            head_dim=256) == (
        "attn[fwd=pallas@128x512 tiles=8320/16384, "
        "bwd=fused@256x512/r8 tiles=4384/8192]")
    # the Ouro cell's call, ISSUE 60's example; no mask, no dead tile
    assert kd.resolved_note(batch=1, seq=16384, heads=16, head_dim=128) == (
        "attn[fwd=pallas@1024x1024 tiles=136/256, bwd=fused@512x512 tiles=528/1024]")
    assert kd.resolved_note(causal=False) == (
        "attn[fwd=pallas@1024x1024 tiles=1/1, bwd=fused@512x512 tiles=4/4]")


# ---------------------------------------------------------------------------
# the walk of a masked call: a table of its live tiles (PR 60)
# ---------------------------------------------------------------------------

# (seq, block_q, block_k, causal, window, ranges): BQ <, =, > BK; a window
# across blocks, inside one, alone; a range of one q block; one tile
WALKS = {
    "causal_square": (512, 128, 128, True, None, 1),
    "causal_keys_wider": (1024, 128, 512, True, None, 2),
    "causal_queries_wider": (1024, 512, 128, True, None, 1),
    "window_across_blocks": (1024, 128, 128, True, 300, 4),
    "window_inside_a_block": (768, 128, 256, True, 50, 3),
    "window_wider_than_the_sequence": (512, 128, 128, True, 4096, 2),
    "window_alone": (512, 64, 128, False, 100, 8),
    "one_tile": (128, 128, 128, True, None, 1),
}


def _pairs(seq, causal, window):
    """[seq, seq] bool, True where the query sees the key: the kernels'
    ``_mask_scores``, literally."""
    q, k = np.arange(seq)[:, None], np.arange(seq)[None, :]
    keep = np.ones((seq, seq), bool)
    if causal:
        keep &= k <= q
    if window is not None:
        keep &= q - k < window
    return keep


@pytest.mark.parametrize("name", WALKS)
def test_the_listed_tiles_against_the_mask_pair_by_pair(name):
    """Every unmasked (query, key) pair lies in a listed tile, no listed tile
    is wholly masked but the one step a (range, major block) with no live
    tile keeps, the order is the rectangle's with its dead steps left out,
    and every query block (forward) and every key block of a range
    (backward) opens and closes once; a step's flags say whether its tile is
    live and whether the mask cuts it; the backward's dQ table names, a step,
    the query block that completes next, each once, on its last live tile."""
    seq, bq, bk, causal, window, ranges = WALKS[name]
    num_q, num_k = seq // bq, seq // bk
    pairs = _pairs(seq, causal, window).reshape(num_q, bq, num_k, bk)
    some, every = pairs.any(axis=(1, 3)), pairs.all(axis=(1, 3))
    live, interior = kd.live_tiles(num_q, num_k, bq, bk, causal, window)
    np.testing.assert_array_equal(live, some)
    np.testing.assert_array_equal(interior, every)

    def ends(runs):     # a run's steps are consecutive: one open, one close
        runs = list(runs)
        assert 1 + sum(a != b for a, b in zip(runs, runs[1:])) == len(set(runs))
        return ([i == 0 or runs[i - 1] != r for i, r in enumerate(runs)],
                [i == len(runs) - 1 or runs[i + 1] != r for i, r in enumerate(runs)])

    def bits(flags, bit):
        return list((flags & bit) != 0)

    q, k, flags = kd.flash_walk("fwd", num_q, num_k, bq, bk, causal, window)
    assert q.dtype == k.dtype == flags.dtype == np.int32
    assert some[q, k].all() and len(q) == some.sum()     # every query block is live
    assert list(zip(q, k)) == [(i, j) for i in range(num_q) for j in range(num_k)
                               if some[i, j]]
    assert set(q) == set(range(num_q))
    assert (bits(flags, kd.OPENS), bits(flags, kd.CLOSES)) == ends(q)
    assert bits(flags, kd.LIVE) == list(some[q, k])
    assert bits(flags, kd.EDGE) == list(some[q, k] & ~every[q, k])
    assert not (flags & (kd.DQ_OPENS | kd.DQ_CLOSES)).any()

    q, k, dq, flags = kd.flash_walk("bwd", num_q, num_k, bq, bk, causal, window, ranges)
    per = num_q // ranges
    rect = [(i, j) for r in range(ranges) for j in range(num_k)
            for i in range(r * per, (r + 1) * per)]
    dead_sweeps = [(r, j) for r in range(ranges) for j in range(num_k)
                   if not some[r * per:(r + 1) * per, j].any()]
    kept = [(i, j) for i, j in rect if some[i, j]
            or ((i // per, j) in dead_sweeps and i % per == 0)]
    assert list(zip(q, k)) == kept and len(q) == some.sum() + len(dead_sweeps)
    sweeps = list(zip(q // per, k))
    assert len(set(sweeps)) == ranges * num_k
    assert (bits(flags, kd.OPENS), bits(flags, kd.CLOSES)) == ends(sweeps)
    real = some[q, k]
    assert bits(flags, kd.LIVE) == list(real)
    assert bits(flags, kd.EDGE) == list(real & ~every[q, k])
    visits = {i: [s for s in range(len(q)) if q[s] == i and real[s]]
              for i in range(num_q)}
    assert [s for s in range(len(q)) if flags[s] & kd.DQ_OPENS] == sorted(
        v[0] for v in visits.values())
    closing = sorted(v[-1] for v in visits.values())
    assert [s for s in range(len(q)) if flags[s] & kd.DQ_CLOSES] == closing
    # dQ's block: the query tile that closes next, so that it is resident on
    # the step that completes it and named by no step after that
    for s in range(len(q)):
        upcoming = [c for c in closing if c >= s] or closing[-1:]
        assert dq[s] == q[upcoming[0]]
    for i, v in visits.items():
        assert dq[v[-1]] == i and not (dq[v[-1] + 1:] == i).any()
        # where the rectangle's kernel tests a dQ's ends by the mask's arithmetic
        first_k = 0 if window is None else max(i * bq - (window - 1), 0) // bk
        last_k = min((i * bq + bq - 1) // bk, num_k - 1) if causal else num_k - 1
        assert (k[v[0]], k[v[-1]]) == (first_k, last_k)


def test_the_walk_is_a_table_where_a_mask_leaves_tiles_dead_and_under_the_cap():
    """From the shape alone: a causal or windowed call's forward and fused
    backward walk the table, the pair and an unmasked call the rectangle; one
    tile a side has none dead; past ``TABLE_CAP_TILES`` steps a KV head (SMEM
    holds 1 MiB: three int32 a forward step, four a backward's) the clamped
    rectangle stays; ``table=`` pins either."""
    def legs(sig, table=None, **pins):
        fwd, bwd = kd.resolve(sig, **pins)
        return kd.walked(sig, fwd, "fwd", table), kd.walked(sig, bwd, "bwd", table)

    ouro = _sig(16384, 16, 16, 128)
    fwd, bwd = legs(ouro)
    assert (fwd.tiles, fwd.grid, fwd.table) == (136, 256, True)
    assert (bwd.tiles, bwd.grid, bwd.table) == (528, 1024, True)
    assert fwd[:4] == kd.resolve(ouro)[0][:4] and bwd[:4] == kd.resolve(ouro)[1][:4]
    pair = legs(ouro, impl_bwd="pallas")[1]
    assert (pair.tiles, pair.grid, pair.table) == (272, 512, False)    # at (1024, 512)
    assert [d.table for d in legs(ouro, table=False)] == [False, False]
    # Phi-4-mini-flash's sliding layers: 63 of 1,024 tiles a KV head
    phi = kd.make_sig((1, 16384, 40, 64), 20, 16384, "bfloat16", True, 512, None,
                      v_dim=128)
    assert phi.window == 512
    assert [(d.tiles, d.grid, d.table) for d in legs(phi)] == [(63, 1024, True)] * 2
    full = kd.make_sig((1, 16384, 16, 128), 16, 16384, "bfloat16", False, None, None)
    assert [(d.tiles, d.grid, d.table) for d in legs(full)] == [
        (256, 256, False), (1024, 1024, False)]
    assert [d.table for d in legs(full, table=True)] == [False, False]
    assert [(d.tiles, d.table) for d in legs(_sig(512, 4, 4, 64))] == [(1, False)] * 2
    # few dead tiles: a dead step costs some 0.2 us, a table's live step
    # 0.06 to 0.09 more, so three live tiles of four keep the rectangle
    preset = legs(_bench_sig())[1]
    assert (preset.tiles, preset.grid, preset.table) == (3, 4, False)
    assert legs(_bench_sig(), table=True)[1].table
    olmoe = legs(_sig(4096, 16, 16, 128))[0]
    assert (olmoe.tiles, olmoe.grid, olmoe.table) == (10, 16, True)
    # the Qwen3-Next cell's ranged walk: 4,160 live tiles and a step each for
    # the 224 (range, kv block) pairs past a range's diagonal
    qwen = _sig(32768, 16, 2, 256)
    assert [(d.tiles, d.grid, d.table) for d in legs(qwen)] == [
        (8320, 16384, True), (4384, 8192, True)]
    long = _sig(131072, 32, 8, 128)
    fwd, bwd = legs(long)
    assert fwd.tiles == 65792 > kd.TABLE_CAP_TILES and not fwd.table
    assert bwd.tiles == 33792 and bwd.table
    assert legs(long, table=True)[0].table
    assert kd.describe(fwd, bwd) == (
        "attn[fwd=pallas@256x512 tiles=65792/131072(grid), "
        "bwd=fused@512x512/r8 tiles=33792/65536]")


# sha256 of the traced text (forward, then the pullback) that the PARENT
# commit (ef2e5aa) gives these calls: bf16, ``interpret=True``
# (``str(jax.make_jaxpr(...))``, jax 0.9.0)
_PARENT_TEXT = {
    "unmasked": (((2, 512, 4, 128), 2, 128), dict(block_q=128, block_k=256),
                 "3b040310bf05278e"),
    "unmasked_two_widths": (((1, 512, 2, 192), 2, 128), dict(block_q=256, block_k=128),
                            "c7899edd03a83508"),
    "unmasked_pair": (((1, 512, 2, 64), 2, 64),
                      dict(block_q=128, block_k=128, impl_bwd="pallas"),
                      "3e9781431760f0dd"),
    "unmasked_two_ranges": (((1, 512, 4, 128), 1, 128),
                            dict(block_q=128, block_k=128, ranges=2), "081a2bf74bc8f73b"),
    "causal_one_tile": (((1, 128, 2, 128), 2, 128), dict(causal=True),
                        "1c0dd51e095665b1"),
    # a masked call pinned to the rectangle is the parent's call too
    "causal_on_the_rectangle": (((1, 512, 2, 128), 2, 128),
                                dict(causal=True, block_q=128, block_k=128, table=False),
                                "d0ae1d925950a9d9"),
    "causal_pair_on_the_rectangle": (
        ((1, 512, 2, 128), 2, 128),
        dict(causal=True, block_q=128, block_k=128, impl_bwd="pallas", table=False),
        "1cf1829f2fe96771"),
}


@pytest.mark.parametrize("name", _PARENT_TEXT)
def test_a_call_with_no_dead_tile_traces_to_the_parents_text(name):
    """An unmasked call (BERT, a cross call) has no dead tile and keeps the
    rectangle: its traced kernels, index maps and grid are the parent's TEXT
    FOR TEXT, forward and backward; so is a masked call of one tile, and a
    masked call pinned to the rectangle (what a call past the cap keeps)."""
    import hashlib
    (shape, kv, dv), pins, want = _PARENT_TEXT[name]
    b, s, h, d = shape
    q = jnp.zeros(shape, jnp.bfloat16)
    k = jnp.zeros((b, s, kv, d), jnp.bfloat16)
    v = jnp.zeros((b, s, kv, dv), jnp.bfloat16)

    def f(q, k, v):
        return flash_attention(q, k, v, interpret=True, **pins)

    text = str(jax.make_jaxpr(f)(q, k, v)) + str(jax.make_jaxpr(
        lambda q, k, v: jax.vjp(f, q, k, v)[1](jnp.ones((b, s, h, dv), jnp.bfloat16))
    )(q, k, v))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want
    if "unmasked" in name:      # and it is what the rule gives it: no pin
        assert "PrefetchScalarGridSpec" not in text and "num_scalar_prefetch" not in text


# ---------------------------------------------------------------------------
# the masked call the kernels refuse (ROADMAP D6's defect)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("leg", ["fwd", "pallas", "fused"])
@pytest.mark.parametrize("sq,sk", [(64, 128), (128, 64)])
def test_a_masked_call_with_unequal_lengths_is_refused(sq, sk, leg):
    """The kernels count the causal diagonal and the window from the first
    query and key, ``_xla_attention`` from the last: with ``seq_q != seq_k``
    the two disagree, so the kernel path refuses the call by name, forward
    and under either backward, rather than answer differently from the path
    off a TPU. With no mask the same shapes run and match the oracle."""
    q, k, v = _qkv(s=sq, sk=sk)

    def run(**kw):
        f = lambda q, k, v: flash_attention(            # noqa: E731
            q, k, v, interpret=True, block_q=64, block_k=64,
            impl_bwd=None if leg == "fwd" else leg, **kw)
        if leg == "fwd":
            return f(q, k, v)
        return jax.grad(lambda *a: (f(*a) ** 2).mean(), (0, 1, 2))(q, k, v)

    for kw in (dict(causal=True), dict(window=32), dict(causal=True, window=32)):
        with pytest.raises(ValueError, match=f"seq_q {sq} != seq_k {sk}"):
            run(**kw)
    got = run(causal=False)
    o_ref, g_ref = _ref(q, k, v, causal=False)
    for a, b in zip((got, ) if leg == "fwd" else got,
                    (o_ref, ) if leg == "fwd" else g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-5)


def test_off_the_kernel_path_unequal_lengths_align_the_last_query():
    """``_xla_attention``, the path off a TPU: the last query sees every
    key (a decode step over a prefix), which is what the refusal names."""
    q, k, v = _qkv(s=64, sk=128)
    out = flash_attention(q, k, v, causal=True)      # a CPU, no interpret
    full = _xla_attention(q, k, v, 1.0 / np.sqrt(q.shape[-1]), False)
    np.testing.assert_allclose(np.asarray(out[:, -1]), np.asarray(full[:, -1]),
                               atol=1e-6)
    assert not np.allclose(np.asarray(out[:, 0]), np.asarray(full[:, 0]))


# ---------------------------------------------------------------------------
# block handling
# ---------------------------------------------------------------------------


def test_explicit_blocks_pin_pallas_tiles():
    """``block_q=`` / ``block_k=`` beat the shape on both legs and under
    either backward, and are not cut to the default VMEM limit."""
    sig = _bench_sig()
    assert kd.resolve(sig)[1] == kd.Decision("fused", 512, 512)
    for impl in (None, ) + BWD_IMPLS:
        fwd, bwd = kd.resolve(sig, impl_bwd=impl, blocks=(128, 256))
        assert (fwd.block_q, fwd.block_k) == (128, 256)
        assert (bwd.block_q, bwd.block_k) == (128, 256)
        assert bwd.impl == (impl or "fused")
    _, bwd = kd.resolve(sig, impl_bwd="pallas", blocks=(1024, 1024))
    assert (bwd.block_q, bwd.block_k) == (1024, 1024)


def test_blocks_fit_short_sequences():
    """The default hd64 blocks (256, 512) exceed s=128 — execution must
    clamp them to divide the sequence instead of tripping the kernels'
    divisibility assert."""
    q, k, v = _qkv(s=128, d=64)
    o_ref, _ = _ref(q, k, v)
    out = flash_attention(q, k, v, causal=True, interpret=True,
                          impl_bwd="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# the offline sweep tool, end to end on CPU
# ---------------------------------------------------------------------------


def _load_sweep_module():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                        "..", "perf", "run_attn_sweep.py")
    spec = importlib.util.spec_from_file_location("run_attn_sweep", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_sweep_takes_a_block_grid_and_times_the_backward_alone():
    """``--impls`` / ``--blocks``: one impl over a grid of one's own beside
    the chosen blocks (the PR 32 block sweep), each leg with a time of its
    own from the same residuals; with no grid, every implementation and the
    reference at the shape's own blocks."""
    sweep = _load_sweep_module()
    results = sweep.sweep_shape(1, 128, 2, 2, 32, "float32", True, iters=1,
                                interpret=True, quick=False,
                                impls=(kd.IMPL_PALLAS, ),
                                grid=[(64, 64), (128, 64)])
    for leg in ("fwd", "bwd"):
        rows = results[leg]
        assert [r[0] for r in rows] == ["pallas@128x128", "pallas@64x64",
                                        "pallas@128x64"]
        assert all(r[-1] > 0 and r[1] == "pallas" for r in rows)
    results = sweep.sweep_shape(1, 128, 2, 2, 32, "float32", True, iters=1,
                                interpret=True, quick=True)
    assert [r[0] for r in results["fwd"]] == ["xla", "pallas@128x128"]
    assert [r[0] for r in results["bwd"]] == ["xla", "pallas@128x128",
                                              "fused@128x128"]


# ---------------------------------------------------------------------------
# the experts' grouped matmuls: moe_gmm or ragged_dot, from what the call shows
# ---------------------------------------------------------------------------

# (sorted rows a call, hidden, expert width) of the five training cells with
# experts: a share's rows array is twice its even share of tokens x top-k
GMM_CELLS = {
    "train-olmoe-1chip-seq4k": (16384 * 8, 2048, 1024),
    "train-lfm2moe-1chip-seq8k": (2 * 32768 * 4 // 8, 2048, 1536),
    "train-kimivl-1chip-seq8k": (2 * 32768 * 6 // 8, 2048, 1408),
    "train-sdar-1chip-bd4-seq8k": (2 * 32768 * 8 // 8, 2048, 768),
    "train-keyevl2-1chip-dsa-seq32k": (2 * 32768 * 8 // 8, 2048, 768),
}


@pytest.mark.parametrize("cell", list(GMM_CELLS))
def test_the_cells_grouped_matmuls_take_the_kernel_on_one_tpu_device(cell):
    rows, hidden, width = GMM_CELLS[cell]
    for k, n in ((hidden, width), (width, hidden)):        # w1 | w3, and w2
        assert kd.gmm_impl(rows, k, n, jnp.bfloat16, True) == kd.IMPL_PALLAS
        assert kd.gmm_impl(rows, k, n, jnp.bfloat16, False) == kd.IMPL_XLA
        # the call asks for what the rule counted, under the cap it is held to
        assert max(kd.gmm_vmem_bytes(leg, kd.GMM_ROW_TILE, k, n, 2)
                   for leg in ("rows", "d_rows", "weights")) <= kd.FUSED_VMEM_CAP_BYTES


@pytest.mark.parametrize("why,call", {
    "float32": (131072, 2048, 1024, jnp.float32),
    "lora_down_to_the_rank": (8192, 4096, 16, jnp.bfloat16),
    "lora_up_from_the_rank": (8192, 16, 4096, jnp.bfloat16),
    "a_decode_waves_rows": (64 * 8, 2048, 1024, jnp.bfloat16),
    "one_row_under_the_floor": (kd.GMM_MIN_ROWS - 1, 2048, 1024, jnp.bfloat16),
    "a_width_off_the_128_grid": (131072, 2048, 1000, jnp.bfloat16),
    "a_depth_off_the_128_grid": (131072, 1000, 2048, jnp.bfloat16),
    "an_expert_too_wide_for_vmem": (131072, 4096, 4096, jnp.bfloat16),
}.items())
def test_every_other_grouped_matmul_keeps_ragged_dot(why, call):
    assert kd.gmm_impl(*call, True) == kd.IMPL_XLA
    assert kd.gmm_impl(kd.GMM_MIN_ROWS, 2048, 1024, jnp.bfloat16, True) == kd.IMPL_PALLAS


@pytest.mark.parametrize("tpu,devices,here", [(False, 1, False), (True, 1, True),
                                              (True, 2, False), (True, 0, True)],
                         ids=["a_cpu", "one_tpu_device", "a_mesh_of_two", "no_mesh_yet"])
def test_the_kernel_runs_where_a_raw_pallas_call_can(monkeypatch, tpu, devices, here):
    from deepspeed_tpu.comm import reset_mesh_context
    from deepspeed_tpu.comm.mesh import MeshContext, set_mesh_context
    from deepspeed_tpu.ops import grouped_matmul as gm
    monkeypatch.setattr(gm, "on_tpu", lambda: tpu)
    reset_mesh_context()
    if devices:
        set_mesh_context(MeshContext.create(devices=jax.devices()[:devices]))
    try:
        assert gm._kernel_here() is here
    finally:
        reset_mesh_context()


# ---------------------------------------------------------------------------
# reporting surfaces
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# Kimi Delta Attention's chunk kernels: the heads a grid step takes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("why,heads,d,chunk,itemsize,block", [
    ("the Ling-3.0 cell: 32 heads of 128, chunks of 64, bf16 (11.9 MB backward, 10.6 "
     "forward at the array counts since PR 53: under the 16 MiB default)", 32, 128, 64, 2, 4),
    ("float32 operands are counted as the float32 temporaries are", 4, 128, 64, 4, 4),
    ("eight heads take four, not eight", 8, 128, 64, 2, 4),
    ("six heads fall to two", 6, 128, 64, 2, 2),
    ("two heads", 2, 128, 64, 2, 2),
    ("an odd count falls to one", 3, 128, 64, 2, 1),
    ("one head", 1, 128, 64, 2, 1),
    ("heads of 512: four are 66.2 MB of the 67.1 MB (64 MiB) cap", 32, 512, 64, 2, 4),
    ("a chunk of 512: two heads fit the cap, four do not", 32, 128, 512, 2, 2),
    ("a chunk of 1,024: one head alone fits", 32, 128, 1024, 2, 1),
    ("heads of 1,024: 91 MB at two", 32, 1024, 64, 2, 1),
], ids=lambda v: v.split(":")[0].replace(" ", "_") if isinstance(v, str) else None)
def test_the_kda_head_block_divides_the_heads_fits_the_cap_and_falls_to_one(
        why, heads, d, chunk, itemsize, block):
    got = kd.choose_kda_heads(heads, d, chunk, itemsize)
    assert got == block, why
    assert heads % got == 0
    assert got == 1 or kd.kda_vmem_bytes(got, d, chunk, itemsize) <= kd.FUSED_VMEM_CAP_BYTES
    # the largest of the list that does both
    for larger in (b for b in kd.KDA_HEAD_BLOCKS if b > got):
        assert heads % larger or kd.kda_vmem_bytes(
            larger, d, chunk, itemsize) > kd.FUSED_VMEM_CAP_BYTES
    # the estimate is the backward's unless told, and grows with the block
    # (all of it but beta's two blocks, a head a lane whatever the block)
    betas = 2 * 2 * chunk * 128 * 4
    assert kd.kda_vmem_bytes(got, d, chunk, itemsize, 6) < kd.kda_vmem_bytes(
        got, d, chunk, itemsize) == got * (kd.kda_vmem_bytes(1, d, chunk, itemsize)
                                           - betas) + betas


def test_the_kda_estimate_counts_the_kernels_blocks_as_they_are_since_the_rows_moved_in():
    """PR 53: the forward pipelines q, k, v, both gates' pre-activations and
    the output (6), the backward those five, the output's gradient and five
    gradients (11): the counts XLA's ``beta k`` / ``beta v`` gave, so the
    Ling-3.0 cell's call still takes four heads under the compiler's default
    limit, and asks for none."""
    fwd, bwd = (kd.kda_vmem_bytes(4, 128, 64, 2, n) for n in (6, 11))
    assert (fwd, bwd) == (10_616_832, 11_927_552)
    assert bwd - fwd == 2 * 5 * 64 * 512 * 4
    assert bwd <= kd.VMEM_SCOPED_DEFAULT_BYTES and kd.vmem_limit_bytes(bwd) is None
    assert kd.choose_kda_heads(32, 128, 64, 2) == 4


@pytest.mark.parametrize("window", [None, 512])
def test_values_wider_than_keys_take_one_lane_tile_and_the_fused_backward(window):
    """Differential attention's stacked call (PR 52): 40 query heads of 64 in
    groups of 2 over 20 key heads, values 128 wide, 16,384 keys. The VMEM
    estimates are asked about 128 lanes (64 | 128 is one lane tile, where 192
    | 128 is two), both legs take (512, 512) and the backward is the fused
    kernel; the window does not enter the rule."""
    sig = kd.make_sig((1, 16384, 40, 64), 20, 16384, "bfloat16", True, window, None,
                      v_dim=128)
    assert sig.v_dim == 128 and sig.windowed == (window is not None)
    assert kd.vmem_width(64, 128) == 128 and kd.vmem_width(192, 128) == 256
    fwd, bwd = kd.resolve(sig)
    assert (fwd, bwd) == (kd.Decision(kd.IMPL_PALLAS, 512, 512),
                          kd.Decision(kd.IMPL_FUSED, 512, 512))
    assert kd.flash_vmem_bytes("fwd", 2, 128, 2, 512, 512) <= kd.VMEM_SCOPED_DEFAULT_BYTES
    assert 28 * 2**20 <= kd.fused_vmem_bytes(sig) <= kd.FUSED_VMEM_CAP_BYTES


def test_env_report_includes_dispatch_lines():
    from deepspeed_tpu.env_report import debug_report
    rep = debug_report()
    assert "attn dispatch @ bench shape" in rep
    assert "attn[fwd=pallas@1024x1024 tiles=1/1, bwd=fused@512x512 tiles=3/4(grid)]" in rep
    assert ("attn[fwd=pallas@1024x1024 tiles=136/256, "
            "bwd=fused@512x512 tiles=528/1024]") in rep
    assert ("attn[fwd=pallas@128x512 tiles=8320/16384, "
            "bwd=fused@256x512/r8 tiles=4384/8192]") in rep
    assert "attn dispatch table" not in rep
    assert "flash-attention variant" not in rep
