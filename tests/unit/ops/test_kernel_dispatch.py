"""Shape-aware attention dispatch: correctness of every (fwd, bwd) route
combination vs the XLA oracle, decision precedence (explicit > env > legacy
env > measured cache > heuristic), the persistent autotune cache's
durability contract, and the offline sweep tool end-to-end on CPU.

All kernel execution is Pallas interpret mode (CPU); the conftest
``_hermetic_attn_cache`` fixture points ``DS_TPU_ATTN_CACHE_DIR`` at a
per-test temp dir, so nothing here ever sees a developer's measured table.
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
from deepspeed_tpu.ops.autotune_cache import (AutotuneCache, CACHE_VERSION,
                                              cache_path, get_cache)

IMPLS = (kd.IMPL_XLA, kd.IMPL_PALLAS, kd.IMPL_FOLDED)
BWD_IMPLS = IMPLS + (kd.IMPL_FUSED, )   # the backward's one-pass kernel too


def _qkv(b=2, s=128, h=4, kv=2, d=32, seed=7, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, kv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, kv, d)), dtype)
    return q, k, v


def _ref(q, k, v, causal=True, window=None, softcap=None):
    scale = 1.0 / np.sqrt(q.shape[-1])

    def loss(q, k, v):
        out = _xla_attention(q, k, v, scale, causal, window, softcap)
        return (out.astype(jnp.float32) ** 2).mean(), out

    (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                   has_aux=True)(q, k, v)
    return o, g


def _route(q, k, v, fwd, bwd, causal=True, window=None, softcap=None):
    # 64x64 blocks pin every Pallas leg to a multi-block grid even at the
    # small parity shapes, so the online-softmax accumulation across
    # k-blocks stays covered without paying interpret-mode cost for big
    # sequences
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, interpret=True,
                              block_q=64, block_k=64,
                              impl_fwd=fwd, impl_bwd=bwd)
        return (out.astype(jnp.float32) ** 2).mean(), out

    (_, o), g = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                   has_aux=True)(q, k, v)
    return o, g


# ---------------------------------------------------------------------------
# route parity: every fwd x bwd combination vs the XLA oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fwd", IMPLS)
@pytest.mark.parametrize("bwd", BWD_IMPLS)
def test_route_parity_causal(fwd, bwd):
    """The custom_vjp must produce oracle values AND oracle grads for all 12
    per-leg combinations — mixed routes cross LSE layouts (natural vs
    per-head) and residual provenance (XLA-computed lse consumed by a
    Pallas bwd), which is exactly where a wiring bug would hide."""
    q, k, v = _qkv()
    o_ref, g_ref = _ref(q, k, v)
    o, g = _route(q, k, v, fwd, bwd)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    for got, ref in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=5e-5, rtol=5e-5)


@pytest.mark.parametrize("fwd,bwd", [("xla", "pallas"), ("pallas", "xla"),
                                     ("folded", "pallas"), ("xla", "fused"),
                                     ("folded", "fused")])
@pytest.mark.parametrize("window,softcap", [(64, None), (None, 20.0),
                                            (64, 20.0)])
def test_route_parity_window_softcap(fwd, bwd, window, softcap):
    """Mask variants through the mixed routes: sliding window and Gemma-2
    softcap change both the forward math and the lse the bwd consumes."""
    q, k, v = _qkv(s=128, d=32)
    o_ref, g_ref = _ref(q, k, v, window=window, softcap=softcap)
    o, g = _route(q, k, v, fwd, bwd, window=window, softcap=softcap)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    for got, ref in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=5e-5, rtol=5e-5)


def test_route_parity_gqa_mixed():
    """GQA head grouping survives the per-head<->natural lse conversion in
    the xla-fwd + pallas-bwd route (the conversion reshapes over [KV, G])."""
    q, k, v = _qkv(h=8, kv=2, d=32, s=128)
    o_ref, g_ref = _ref(q, k, v)
    o, g = _route(q, k, v, "xla", "pallas")
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    for got, ref in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=5e-5, rtol=5e-5)


def test_bfloat16_route_parity():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    o_ref, g_ref = _ref(q, k, v)
    o, g = _route(q, k, v, "xla", "pallas")
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               atol=3e-2, rtol=3e-2)
    for got, ref in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   atol=3e-2, rtol=3e-2)


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
                                          None, None), "interpret",
                              impl_bwd=impl_bwd)
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
                              impl_fwd="pallas", impl_bwd=impl_bwd)
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


def test_bench_shape_routes_xla_fwd_pallas_bwd():
    """THE acceptance table entry: at hd64/seq1024 the heuristic must pick
    the XLA fused forward (measured 42.7 ms < 62.9 ms Pallas) and keep the
    Pallas flash backward."""
    fwd, bwd = kd.resolve(_bench_sig(), "TPU v5e")
    assert fwd.impl == kd.IMPL_XLA and fwd.source == "heuristic"
    assert bwd.impl == kd.IMPL_FUSED and bwd.source == "heuristic"
    assert (bwd.block_q, bwd.block_k) == kd.choose_blocks(_bench_sig(), "fused")
    assert (bwd.block_q, bwd.block_k) == (512, 512)   # the fused kernel's


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
    # and it is what a Pallas leg resolves to: unpinned forward and back,
    # but that the backward of a shape that fits is the fused kernel, with
    # blocks of its own (below); the pair's are these
    fwd_dec, dec = kd.resolve(sig, "TPU v5e")
    if fwd_dec.impl == kd.IMPL_PALLAS:
        assert (fwd_dec.block_q, fwd_dec.block_k) == fwd
    assert dec.source == "heuristic"
    if dec.impl == kd.IMPL_PALLAS:
        assert (dec.block_q, dec.block_k) == bwd
    pair = kd.resolve_leg("bwd", sig, "TPU v5e", explicit_impl="pallas")
    assert (pair.block_q, pair.block_k) == bwd


@pytest.mark.parametrize("name,sig,impl", [
    # the benchmark's four cells
    ("cell", _sig(4096, 32, 8, 128, window=4096), kd.IMPL_FUSED),
    ("lfm2_cell", _sig(8192, 32, 8, 64, batch=4), kd.IMPL_FUSED),
    ("granite_cell", _sig(16384, 32, 8, 64), kd.IMPL_FUSED),
    ("olmoe_cell", _sig(4096, 16, 16, 128, batch=4), kd.IMPL_FUSED),
    ("chip_smoke", _sig(2048, 32, 8, 128, window=4096), kd.IMPL_FUSED),
    ("hd64_1024", _bench_sig(), kd.IMPL_FUSED),
    ("llama3_70b", _sig(4096, 64, 8, 128), kd.IMPL_FUSED),
    ("gemma2_hd256", _sig(4096, 16, 8, 256), kd.IMPL_FUSED),
    ("fp32", _sig(4096, 32, 8, 128, "float32"), kd.IMPL_FUSED),
    ("seq64", _sig(64, 4, 4, 16), kd.IMPL_FUSED),
    # the float32 dQ of a KV head's sequence grows with group x seq_q:
    # 16,384 tokens at group 4 are 55 MiB with the tiles, 24,576 are 71
    ("group4_24k", _sig(24576, 32, 8, 128), kd.IMPL_PALLAS),
    ("group4_32k_hd64", _sig(32768, 32, 8, 64), kd.IMPL_PALLAS),
    ("ulysses_32k", _sig(32768, 8, 2, 128), kd.IMPL_PALLAS),
    ("group8_16k", _sig(16384, 64, 8, 128), kd.IMPL_PALLAS),
    ("mha_64k", _sig(65536, 16, 16, 128), kd.IMPL_FUSED),
    ("mha_128k", _sig(131072, 16, 16, 128), kd.IMPL_PALLAS),
    # the accumulator follows the queries, not the keys
    ("keys_ne_queries", _sig(256, 8, 2, 128, seq_k=65536), kd.IMPL_FUSED),
])
def test_the_backward_is_fused_where_its_accumulator_fits(name, sig, impl):
    """The backward's heuristic: one kernel for dQ, dK and dV where its VMEM
    estimate, whole-sequence dQ accumulator included, is within the cap;
    past it the dq + dk/dv pair. What a fused call asks of the chip stays
    well inside the core's 128 MiB."""
    dec = kd.resolve_leg("bwd", sig, "TPU v5e")
    assert dec.impl == impl and dec.source == "heuristic", name
    est = kd.fused_vmem_bytes(sig)
    assert (est <= kd.FUSED_VMEM_CAP_BYTES) == (impl == kd.IMPL_FUSED), est
    # the accumulator, at 128 lanes a row at least, is inside the estimate
    assert est >= (sig.heads // sig.kv_heads * sig.seq_q
                   * max(sig.head_dim, 128) * 4)
    if impl == kd.IMPL_FUSED:
        assert (kd.vmem_limit_bytes(est) or 0) <= 80 * 2**20
    # the forward never resolves to it; each backward has its own blocks
    assert kd.resolve_leg("fwd", sig, "TPU v5e").impl != kd.IMPL_FUSED
    assert (dec.block_q, dec.block_k) == kd.choose_blocks(
        sig, "fused" if impl == kd.IMPL_FUSED else "bwd")


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
    dec = kd.resolve_leg("bwd", sig, "TPU v5e")
    assert (dec.impl, dec.block_q, dec.block_k) == (kd.IMPL_FUSED, ) + blocks
    # the pair, pinned, keeps the blocks it had
    pair = kd.resolve_leg("bwd", sig, "TPU v5e", explicit_impl="pallas")
    assert (pair.block_q, pair.block_k) == kd.choose_blocks(sig, "bwd")


def test_fused_is_a_backward_implementation_only(monkeypatch, tmp_path):
    """The ladder pins either backward through what exists: ``impl_bwd=``,
    ``DS_TPU_ATTN_BWD`` and a measured entry take "fused" and "pallas" (the
    pair); the forward's names do not include it."""
    sig = _sig(32768, 32, 8, 64)        # heuristic: the pair
    dec = kd.resolve_leg("bwd", sig, "TPU v5e", explicit_impl="fused")
    assert (dec.impl, dec.source) == ("fused", "explicit")
    with pytest.raises(AssertionError):
        kd.resolve_leg("fwd", sig, "TPU v5e", explicit_impl="fused")
    monkeypatch.setenv("DS_TPU_ATTN_BWD", "fused")
    monkeypatch.setenv("DS_TPU_ATTN_FWD", "fused")      # ignored, with a warning
    fwd, bwd = kd.resolve(sig, "TPU v5e")
    assert (bwd.impl, bwd.source) == ("fused", "env")
    assert (fwd.impl, fwd.source) == ("pallas", "heuristic")
    monkeypatch.setenv("DS_TPU_ATTN_BWD", "pallas")
    small = _sig(4096, 32, 8, 128)      # heuristic: fused
    assert kd.resolve_leg("bwd", small, "TPU v5e").impl == kd.IMPL_PALLAS
    monkeypatch.delenv("DS_TPU_ATTN_BWD")
    monkeypatch.delenv("DS_TPU_ATTN_FWD")
    monkeypatch.setenv("DS_TPU_ATTN_CACHE_DIR", str(tmp_path))
    for leg in ("fwd", "bwd"):
        get_cache().commit(kd.signature(leg, sig, "TPU v5e"),
                           {"impl": "fused", "block_q": 128, "block_k": 512})
    fwd, bwd = kd.resolve(sig, "TPU v5e")
    assert (bwd.impl, bwd.block_q, bwd.source) == ("fused", 128, "measured")
    assert (fwd.impl, fwd.source) == ("pallas", "heuristic")
    assert kd.describe(fwd, bwd) == (
        "attn[fwd=pallas@256x512:heuristic,bwd=fused@128x512:measured]")


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
    # short sequences keep the Pallas forward
    fwd, _ = kd.resolve(_bench_sig(q_shape=(8, 512, 16, 64), seq_k=512))
    assert fwd.impl == kd.IMPL_PALLAS
    # big heads keep the Pallas forward
    fwd, _ = kd.resolve(_bench_sig(q_shape=(8, 1024, 8, 128)))
    assert fwd.impl == kd.IMPL_PALLAS
    # windowed shapes keep the Pallas forward (it skips out-of-window
    # blocks; XLA still materializes [S, S])
    fwd, _ = kd.resolve(_bench_sig(window=256))
    assert fwd.impl == kd.IMPL_PALLAS


def test_measured_entry_beats_heuristic(monkeypatch, tmp_path):
    monkeypatch.setenv("DS_TPU_ATTN_CACHE_DIR", str(tmp_path))
    sig = _bench_sig()
    # heuristic first (cache empty)
    fwd, _ = kd.resolve(sig, "TPU v5e")
    assert fwd.source == "heuristic"
    get_cache().commit(kd.signature("fwd", sig, "TPU v5e"),
                       {"impl": "folded", "block_q": 512, "block_k": 1024,
                        "ms": 33.3})
    fwd, bwd = kd.resolve(sig, "TPU v5e")
    assert (fwd.impl, fwd.source) == ("folded", "measured")
    assert (fwd.block_q, fwd.block_k) == (512, 1024)
    # the OTHER leg has no measurement: stays heuristic
    assert bwd.source == "heuristic"
    # a different device kind does not see this measurement
    fwd_cpu, _ = kd.resolve(sig, "TPU v4")
    assert fwd_cpu.source == "heuristic"


def test_env_overrides_beat_measured(monkeypatch, tmp_path):
    monkeypatch.setenv("DS_TPU_ATTN_CACHE_DIR", str(tmp_path))
    sig = _bench_sig()
    get_cache().commit(kd.signature("fwd", sig, "x"),
                       {"impl": "folded", "block_q": 256, "block_k": 256})
    monkeypatch.setenv("DS_TPU_ATTN_FWD", "pallas")
    monkeypatch.setenv("DS_TPU_ATTN_BWD", "xla")
    fwd, bwd = kd.resolve(sig, "x")
    assert (fwd.impl, fwd.source) == ("pallas", "env")
    assert (bwd.impl, bwd.source) == ("xla", "env")
    # explicit kwargs beat even the env
    fwd, bwd = kd.resolve(sig, "x", impl_fwd="xla", impl_bwd="folded")
    assert (fwd.impl, fwd.source) == ("xla", "explicit")
    assert (bwd.impl, bwd.source) == ("folded", "explicit")


def test_legacy_folded_env_forces_both_legs(monkeypatch):
    monkeypatch.setenv("DS_TPU_FLASH_FOLDED", "1")
    fwd, bwd = kd.resolve(_bench_sig())
    assert fwd.impl == bwd.impl == kd.IMPL_FOLDED
    assert fwd.source == bwd.source == "legacy-env"
    # "0" only pins the per-head VARIANT; the fwd=XLA heuristic still wins
    monkeypatch.setenv("DS_TPU_FLASH_FOLDED", "0")
    fwd, bwd = kd.resolve(_bench_sig(), "TPU v5e")
    assert fwd.impl == kd.IMPL_XLA
    assert bwd.impl == kd.IMPL_FUSED    # a per-head kernel too


def test_pallas_only_restriction(monkeypatch):
    """force_pallas=True callers (kernel-math tests) must never silently get
    the XLA path back — an XLA pick degrades to the per-head kernel."""
    fwd, bwd = kd.resolve(_bench_sig(), "TPU v5e", pallas_only=True)
    assert fwd.impl == kd.IMPL_PALLAS and "pallas-forced" in fwd.source
    assert bwd.impl == kd.IMPL_FUSED
    monkeypatch.setenv("DS_TPU_FLASH_FOLDED", "1")
    fwd, _ = kd.resolve(_bench_sig(), "TPU v5e", pallas_only=True)
    assert fwd.impl == kd.IMPL_FOLDED


def test_describe_and_resolved_note():
    note = kd.resolved_note(kind="TPU v5e")
    assert note.startswith("attn[fwd=xla:heuristic,bwd=fused@")
    fwd, bwd = kd.resolve(_bench_sig(), "TPU v5e")
    d = kd.describe(fwd, bwd)
    assert "fwd=xla" in d and "bwd=fused@" in d


# ---------------------------------------------------------------------------
# persistent cache durability
# ---------------------------------------------------------------------------


def test_cache_round_trip(tmp_path):
    c = AutotuneCache(str(tmp_path / "t.json"))
    assert c.lookup("k") is None
    c.commit("k", {"impl": "xla", "block_q": 128, "block_k": 128, "ms": 1.0})
    got = c.lookup("k")
    assert got["impl"] == "xla" and "utc" in got
    # a second commit merges, never clobbers other keys
    c.commit("k2", {"impl": "pallas", "block_q": 256, "block_k": 512})
    assert c.lookup("k")["impl"] == "xla"
    assert c.lookup("k2")["impl"] == "pallas"


def test_cache_tolerates_torn_and_wrong_version(tmp_path):
    p = tmp_path / "t.json"
    p.write_text('{"version": 1, "entries": {"k": {"impl": "fol')  # torn
    c = AutotuneCache(str(p))
    assert c.lookup("k") is None
    assert "heuristic" in c.source_description()
    p.write_text(json.dumps({"version": CACHE_VERSION + 1,
                             "entries": {"k": {"impl": "xla"}}}))
    c2 = AutotuneCache(str(p))
    assert c2.lookup("k") is None
    # committing over garbage produces a clean valid table
    c.commit("k", {"impl": "xla", "block_q": 128, "block_k": 128})
    doc = json.loads(p.read_text())
    assert doc["version"] == CACHE_VERSION and "k" in doc["entries"]


def test_cache_bad_impl_entry_falls_back(monkeypatch, tmp_path):
    """A table entry naming an impl this build doesn't know (forward compat)
    must fall through to the heuristic, not crash or dispatch garbage."""
    monkeypatch.setenv("DS_TPU_ATTN_CACHE_DIR", str(tmp_path))
    sig = _bench_sig()
    get_cache().commit(kd.signature("fwd", sig, "z"),
                       {"impl": "cuda-graphs", "block_q": 1, "block_k": 1})
    fwd, _ = kd.resolve(sig, "z")
    assert fwd.source == "heuristic"


def test_env_dir_precedence(monkeypatch, tmp_path):
    monkeypatch.setenv("DS_TPU_ATTN_CACHE_DIR", str(tmp_path / "a"))
    assert cache_path() == str(tmp_path / "a" / "attn_dispatch.json")
    # unset: the tracked (empty) table in the checkout — never a home
    monkeypatch.delenv("DS_TPU_ATTN_CACHE_DIR")
    monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path / "xdg"))
    import json
    import deepspeed_tpu.ops.autotune_cache as ac
    tracked = os.path.join(os.path.dirname(os.path.abspath(ac.__file__)),
                           "attn_dispatch.json")
    assert cache_path() == tracked
    with open(tracked) as f:
        assert json.load(f) == {"version": 1, "entries": {}}


def test_cache_hit_changes_dispatched_kernels(monkeypatch, tmp_path):
    """End-to-end: a committed measurement changes which kernels the NEXT
    flash_attention call traces — and the answer stays oracle-correct."""
    monkeypatch.setenv("DS_TPU_ATTN_CACHE_DIR", str(tmp_path))
    q, k, v = _qkv(s=128, d=32)
    sig = kd.make_sig(q.shape, k.shape[2], k.shape[1], q.dtype, True,
                      None, None)
    kind = kd.device_kind()
    get_cache().commit(kd.signature("fwd", sig, kind),
                       {"impl": "folded", "block_q": 128, "block_k": 128})
    fwd, _ = kd.resolve(sig, kind)
    assert (fwd.impl, fwd.source) == ("folded", "measured")
    o_ref, _ = _ref(q, k, v)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# block handling
# ---------------------------------------------------------------------------


def test_explicit_blocks_pin_pallas_tiles(monkeypatch, tmp_path):
    monkeypatch.setenv("DS_TPU_ATTN_CACHE_DIR", str(tmp_path))
    sig = _bench_sig()
    get_cache().commit(kd.signature("bwd", sig, "y"),
                       {"impl": "pallas", "block_q": 512, "block_k": 1024})
    _, bwd = kd.resolve(sig, "y", blocks=(128, 128))
    assert (bwd.block_q, bwd.block_k) == (128, 128)  # explicit beats measured
    monkeypatch.setenv("DS_TPU_FLASH_BLOCKS", "256,256")
    _, bwd = kd.resolve(sig, "y")
    assert (bwd.block_q, bwd.block_k) == (256, 256)  # env beats measured
    monkeypatch.delenv("DS_TPU_FLASH_BLOCKS")
    _, bwd = kd.resolve(sig, "y")
    assert (bwd.block_q, bwd.block_k) == (512, 1024)  # measured beats default


def test_blocks_fit_short_sequences():
    """The default hd64 blocks (256, 512) exceed s=128 — execution must
    clamp them to divide the sequence instead of tripping the kernels'
    divisibility assert."""
    q, k, v = _qkv(s=128, d=64)
    o_ref, _ = _ref(q, k, v)
    out = flash_attention(q, k, v, causal=True, interpret=True,
                          impl_fwd="pallas", impl_bwd="pallas")
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


def test_sweep_writes_cache_consumed_by_dispatch(monkeypatch, tmp_path):
    """Acceptance: the sweep runs end-to-end on CPU (interpret mode), writes
    a valid version-stamped cache, and the next resolve() consumes it as
    'measured' for BOTH legs."""
    monkeypatch.setenv("DS_TPU_ATTN_CACHE_DIR", str(tmp_path))
    sweep = _load_sweep_module()
    results = sweep.sweep_shape(1, 128, 2, 2, 32, "float32", True,
                                iters=1, interpret=True, quick=True)
    assert set(results) == {"fwd", "bwd"}
    doc = json.loads((tmp_path / "attn_dispatch.json").read_text())
    assert doc["version"] == CACHE_VERSION and len(doc["entries"]) == 2
    sig = kd.make_sig((1, 128, 2, 32), 2, 128, "float32", True, None, None)
    fwd, bwd = kd.resolve(sig, "interpret")
    assert fwd.source == "measured" and bwd.source == "measured"
    assert fwd.impl in IMPLS and bwd.impl in BWD_IMPLS


def test_sweep_dry_run_commits_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv("DS_TPU_ATTN_CACHE_DIR", str(tmp_path))
    sweep = _load_sweep_module()
    sweep.sweep_shape(1, 128, 2, 2, 32, "float32", True, iters=1,
                      interpret=True, quick=True, commit=False,
                      impls=(kd.IMPL_XLA, kd.IMPL_PALLAS))
    assert not (tmp_path / "attn_dispatch.json").exists()


def test_sweep_takes_a_block_grid_and_times_the_backward_alone(monkeypatch,
                                                              tmp_path):
    """``--impls`` / ``--blocks``: one impl over a grid of one's own beside
    the chosen blocks (the PR 32 block sweep), each leg with a time of its
    own from the same residuals."""
    monkeypatch.setenv("DS_TPU_ATTN_CACHE_DIR", str(tmp_path))
    sweep = _load_sweep_module()
    results = sweep.sweep_shape(1, 128, 2, 2, 32, "float32", True, iters=1,
                                interpret=True, quick=False, commit=False,
                                impls=(kd.IMPL_PALLAS, ),
                                grid=[(64, 64), (128, 64)])
    for leg in ("fwd", "bwd"):
        entry, rows = results[leg]
        assert [r[0] for r in rows] == ["pallas@128x128", "pallas@64x64",
                                        "pallas@128x64"]
        assert all(r[-1] > 0 for r in rows) and entry["impl"] == "pallas"
    assert not (tmp_path / "attn_dispatch.json").exists()


# ---------------------------------------------------------------------------
# reporting surfaces
# ---------------------------------------------------------------------------


def test_env_report_includes_dispatch_lines():
    from deepspeed_tpu.env_report import debug_report
    rep = debug_report()
    assert "attn dispatch table" in rep
    assert "attn dispatch @ bench shape" in rep
    assert "attn[fwd=" in rep


def test_table_source_reflects_cache_state(monkeypatch, tmp_path):
    monkeypatch.setenv("DS_TPU_ATTN_CACHE_DIR", str(tmp_path))
    assert "heuristic" in kd.table_source()
    get_cache().commit("sig", {"impl": "xla", "block_q": 1, "block_k": 1})
    assert kd.table_source().startswith("measured")
