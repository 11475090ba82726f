"""Paged-attention (blocked flash decode) kernel numerics.

Kernel runs in Pallas interpret mode on the CPU test harness; the reference
is the dense-gather XLA path it replaces (round-1 serving attention)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.ops.paged_attention import paged_attention, paged_attention_reference

INTERP = jax.default_backend() != "tpu"


def _setup(rng, S, N, KV, G, D, ps, n_pages, B, seen, n_new, dtype=jnp.float32):
    # cache layout [2L, slots, KV*D]: k row 2l, v row 2l+1 (kv_cache.py);
    # queries head-major [S, N, H=KV*G, D]
    cache = jnp.asarray(rng.normal(size=(2 * 2, n_pages * ps, KV * D)), dtype)
    q = jnp.asarray(rng.normal(size=(S, N, KV * G, D)), dtype)
    bt = jnp.asarray(rng.permutation(n_pages)[:S * B].reshape(S, B), jnp.int32)
    seen = jnp.asarray(seen, jnp.int32)
    lens = seen + jnp.asarray(n_new, jnp.int32)
    return q, cache, bt, seen, lens


def test_matches_dense_reference_mixed_batch():
    """Decode (N tail) + prefill-burst + fully-padded sequences in one batch."""
    rng = np.random.default_rng(0)
    S, N, KV, G, D, ps, n_pages, B = 4, 2, 2, 3, 32, 16, 32, 4
    q, cache, bt, seen, lens = _setup(rng, S, N, KV, G, D, ps, n_pages, B,
                                      seen=[5, 0, 37, 0], n_new=[2, 1, 2, 0])
    out_k = paged_attention(q, cache, 1, bt, seen, lens, page_size=ps, interpret=INTERP)
    out_r = paged_attention_reference(q, cache, 1, bt, seen, lens, page_size=ps)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=2e-5)


def test_layer_indexing_reads_right_pages():
    rng = np.random.default_rng(1)
    q, cache, bt, seen, lens = _setup(rng, 2, 1, 1, 2, 16, 8, 8, 2,
                                      seen=[7, 3], n_new=[1, 1])
    for layer in (0, 1):
        out_k = paged_attention(q, cache, layer, bt, seen, lens, page_size=8,
                                interpret=INTERP)
        out_r = paged_attention_reference(q, cache, layer, bt, seen, lens, page_size=8)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=2e-5)
    # and the two layers genuinely differ
    a = paged_attention(q, cache, 0, bt, seen, lens, page_size=8, interpret=INTERP)
    b = paged_attention(q, cache, 1, bt, seen, lens, page_size=8, interpret=INTERP)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-3


def test_gqa_grouping():
    """G query heads share one KV head — compare against expanded-KV einsum."""
    rng = np.random.default_rng(2)
    S, N, KV, G, D, ps, n_pages, B = 2, 1, 2, 4, 16, 8, 16, 2
    q, cache, bt, seen, lens = _setup(rng, S, N, KV, G, D, ps, n_pages, B,
                                      seen=[9, 2], n_new=[1, 1])
    out_k = paged_attention(q, cache, 0, bt, seen, lens, page_size=ps, interpret=INTERP)
    out_r = paged_attention_reference(q, cache, 0, bt, seen, lens, page_size=ps)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=2e-5)


def test_bf16_inputs_fp32_accumulation():
    rng = np.random.default_rng(3)
    q, cache, bt, seen, lens = _setup(rng, 2, 1, 1, 1, 32, 16, 8, 2,
                                      seen=[20, 11], n_new=[1, 1], dtype=jnp.bfloat16)
    out_k = paged_attention(q, cache, 0, bt, seen, lens, page_size=16, interpret=INTERP)
    out_r = paged_attention_reference(q, cache, 0, bt, seen, lens, page_size=16)
    assert out_k.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out_k, dtype=np.float32),
                               np.asarray(out_r, dtype=np.float32), atol=3e-2)


def test_ragged_forward_paged_matches_dense():
    """Engine-level: the full ragged forward produces the same logits under
    both attention backends."""
    from functools import partial
    from deepspeed_tpu.models import LlamaConfig
    from deepspeed_tpu.models.llama import init_llama
    from deepspeed_tpu.inference.v2.model import RaggedLlamaModel, _ragged_forward
    from deepspeed_tpu.inference.v2.ragged.kv_cache import BlockedKVCache
    from deepspeed_tpu.inference.v2.ragged.ragged_wrapper import RaggedBatch

    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    _, params = init_llama(cfg)
    bs = 8
    n_blocks = 8
    total = n_blocks * bs
    kvc = BlockedKVCache.__new__(BlockedKVCache)
    cache0 = jnp.asarray(np.random.default_rng(0).normal(
        size=(2 * cfg.num_hidden_layers, total,
              cfg.num_key_value_heads * cfg.head_dim_)) * 0.1,
        jnp.float32)

    # one seq: 5 seen tokens (pages 1,2), 2 new
    batch = RaggedBatch(
        tokens=jnp.asarray([3, 4], jnp.int32),
        token_seq=jnp.asarray([0, 0], jnp.int32),
        token_pos=jnp.asarray([5, 6], jnp.int32),
        token_slot=jnp.asarray([1 * bs + 5, 1 * bs + 6], jnp.int32),
        seq_start=jnp.asarray([0], jnp.int32),
        seq_n_new=jnp.asarray([2], jnp.int32),
        seq_seen=jnp.asarray([5], jnp.int32),
        block_table=jnp.asarray([[1, 2]], jnp.int32),
        last_token_idx=jnp.asarray([1], jnp.int32),
        q_tok_idx=jnp.asarray([[0, 1]], jnp.int32),
    )
    fp = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), params)
    logits_d, _ = _ragged_forward(fp, cache0, batch, config=cfg, block_size=bs,
                                  attn_backend="dense")
    logits_p, _ = _ragged_forward(fp, cache0, batch, config=cfg, block_size=bs,
                                  attn_backend="paged")
    np.testing.assert_allclose(np.asarray(logits_p), np.asarray(logits_d),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("window", [4, 16])
def test_sliding_window_matches_reference(window):
    """Local attention: out-of-window pages skipped, numerics match."""
    rng = np.random.default_rng(5)
    S, N, KV, G, D, ps, n_pages, B = 3, 2, 2, 2, 32, 8, 32, 4
    q, cache, bt, seen, lens = _setup(rng, S, N, KV, G, D, ps, n_pages, B,
                                      seen=[20, 3, 0], n_new=[2, 1, 2])
    out_k = paged_attention(q, cache, 0, bt, seen, lens, page_size=ps,
                            window=window, interpret=INTERP)
    out_r = paged_attention_reference(q, cache, 0, bt, seen, lens, page_size=ps,
                                      window=window)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=2e-5)
    # and differs from global attention where history exceeds the window
    out_g = paged_attention_reference(q, cache, 0, bt, seen, lens, page_size=ps)
    assert not np.allclose(np.asarray(out_r[0]), np.asarray(out_g[0]))


def test_alibi_and_scale_match_reference():
    rng = np.random.default_rng(6)
    S, N, KV, G, D, ps, n_pages, B = 2, 2, 2, 2, 32, 8, 16, 3
    q, cache, bt, seen, lens = _setup(rng, S, N, KV, G, D, ps, n_pages, B,
                                      seen=[10, 0], n_new=[2, 2])
    out_k = paged_attention(q, cache, 0, bt, seen, lens, page_size=ps,
                            use_alibi=True, attn_scale=1.0, interpret=INTERP)
    out_r = paged_attention_reference(q, cache, 0, bt, seen, lens, page_size=ps,
                                      use_alibi=True, attn_scale=1.0)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=2e-5)
    out_noalibi = paged_attention_reference(q, cache, 0, bt, seen, lens,
                                            page_size=ps, attn_scale=1.0)
    assert not np.allclose(np.asarray(out_r), np.asarray(out_noalibi))


def test_softcap_matches_reference():
    """Gemma-2 logit softcap in-kernel: cap*tanh(s/cap) before masks, both
    alone and combined with a sliding window."""
    rng = np.random.default_rng(7)
    S, N, KV, G, D, ps, n_pages, B = 2, 2, 2, 2, 32, 8, 16, 3
    q, cache, bt, seen, lens = _setup(rng, S, N, KV, G, D, ps, n_pages, B,
                                      seen=[18, 4], n_new=[2, 2])
    for window in (None, 12):
        out_k = paged_attention(q, cache, 0, bt, seen, lens, page_size=ps,
                                softcap=5.0, window=window, interpret=INTERP)
        out_r = paged_attention_reference(q, cache, 0, bt, seen, lens,
                                          page_size=ps, softcap=5.0,
                                          window=window)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                                   atol=2e-5)
    # the cap must actually bite (differs from uncapped)
    out_u = paged_attention_reference(q, cache, 0, bt, seen, lens, page_size=ps)
    assert not np.allclose(np.asarray(out_r), np.asarray(out_u))


@pytest.mark.parametrize("window", [None, 24])
def test_query_tiles_match_dense_reference(monkeypatch, window):
    """A new-token run longer than one query tile: the grid's tile axis,
    the per-tile causal page skip and the window skip must give what the
    whole-run block gave. The VMEM budget is shrunk so that these small
    shapes split into four tiles of 8."""
    from deepspeed_tpu.ops import paged_attention as pa
    S, N, KV, G, D, ps, n_pages, B = 3, 32, 2, 2, 32, 16, 32, 8
    per_row = D * 4 + 2 * 128 * 4 + 4 * D * 4
    monkeypatch.setattr(pa, "_RESIDENT_VMEM_BYTES", 8 * KV * G * per_row)
    assert pa._query_tile(N, KV * G, D, 4) == 8
    rng = np.random.default_rng(11)
    q, cache, bt, seen, lens = _setup(rng, S, N, KV, G, D, ps, n_pages, B,
                                      seen=[40, 0, 70], n_new=[32, 19, 1])
    out_k = paged_attention(q, cache, 1, bt, seen, lens, page_size=ps,
                            window=window, interpret=INTERP)
    out_r = paged_attention_reference(q, cache, 1, bt, seen, lens,
                                      page_size=ps, window=window)
    n_new = np.asarray(lens - seen)
    for s in range(S):  # rows past n_new are padding: unspecified
        np.testing.assert_allclose(np.asarray(out_k)[s, :n_new[s]],
                                   np.asarray(out_r)[s, :n_new[s]], atol=2e-5)


def test_query_tile_bounds_resident_vmem_at_7b_widths():
    from deepspeed_tpu.ops.paged_attention import _query_tile
    assert _query_tile(512, 32, 128, 2) == 128  # what the v5e compiler takes
    assert _query_tile(128, 32, 128, 2) == 128
    assert _query_tile(1, 32, 128, 2) == 1
