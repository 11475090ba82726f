"""Inference-v2 ragged engine tests.

Mirrors reference coverage in ``tests/unit/inference/v2/ragged/`` (allocator,
manager) and ``tests/unit/inference/v2/model_implementations`` — plus the key
numerics check the reference does per-kernel: incremental paged-KV serving
must match the dense training-model forward.
"""

import functools

import jax
import numpy as np
import pytest
import jax.numpy as jnp

from deepspeed_tpu.models.llama import LlamaConfig, init_llama, LlamaForCausalLM
from deepspeed_tpu.inference.v2 import (RaggedInferenceEngineConfig, DSStateManagerConfig,
                                        SchedulingResult, SchedulingError, build_llama_engine)
from deepspeed_tpu.inference.v2.ragged import BlockedAllocator


CFG = LlamaConfig.tiny(dtype=jnp.float32)


@functools.lru_cache(maxsize=None)
def _dense_forward(model):
    return jax.jit(lambda params, ids: model.apply({"params": params}, ids))


def dense_logits(model, params, tokens):
    """Reference logits from the training model's full forward, [len, vocab].
    The tokens are padded on the right to a multiple of 32 and the forward is
    one compiled program a length: the model is causal, so the logits at a
    position do not read the positions after it."""
    ids = np.zeros((1, -(-len(tokens) // 32) * 32), np.int32)
    ids[0, :len(tokens)] = tokens
    return np.asarray(_dense_forward(model)(params, ids))[0, :len(tokens)]


@pytest.fixture(scope="module")
def llama():
    model, params = init_llama(CFG, seed=0, seq_len=8)
    return model, params


@pytest.fixture()
def engine(llama):
    _, params = llama
    return build_llama_engine(CFG, params=params, dtype=jnp.float32, kv_block_size=16,
                              engine_config=RaggedInferenceEngineConfig(
                                  state_manager=DSStateManagerConfig(
                                      max_tracked_sequences=16,
                                      max_ragged_batch_size=128,
                                      max_ragged_sequence_count=8,
                                      max_context=128),
                                  num_kv_blocks=32))


class TestBlockedAllocator:

    def test_alloc_free_roundtrip(self):
        a = BlockedAllocator(8)
        blocks = a.allocate(5)
        assert a.free_blocks == 3
        assert len(set(int(b) for b in blocks)) == 5
        a.free(blocks)
        assert a.free_blocks == 8

    def test_over_allocate_raises(self):
        a = BlockedAllocator(4)
        a.allocate(3)
        with pytest.raises(ValueError):
            a.allocate(2)

    def test_double_free_raises(self):
        a = BlockedAllocator(4)
        b = a.allocate(1)
        a.free(b)
        with pytest.raises(ValueError):
            a.free(b)

    def test_invalid_block_raises(self):
        a = BlockedAllocator(4)
        with pytest.raises(ValueError):
            a.free(17)


class TestScheduling:

    def test_can_schedule_success(self, engine):
        assert engine.can_schedule([0, 1], [10, 20]) == SchedulingResult.Success

    def test_batch_token_limit(self, engine):
        # 6 seqs x 25 tokens = 150 > max_ragged_batch_size=128, each within
        # max_context and KV capacity
        uids, lens = list(range(6)), [25] * 6
        assert engine.can_schedule(uids, lens) == SchedulingResult.BatchTokenLimitExceeded

    def test_sequence_count_limit(self, engine):
        uids = list(range(9))
        assert engine.can_schedule(uids, [1] * 9) == SchedulingResult.BatchSequenceLimitExceeded

    def test_put_unschedulable_raises(self, engine):
        with pytest.raises(SchedulingError):
            engine.put([0], [np.arange(1000)])

    def test_max_context_enforced(self, engine):
        # 129 > max_context=128 must be rejected BEFORE put() would crash
        assert engine.can_schedule([0], [129]) == SchedulingResult.SequenceTokenLimitExceeded

    def test_query_new_uid(self, engine):
        toks, blocks = engine.query(uid=123, max_request_tokens=20, max_request_blocks=100)
        assert toks == 20 and blocks == 2  # ceil(20/16)


class TestRaggedServing:

    def test_prefill_matches_dense(self, llama, engine):
        model, params = llama
        tokens = np.arange(1, 13) % CFG.vocab_size
        logits = np.asarray(engine.put([7], [tokens]))
        ref = dense_logits(model, params, tokens)[-1]
        np.testing.assert_allclose(logits[0], ref, rtol=2e-4, atol=2e-4)

    def test_decode_matches_dense(self, llama, engine):
        model, params = llama
        prompt = (np.arange(1, 10) * 3) % CFG.vocab_size
        engine.put([1], [prompt])
        fed = [(7 * step + 1) % CFG.vocab_size for step in range(20)]
        # one dense forward over all that will have been fed: its row i is
        # the reference after i + 1 tokens
        ref = dense_logits(model, params, list(prompt) + fed)
        for step, nxt in enumerate(fed):  # crosses a 16-token block boundary
            logits = np.asarray(engine.put([1], [[nxt]]))
            np.testing.assert_allclose(logits[0], ref[len(prompt) + step],
                                       rtol=5e-4, atol=5e-4)

    def test_multi_sequence_ragged_batch(self, llama, engine):
        model, params = llama
        t_a = np.arange(1, 8) % CFG.vocab_size
        t_b = (np.arange(1, 15) * 5) % CFG.vocab_size
        logits = np.asarray(engine.put([10, 11], [t_a, t_b]))
        np.testing.assert_allclose(logits[0], dense_logits(model, params, t_a)[-1],
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(logits[1], dense_logits(model, params, t_b)[-1],
                                   rtol=2e-4, atol=2e-4)

    def test_mixed_prefill_decode(self, llama, engine):
        """Dynamic SplitFuse composition: one decoding seq + one fresh prefill."""
        model, params = llama
        t_a = np.arange(1, 8) % CFG.vocab_size
        engine.put([1], [t_a])
        t_b = (np.arange(1, 20) * 11) % CFG.vocab_size
        logits = np.asarray(engine.put([1, 2], [[42], t_b]))
        np.testing.assert_allclose(
            logits[0], dense_logits(model, params, list(t_a) + [42])[-1], rtol=5e-4, atol=5e-4)
        np.testing.assert_allclose(logits[1], dense_logits(model, params, t_b)[-1],
                                   rtol=2e-4, atol=2e-4)

    def test_flush_frees_blocks(self, engine):
        free0 = engine.free_blocks
        engine.put([5], [np.arange(1, 40)])
        assert engine.free_blocks < free0
        engine.flush(5)
        assert engine.free_blocks == free0

    def test_remaining_block_capacity(self, engine):
        engine.put([5], [np.arange(1, 10)])  # 9 tokens, block 16
        assert engine.get_remaining_block_capacity(5) == 16 - 9
