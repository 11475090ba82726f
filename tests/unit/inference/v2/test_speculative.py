"""Prompt-lookup speculative decoding (beyond the reference): draft from
earlier context, verify all drafts in ONE window-logits forward, roll back
rejections in place. Greedy-exactness is the correctness bar: speculative
output must EQUAL plain greedy decode token-for-token (acceptance only
short-circuits compute, never changes the distribution)."""

import numpy as np
import pytest
import jax.numpy as jnp

from deepspeed_tpu.comm.mesh import reset_mesh_context
from deepspeed_tpu.inference.v2.engine_v2 import build_llama_engine
from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
from deepspeed_tpu.models import LlamaConfig, init_llama


def _engines(prefix=False):
    reset_mesh_context()
    cfg = LlamaConfig.tiny(num_key_value_heads=4)
    _, params = init_llama(cfg, seed=21)
    ec = RaggedInferenceEngineConfig(num_kv_blocks=128,
                                     enable_prefix_caching=prefix)
    mk = lambda: build_llama_engine(cfg, params=params, dtype=jnp.float32,  # noqa: E731
                                    engine_config=ec, kv_block_size=16)
    return mk(), mk(), cfg


def _repetitive_prompt(rng, n=48):
    # repetition makes prompt-lookup drafts actually fire
    motif = rng.integers(0, 64, size=6).tolist()
    out = []
    while len(out) < n:
        out.extend(motif)
    return out[:n]


def test_speculative_matches_plain_greedy():
    rng = np.random.default_rng(0)
    prompts = [_repetitive_prompt(rng), rng.integers(0, 200, size=20).tolist()]
    eng_a, eng_b, _ = _engines()
    ref = eng_a.generate(prompts, max_new_tokens=12)
    got = eng_b.generate(prompts, max_new_tokens=12,
                         speculative="prompt_lookup", num_draft_tokens=4)
    assert got == ref
    assert all(len(o) == 12 for o in got)


def test_speculative_rollback_bookkeeping():
    """After a round with rejections, seen_tokens must equal prompt +
    accepted outputs (rolled back in place), and decode must continue
    correctly from there."""
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 200, size=24).tolist()  # random: drafts miss
    eng_a, eng_b, _ = _engines()
    ref = eng_a.generate([prompt], max_new_tokens=8)
    got = eng_b.generate([prompt], max_new_tokens=8,
                         speculative="prompt_lookup", num_draft_tokens=3,
                         draft_ngram=1)
    assert got == ref


def test_speculative_composes_with_prefix_caching():
    rng = np.random.default_rng(2)
    shared = _repetitive_prompt(rng, n=32)
    eng_a, eng_b, _ = _engines(prefix=True)
    ref = eng_a.generate([shared + [7, 9]], max_new_tokens=10)
    # second engine: warm the prefix cache, then speculative-decode a
    # sibling prompt adopting the cached prefix
    eng_b.generate([shared + [3, 5]], max_new_tokens=2)
    got = eng_b.generate([shared + [7, 9]], max_new_tokens=10,
                         speculative="prompt_lookup", num_draft_tokens=4)
    assert got == ref


def test_speculative_eos_and_validation():
    rng = np.random.default_rng(3)
    prompt = _repetitive_prompt(rng)
    eng_a, eng_b, _ = _engines()
    ref = eng_a.generate([prompt], max_new_tokens=12, eos_token_id=5)
    got = eng_b.generate([prompt], max_new_tokens=12, eos_token_id=5,
                         speculative="prompt_lookup", num_draft_tokens=4)
    assert got == ref
    # speculative + sampling is ACCEPTED now (on-device rejection
    # sampling); only per-emitted-token mutations and logprobs remain out
    sampled = eng_b.generate([prompt], max_new_tokens=4,
                             speculative="prompt_lookup", temperature=0.7,
                             seed=3)
    assert len(sampled[0]) == 4
    with pytest.raises(ValueError, match="does not return logprobs"):
        eng_b.generate([prompt], max_new_tokens=2,
                       speculative="prompt_lookup", return_logprobs=True)
    with pytest.raises(ValueError, match="does not compose"):
        eng_b.generate([prompt], max_new_tokens=2,
                       speculative="prompt_lookup", repetition_penalty=1.2)
    with pytest.raises(ValueError, match="unknown speculative"):
        eng_b.generate([prompt], max_new_tokens=2, speculative="medusa")


def test_speculative_with_sliding_window_defers_frees():
    """Review repro class: with a uniform sliding window, the trailing-KV
    free must not act on draft-inflated seen_tokens — a block freed against
    the inflated window could still be needed after rollback. Window frees
    are deferred to post-rollback; outputs must equal plain greedy."""
    reset_mesh_context()
    cfg = LlamaConfig.tiny(num_key_value_heads=4, sliding_window=16,
                           attn_impl="xla")
    _, params = init_llama(cfg, seed=23)
    ec = RaggedInferenceEngineConfig(num_kv_blocks=128)
    mk = lambda: build_llama_engine(cfg, params=params, dtype=jnp.float32,  # noqa: E731
                                    engine_config=ec, kv_block_size=8)
    rng = np.random.default_rng(4)
    prompt = _repetitive_prompt(rng, n=40)
    ref = mk().generate([prompt], max_new_tokens=16)
    got = mk().generate([prompt], max_new_tokens=16,
                        speculative="prompt_lookup", num_draft_tokens=4)
    assert got == ref


def test_warmup_covers_window_bucket():
    eng, _, _ = _engines()
    n = eng.warmup(prefill_lens=(32,), draft_tokens=3)
    keys = list(eng.model()._fwd_cache)
    assert any(k[1] for k in keys), keys  # a window_logits program compiled
    assert n == len(keys)


def test_triple_composition_int8_prefix_speculative():
    """The three beyond-reference serving features compose: int8 KV cache
    (adoption shares quantized blocks + scales), prefix caching, and
    speculative decoding together produce the same greedy output as a
    plain engine."""
    reset_mesh_context()
    cfg = LlamaConfig.tiny(num_key_value_heads=4)
    _, params = init_llama(cfg, seed=31)
    rng = np.random.default_rng(0)
    shared = (rng.integers(0, 64, size=8).tolist() * 8)[:48]

    plain = build_llama_engine(
        cfg, params=params, dtype=jnp.float32,
        engine_config=RaggedInferenceEngineConfig(num_kv_blocks=128),
        kv_block_size=16)
    ref = plain.generate([shared + [3, 7]], max_new_tokens=10)

    combo = build_llama_engine(
        cfg, params=params, dtype=jnp.float32,
        engine_config=RaggedInferenceEngineConfig(
            num_kv_blocks=128, enable_prefix_caching=True),
        kv_block_size=16, kv_cache_dtype="int8")
    combo.generate([shared + [1, 2]], max_new_tokens=2)  # warm the cache
    got = combo.generate([shared + [3, 7]], max_new_tokens=10,
                         speculative="prompt_lookup", num_draft_tokens=4)
    # int8 rounding can in principle flip near-ties; on this fixture the
    # outputs are exactly equal — pin that (a flake here means real drift)
    assert got == ref
    pc = combo._state_manager.prefix_cache
    assert len(pc) >= 3  # the shared prefix lives in the (quantized) cache


def test_score_matches_teacher_forced_apply():
    """engine.score() log-probs must equal the training model's full
    teacher-forced forward (the exact oracle), and flush=False leaves the
    prefix decodable."""
    reset_mesh_context()
    cfg = LlamaConfig.tiny(num_key_value_heads=4, attn_impl="xla",
                           dtype=jnp.float32)
    model, params = init_llama(cfg, seed=51)
    eng = build_llama_engine(
        cfg, params=params, dtype=jnp.float32,
        engine_config=RaggedInferenceEngineConfig(num_kv_blocks=64),
        kv_block_size=16)
    rng = np.random.default_rng(0)
    toks = [rng.integers(0, 200, size=n).tolist() for n in (24, 17)]

    got = eng.score([0, 1], toks, flush=False)

    import jax
    # both in one compiled forward, the shorter padded on the right: a
    # causal model's logits at a position read nothing after it
    ids = np.zeros((len(toks), max(map(len, toks))), np.int32)
    for i, t in enumerate(toks):
        ids[i, :len(t)] = t
    dense = np.asarray(jax.jit(lambda p, x: model.apply({"params": p}, x))(
        params, ids), np.float64)
    for i, t in enumerate(toks):
        logits = dense[i, :len(t)]  # [T, V]
        rows = logits[:-1]
        logz = np.log(np.exp(rows - rows.max(-1, keepdims=True))
                      .sum(-1)) + rows.max(-1)
        ref = rows[np.arange(len(t) - 1), np.asarray(t[1:])] - logz
        np.testing.assert_allclose(got[i], ref, rtol=1e-4, atol=1e-4)

    # flush=False: the scored prefix keeps decoding
    nxt = np.asarray(eng.put([0], [[toks[0][-1] % 200]]), np.float32)
    assert np.isfinite(nxt).all()
    eng.flush(0), eng.flush(1)
    with pytest.raises(ValueError, match="NEW sequences"):
        eng.put([5], [[1, 2, 3]])
        eng.score([5], [[1, 2, 3]])


def test_speculative_staggered_batch_matches_plain():
    """8 prompts through a max_seqs-limited engine: admission waves,
    retirements and batched draft/verify steps together must still be
    greedy-exact vs the plain path."""
    reset_mesh_context()
    cfg = LlamaConfig.tiny(num_key_value_heads=4)
    _, params = init_llama(cfg, seed=61)
    from deepspeed_tpu.inference.v2.config_v2 import DSStateManagerConfig
    ec = RaggedInferenceEngineConfig(
        num_kv_blocks=256,
        state_manager=DSStateManagerConfig(max_ragged_sequence_count=3))
    mk = lambda: build_llama_engine(cfg, params=params, dtype=jnp.float32,  # noqa: E731
                                    engine_config=ec, kv_block_size=16)
    rng = np.random.default_rng(6)
    prompts = []
    for i in range(8):
        if i % 2 == 0:
            prompts.append(_repetitive_prompt(rng, n=30 + i))
        else:
            prompts.append(rng.integers(0, 200, size=12 + i).tolist())
    ref = mk().generate(prompts, max_new_tokens=7)
    got = mk().generate(prompts, max_new_tokens=7,
                        speculative="prompt_lookup", num_draft_tokens=3)
    assert got == ref


def test_score_with_prefix_caching_enabled():
    """Regression (found by the serving demo): score() must feed EVERY
    token even when the prompt's prefix is cached — adoption would leave
    window logits covering only the suffix."""
    reset_mesh_context()
    cfg = LlamaConfig.tiny(num_key_value_heads=4, dtype=jnp.float32)
    _, params = init_llama(cfg, seed=71)
    eng = build_llama_engine(
        cfg, params=params, dtype=jnp.float32,
        engine_config=RaggedInferenceEngineConfig(
            num_kv_blocks=64, enable_prefix_caching=True),
        kv_block_size=16)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 200, size=33).tolist()
    ref = eng.score([0], [prompt])[0]        # cold: nothing cached yet
    eng.put([1], [prompt])
    eng.flush(1)                             # prompt now cached
    got = eng.score([2], [prompt])[0]        # must NOT adopt
    np.testing.assert_allclose(got, ref, rtol=1e-6)
    assert len(got) == 32
