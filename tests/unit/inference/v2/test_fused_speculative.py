"""Fused speculative decoding: draft + verify + accept inside the K-window
scan (ops/sampling.ngram_draft_ring + spec_verify_window, model
fused_spec_decode, engine fused_spec_decode_steps, scheduler fused spec
wave).

Parity contracts:
- GREEDY: the fused program must be byte-identical to the per-token host
  path (prompt_lookup_draft + accept_drafts) AND to plain greedy — greedy
  verification is draft-independent by construction (accepted drafts equal
  the argmax tokens), so any divergence is a real bug.
- SAMPLED: under a fixed seed the fused program must match the host
  rejection-sampling oracle (accept_drafts_sampled, gate off) token for
  token: both sides run the SAME spec_verify_window math and burn exactly
  one key split per window. The oracle comparison needs ample output
  budget (host room caps can shorten end-of-stream drafts; the draft
  CONTENT feeds the sampled accept test, unlike greedy).
"""

import numpy as np
import pytest
import jax.numpy as jnp

from deepspeed_tpu.comm.mesh import reset_mesh_context
from deepspeed_tpu.inference.v2.config_v2 import (RaggedInferenceEngineConfig,
                                                  SamplingConfig)
from deepspeed_tpu.inference.v2.engine_v2 import build_llama_engine
from deepspeed_tpu.inference.v2.server import ServingScheduler
from deepspeed_tpu.models import LlamaConfig, init_llama

BS = 16


def _engine(num_blocks=160, **cfg_kw):
    reset_mesh_context()
    cfg = LlamaConfig.tiny(num_key_value_heads=4)
    _, params = init_llama(cfg, seed=21)
    return build_llama_engine(
        cfg, params=params, dtype=jnp.float32, kv_block_size=BS,
        engine_config=RaggedInferenceEngineConfig(num_kv_blocks=num_blocks,
                                                  **cfg_kw))


def _repetitive_prompt(rng, n=50):
    motif = rng.integers(0, 64, size=6).tolist()
    out = []
    while len(out) < n:
        out.extend(motif)
    return out[:n]


def test_fused_spec_greedy_bit_identical():
    """Fused speculative == per-token speculative == plain greedy on both
    a draft-friendly (repetitive) and a draft-hostile (random) prompt."""
    rng = np.random.default_rng(0)
    prompts = [_repetitive_prompt(rng),
               rng.integers(0, 200, size=20).tolist()]
    ref = _engine().generate(prompts, max_new_tokens=18)
    kw = dict(max_new_tokens=18, speculative="prompt_lookup",
              num_draft_tokens=4, draft_ngram=2)
    per_tok = _engine().generate(prompts, fused_decode_window=1, **kw)
    fused = _engine().generate(prompts, fused_decode_window=8, **kw)
    assert per_tok == ref
    assert fused == ref


def test_fused_spec_sampled_matches_host_oracle():
    """Fixed seed: fused speculative sampling equals the host
    rejection-sampling oracle (fused_speculative_decode=False keeps the
    per-token accept_drafts_sampled path — same spec_verify_window math,
    same one-key-split-per-window budget)."""
    rng = np.random.default_rng(0)
    prompts = [_repetitive_prompt(rng),
               rng.integers(0, 200, size=20).tolist()]
    kw = dict(max_new_tokens=18, speculative="prompt_lookup",
              num_draft_tokens=4, draft_ngram=2, fused_decode_window=8,
              temperature=0.8, top_k=20, top_p=0.9, seed=123)
    fused = _engine().generate(prompts, **kw)
    oracle = _engine(sampling=SamplingConfig(
        fused_speculative_decode=False)).generate(prompts, **kw)
    assert fused == oracle
    assert all(len(o) == 18 for o in fused)


def test_fused_spec_one_dispatch_per_k_windows():
    """Trace-counted: on the fused path EVERY decode token comes out of
    fused_spec dispatches — puts are prefill-only — and each dispatch is
    one host fetch covering its whole run of windows; the per-token path
    spends one put per window."""
    rng = np.random.default_rng(0)
    prompt = _repetitive_prompt(rng)
    new, K = 16, 8

    def run(window):
        eng = _engine()
        calls = {"put": 0, "spec_windows": [], "spec_tokens": []}
        orig_put = eng.put
        orig_spec = eng.fused_spec_decode_steps
        eng.put = lambda *a, **k: calls.__setitem__(
            "put", calls["put"] + 1) or orig_put(*a, **k)

        def spec(uids, hists, n_steps, **k):
            res = orig_spec(uids, hists, n_steps, **k)
            calls["spec_windows"].append(n_steps)
            calls["spec_tokens"].append(len(res[0][0]))
            return res

        eng.fused_spec_decode_steps = spec
        out = eng.generate([prompt], max_new_tokens=new,
                           speculative="prompt_lookup", num_draft_tokens=4,
                           draft_ngram=2, fused_decode_window=window)
        return out, calls

    out1, c1 = run(1)
    out8, c8 = run(K)
    assert out1 == out8
    assert c1["spec_windows"] == []  # window 1 never fuses
    # the first dispatch carries the full K windows; a later one carries
    # the largest power of two the remaining output budget admits
    # (fused_spec_partition), and every window emits at least one token
    assert c8["spec_windows"][0] == K
    assert all(n >= 2 and n & (n - 1) == 0 for n in c8["spec_windows"])
    assert all(t >= n for t, n in zip(c8["spec_tokens"], c8["spec_windows"]))
    # so with NO draft accepted (this seeded model takes one of eight) the
    # new - 1 tokens after the prefill's cost 8 + 4 + 2 windows, three
    # dispatches and one solo tick; accepted drafts only shorten that. The
    # per-token path spends a put per window.
    worst, left = 0, new - 1
    while left >= 2:
        left -= min(K, 1 << (left.bit_length() - 1))
        worst += 1
    assert len(c8["spec_windows"]) <= worst == 3
    # fused path decode never touches put but for the near-retirement solo
    # tick: prefill-only (the per-token run spends every additional put on
    # decode windows)
    assert c8["put"] < c1["put"]
    assert c8["put"] <= 2 + left


def test_fused_spec_rollback_after_full_rejection():
    """Random prompt + 1-gram drafts: drafts fire and get (mostly)
    rejected. On device the rejected tail is rolled back purely by
    position: the next window overwrites its KV slots. The host invariant:
    seen_tokens advances by exactly the emitted count, and the stream
    matches plain greedy."""
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 200, size=24).tolist()
    ref = _engine().generate([prompt], max_new_tokens=10)
    got = _engine().generate([prompt], max_new_tokens=10,
                             speculative="prompt_lookup",
                             num_draft_tokens=3, draft_ngram=1,
                             fused_decode_window=4)
    assert got == ref

    # direct engine-level check of the bookkeeping after one fused call
    eng = _engine()
    uid = 7
    eng.put([uid], [prompt[:-1]])
    seq = eng._state_manager.get_sequence(uid)
    seen0 = seq.seen_tokens
    toks, drafted, accepted = eng.fused_spec_decode_steps(
        [uid], [list(prompt)], 2, num_draft_tokens=3, draft_ngram=1)
    emitted = toks[0]
    assert len(emitted) >= 2                       # >= 1 token per window
    assert seq.seen_tokens == seen0 + len(emitted)
    assert seq.in_flight_tokens == 0
    assert accepted[0] == len(emitted) - 2
    assert drafted[0] >= accepted[0] >= 0


def test_scheduler_fused_spec_parity_and_stats():
    """The serving scheduler's fused speculative wave produces the same
    greedy stream as its per-token tick, and the accept-rate counters
    surface per-request (handle.stats) and aggregated (scheduler stats →
    /health payload)."""
    rng = np.random.default_rng(0)
    prompt = _repetitive_prompt(rng)

    def run(window):
        sched = ServingScheduler(_engine(), fused_decode_window=window)
        h = sched.submit(prompt, max_new_tokens=18,
                         speculative="prompt_lookup", num_draft_tokens=4,
                         draft_ngram=2)
        while not h.finished:
            sched.step()
        return h.result(), h.stats, sched.stats

    out1, st1, agg1 = run(1)
    out8, st8, agg8 = run(8)
    assert out1 == out8
    ref = _engine().generate([prompt], max_new_tokens=18)[0]
    assert out8 == ref
    for st, agg in ((st1, agg1), (st8, agg8)):
        assert st["drafted"] > 0 and st["accepted"] > 0
        assert agg["spec_drafted"] == st["drafted"]
        assert agg["spec_accepted"] == st["accepted"]
        assert agg["spec_accept_rate"] == pytest.approx(
            st["accepted"] / st["drafted"], abs=1e-3)


def test_fused_spec_gate_off_keeps_per_token_path():
    """fused_speculative_decode=False: no fused spec dispatch ever runs,
    outputs unchanged (the per-token oracle path serves everything)."""
    rng = np.random.default_rng(0)
    prompt = _repetitive_prompt(rng)
    eng = _engine(sampling=SamplingConfig(fused_speculative_decode=False))
    called = {"spec": 0}
    orig = eng.fused_spec_decode_steps
    eng.fused_spec_decode_steps = lambda *a, **k: called.__setitem__(
        "spec", called["spec"] + 1) or orig(*a, **k)
    out = eng.generate([prompt], max_new_tokens=12,
                       speculative="prompt_lookup", num_draft_tokens=4,
                       fused_decode_window=8)
    assert called["spec"] == 0
    ref = _engine().generate([prompt], max_new_tokens=12)
    assert out == ref


def test_prompt_lookup_draft_window_and_cache():
    """The bounded host scan with a cached last-match position returns the
    same drafts as the unbounded scan whenever the match lies inside the
    window, and never proposes from beyond it."""
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    draft = InferenceEngineV2.prompt_lookup_draft
    hist = [1, 2, 3, 9, 9, 1, 2, 3]
    full = draft(hist, draft_ngram=2, max_tokens=3)
    assert full == draft(hist, draft_ngram=2, max_tokens=3,
                         match_window=len(hist))
    # match outside the window -> no draft
    assert draft(hist, draft_ngram=2, max_tokens=3, match_window=3) == []
    # the cache floor reuses the last hit without changing results
    cache = {}
    rng = np.random.default_rng(5)
    seq = (rng.integers(0, 8, size=6).tolist() * 8)[:40]
    for t in range(20, 40):
        ref = draft(seq[:t], draft_ngram=2, max_tokens=4)
        got = draft(seq[:t], draft_ngram=2, max_tokens=4,
                    match_window=len(seq), match_cache=cache)
        assert got == ref, t
