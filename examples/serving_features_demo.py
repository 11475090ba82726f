"""Serving-extension tour: one script through every beyond-reference
feature of the v2 ragged engine.

- int8 KV cache           (half KV HBM per token, in-kernel dequant)
- automatic prefix cache  (shared system prompts prefill once)
- speculative decoding    (prompt-lookup drafts, greedy-exact)
- parallel sampling       (N samples share the prompt KV)
- score()                 (teacher-forced per-token log-probs)

Run (host CPU):
  JAX_PLATFORMS=cpu PYTHONPATH=/root/repo \
      python examples/serving_features_demo.py
On TPU, drop the env overrides.
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def main():
    import jax.numpy as jnp
    from deepspeed_tpu.inference.v2.engine_v2 import build_llama_engine
    from deepspeed_tpu.inference.v2.config_v2 import RaggedInferenceEngineConfig
    from deepspeed_tpu.models import LlamaConfig

    eng = build_llama_engine(
        LlamaConfig.tiny(num_key_value_heads=4), seed=0, dtype=jnp.float32,
        engine_config=RaggedInferenceEngineConfig(
            num_kv_blocks=256, enable_prefix_caching=True),
        kv_block_size=16, kv_cache_dtype="int8")
    kv = eng._state_manager.kv_cache
    print(f"int8 KV cache: {kv.cache[0].dtype} data + {kv.cache[1].dtype} "
          f"scales, {kv.per_token_bytes} B/token")

    rng = np.random.default_rng(0)
    system = (rng.integers(0, 64, size=8).tolist() * 12)[:80]

    # warm every program class the timed sections hit with a THROWAWAY
    # system prompt (same lengths, different content), so the printed
    # deltas measure the FEATURES, not one-time jit compiles
    other = (rng.integers(64, 128, size=8).tolist() * 12)[:80]
    eng.generate([other + [3, 7]], max_new_tokens=8)      # full prefill
    eng.generate([other + [9, 1]], max_new_tokens=8)      # adopted prefill
    eng.generate([other + [3, 7]], max_new_tokens=16,     # drafted decode
                 speculative="prompt_lookup", num_draft_tokens=6)

    t0 = time.time()
    eng.generate([system + [3, 7]], max_new_tokens=8)
    cold = time.time() - t0
    t0 = time.time()
    eng.generate([system + [9, 1]], max_new_tokens=8)
    warm = time.time() - t0
    pc = eng._state_manager.prefix_cache
    print(f"prefix cache: {len(pc)} cached blocks; request 2 reused the "
          f"system prompt ({cold:.2f}s -> {warm:.2f}s)")

    t0 = time.time()
    plain = eng.generate([system + [3, 7]], max_new_tokens=16)
    t_plain = time.time() - t0
    t0 = time.time()
    spec = eng.generate([system + [3, 7]], max_new_tokens=16,
                        speculative="prompt_lookup", num_draft_tokens=6)
    t_spec = time.time() - t0
    assert spec == plain, "speculative must be greedy-exact"
    print(f"speculative decode: greedy-exact, {t_plain:.2f}s plain vs "
          f"{t_spec:.2f}s drafted for 16 tokens")

    samples = eng.generate([system + [5]], max_new_tokens=6, temperature=0.9,
                           num_return_sequences=3, seed=7)
    print(f"parallel sampling: 3 samples sharing one prompt prefill -> "
          f"{samples}")

    lp = eng.score([999], [system[:33]])[0]
    print(f"score(): mean teacher-forced logprob over the prompt = "
          f"{float(np.mean(lp)):.3f}")
    print("SERVING FEATURE TOUR OK")


if __name__ == "__main__":
    main()
