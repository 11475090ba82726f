"""ZeRO-Inference demo: llama decode with weights streaming from NVMe/DRAM.

Reference capability: ``blogs/deepspeed-gds/README.md:74`` — a model too big
for device memory decodes with its weights streaming NVMe→HBM per layer.
This drives `runtime/zero_infinity.ZeroInferenceEngine` with a real llama
stack (one `LlamaDecoderLayer` per streamed layer; embed/norm/head resident)
and journals decode tok/s + achieved weight-streaming GB/s.

Greedy decode recomputes the full prefix each token (no KV cache): every
decode step re-streams the whole model, which is exactly the
NVMe-bandwidth-bound regime ZeRO-Inference lives in — the measured GB/s is
the star, tok/s follows from it as (GB/s / model-GB) at batch 1.

Run (host CPU, reduced scale):
  JAX_PLATFORMS=cpu PYTHONPATH=/root/repo \
      python examples/zero_inference_demo.py --layers 8 --hidden 512 \
      --device nvme --tokens 8

On TPU, drop the env overrides and raise --hidden/--layers until the model
exceeds HBM — the point of the exercise.
"""

import argparse
import json
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--hidden", type=int, default=512)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=2048)
    ap.add_argument("--tokens", type=int, default=8)
    ap.add_argument("--prompt_len", type=int, default=16)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--device", choices=["cpu", "nvme"], default="nvme")
    ap.add_argument("--nvme_path", default="/tmp/ds_tpu_zero_inference")
    ap.add_argument("--prefetch", type=int, default=1)
    args = ap.parse_args()

    from deepspeed_tpu.models import LlamaConfig, init_llama
    from deepspeed_tpu.models.llama import LlamaDecoderLayer, precompute_rope
    from deepspeed_tpu.runtime.zero_infinity import ZeroInferenceEngine

    cfg = LlamaConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                      intermediate_size=int(args.hidden * 2.75),
                      num_hidden_layers=args.layers,
                      num_attention_heads=max(args.hidden // 64, 1),
                      num_key_value_heads=max(args.hidden // 64, 1),
                      max_position_embeddings=args.prompt_len + args.tokens + 1,
                      attn_impl="xla", dtype=jnp.bfloat16)
    model, params = init_llama(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
    mp = params["model"]

    # resident small pieces: embed, final norm, head
    embed_w = jnp.asarray(mp["embed_tokens"]["embedding"], jnp.bfloat16)
    norm_w = jnp.asarray(mp["norm"]["weight"], jnp.float32)
    head_w = jnp.asarray(mp["lm_head"]["kernel"], jnp.bfloat16)
    cos, sin = precompute_rope(cfg.head_dim_, cfg.max_position_embeddings,
                               cfg.rope_theta)

    layer_params = [mp[f"layers_{i}"] for i in range(cfg.num_hidden_layers)]

    def make_layer(i):
        mod = LlamaDecoderLayer(cfg, i)

        def fn(p, pack):
            x, positions, mask = pack
            y = mod.apply({"params": p}, x, cos, sin, positions, mask)
            return (y, positions, mask)
        return fn

    eng = ZeroInferenceEngine([make_layer(i) for i in range(cfg.num_hidden_layers)],
                              layer_params, device=args.device,
                              nvme_path=args.nvme_path,
                              prefetch=args.prefetch)

    @jax.jit
    def lm_head(x):
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        normed = (xf * jax.lax.rsqrt(var + cfg.rms_norm_eps) * norm_w)
        return normed.astype(jnp.bfloat16) @ head_w

    rng = np.random.default_rng(0)
    # FIXED-shape decode buffers: ids padded to prompt+tokens with a key
    # padding mask, cur_len a traced scalar — every decode step reuses the
    # same compiled per-layer programs (a growing sequence would retrace
    # all layers per token and the timing would measure XLA, not streaming)
    L = args.prompt_len + args.tokens
    ids_buf = np.zeros((args.batch, L), np.int32)
    ids_buf[:, :args.prompt_len] = rng.integers(
        0, cfg.vocab_size, size=(args.batch, args.prompt_len))
    ids_buf = jnp.asarray(ids_buf)
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32)[None],
                                 (args.batch, L))

    def decode_step(ids, cur_len):
        mask = (jnp.arange(L, dtype=jnp.int32)[None] < cur_len)
        mask = jnp.broadcast_to(mask, ids.shape)
        x = jnp.take(embed_w, ids, axis=0)
        x, _, _ = eng.streamed_apply((x, positions, mask))
        last = x[jnp.arange(args.batch), cur_len - 1]  # [B, H]
        logits = lm_head(last)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # warmup (compiles the per-layer programs for the ONE fixed shape)
    _ = jax.block_until_ready(decode_step(ids_buf, jnp.int32(args.prompt_len)))
    eng.bytes_streamed = 0

    t0 = time.time()
    out = ids_buf
    for t in range(args.tokens):
        cur = jnp.int32(args.prompt_len + t)
        nxt = decode_step(out, cur)
        out = jax.lax.dynamic_update_slice(
            out, nxt[:, None], (0, args.prompt_len + t))
    jax.block_until_ready(out)
    dt = time.time() - t0

    report = {
        "metric": "zero_inference_decode",
        "platform": jax.devices()[0].platform,
        "device_store": args.device,
        "model_mparams": round(n_params / 1e6, 1),
        "streamed_gb_total": round(eng.bytes_streamed / 1e9, 3),
        "achieved_stream_gbps": round(eng.bytes_streamed / 1e9 / dt, 3),
        "decode_tokens_per_sec": round(args.tokens * args.batch / dt, 3),
        "peak_streamed_param_mb": round(eng.peak_param_bytes / 1e6, 2),
        # NVMe prefetch stages HOST read buffers; only the DRAM store holds
        # (1 + prefetch) layers device-resident (see _LayerStreaming)
        "resident_layers": 1 if args.device == "nvme" else 1 + args.prefetch,
        "new_tokens": args.tokens * args.batch,
    }
    print(json.dumps(report), flush=True)


if __name__ == "__main__":
    main()
