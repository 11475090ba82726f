#!/usr/bin/env python3
"""Bring-up proof: the serving daemon and the trainer on one TPU chip.

    python chip_smoke.py               # one chip: kernels, serving, training
    python chip_smoke.py --four-chips  # four chips: ZeRO-3 against data parallel

Drives the system's two main paths through the entry points a user calls, at
the widths of Mistral-7B-v0.1 (its public ``config.json`` through
``MistralPolicy.config_from_hf``), weights from a seed, depth cut to what one
16 GB chip holds and printed. Exits non-zero when JAX finds no TPU, when a
phase raises and when an assertion fails; nothing here catches an error to
carry on. The last line of standard output is one JSON object naming the
device; everything else is on earlier lines. Every time and rate printed is
"observed, not a benchmark": one cold run, compilation in the way.

One process holds a chip at a time, so this parent never imports JAX: it runs
each phase as a child (``--phase NAME``) in turn, which also hands each phase
an empty device. The phases are plain functions of a model config and sizes;
``tests/unit/test_chip_smoke.py`` runs the same functions at toy size on a
CPU, and checks that this script refuses one.
"""

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time

# https://huggingface.co/mistralai/Mistral-7B-v0.1/blob/main/config.json
MISTRAL_7B_V01 = {
    "architectures": ["MistralForCausalLM"],
    "hidden_size": 4096,
    "intermediate_size": 14336,
    "num_hidden_layers": 32,
    "num_attention_heads": 32,
    "num_key_value_heads": 8,
    "max_position_embeddings": 32768,
    "rms_norm_eps": 1e-05,
    "rope_theta": 10000.0,
    "sliding_window": 4096,
    "tie_word_embeddings": False,
    "vocab_size": 32000,
}

_DEVICE_TAG = "CHIP_SMOKE_DEVICE "
SEED = 0


def log(msg: str) -> None:
    print(msg, flush=True)


def mistral_config():
    from deepspeed_tpu.module_inject.replace_policy import MistralPolicy
    return MistralPolicy().config_from_hf(MISTRAL_7B_V01)


def param_count(cfg, depth: int) -> int:
    """Parameters at ``depth`` layers: layers, the embedding, the untied
    head and the final norm."""
    embed = cfg.vocab_size * cfg.hidden_size
    head = 0 if cfg.tie_word_embeddings else embed
    return depth * cfg.per_layer_elements() + embed + head + cfg.hidden_size


def widths(cfg) -> str:
    return (f"hidden {cfg.hidden_size} / {cfg.num_attention_heads}·"
            f"{cfg.num_key_value_heads}×{cfg.head_dim_} / FFN "
            f"{cfg.intermediate_size} / vocab {cfg.vocab_size} / window "
            f"{cfg.sliding_window}")


def rel_err(got, want) -> float:
    """Largest absolute difference over the largest reference magnitude."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


# --------------------------------------------------------------------------
# device
# --------------------------------------------------------------------------

def require_tpu() -> dict:
    """The device as JAX reports it; exits when it is not a TPU."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def device_line(device: dict) -> int:
    """Prints the device line; returns the device's ``bytes_limit``."""
    import jax
    import jaxlib
    from importlib.metadata import version
    from deepspeed_tpu.runtime.compiler import configure_compile_cache
    stats = jax.devices()[0].memory_stats()
    log(f"device: platform={device['platform']} kind={device['kind']!r} "
        f"count={device['count']} bytes_limit={stats['bytes_limit']} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={version('libtpu')} "
        f"compile_cache={configure_compile_cache()}")
    return int(stats["bytes_limit"])


class PersistentCacheCounter:
    """Hits and misses of JAX's persistent compilation cache, from its own
    monitoring events: a second run in the same directory reports hits."""

    def __init__(self):
        import jax.monitoring
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name, **kw):
        if name.endswith("/compilation_cache/cache_hits"):
            self.hits += 1
        elif name.endswith("/compilation_cache/cache_misses"):
            self.misses += 1

    def line(self) -> str:
        return (f"persistent compile cache: {self.hits} hits, "
                f"{self.misses} misses")


def peak_bytes() -> int:
    import jax
    stats = jax.devices()[0].memory_stats()
    return int(stats["peak_bytes_in_use"]) if stats else 0


# --------------------------------------------------------------------------
# kernels (chip only: compiled, never interpreted)
# --------------------------------------------------------------------------

def _compiled(fn, *args):
    """Compile ``fn`` for the attached chip and require a Mosaic kernel in
    the program: an XLA fallback has no ``tpu_custom_call``."""
    import jax
    exe = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in exe.as_text(), "no Pallas kernel compiled"
    return exe


def kernels_phase(cfg, *, page: int = 64, prefill_n: int = 512,
                  flash_seq: int = 2048, norm_rows: int = 1024) -> None:
    import functools
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.ops.attention import _xla_attention, flash_attention
    from deepspeed_tpu.ops.normalization import rms_norm
    from deepspeed_tpu.ops.paged_attention import (paged_attention,
                                                   paged_attention_reference)

    H, KV, D = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim_
    window = cfg.sliding_window
    rng = np.random.default_rng(SEED)

    def normal(shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32),
                           jnp.bfloat16)

    # paged attention: two sequences, one past the window, one short.
    # Tolerance 4e-2 of the largest reference value: kernel and reference
    # read the same bf16 cache, the kernel rounds the probabilities to bf16
    # for the second matmul and the result to bf16 (2^-8 each), the
    # reference works in fp32 throughout.
    n_pages = 192
    cache = normal((2, n_pages * page, KV * D))
    table = jnp.asarray(rng.permutation(n_pages).reshape(2, n_pages // 2),
                        jnp.int32)
    for n_new, seen in ((1, (5003, 311)), (prefill_n, (4600, 0))):
        q = normal((2, n_new, H, D))
        seen_a = jnp.asarray(seen, jnp.int32)
        lens = seen_a + n_new
        kern = functools.partial(paged_attention, page_size=page,
                                 window=window)
        exe = _compiled(kern, q, cache, 0, table, seen_a, lens)
        got = exe(q, cache, jnp.int32(0), table, seen_a, lens)
        want = paged_attention_reference(q, cache, 0, table, seen_a, lens,
                                         page_size=page, window=window)
        err = rel_err(got, want)
        log(f"kernel paged_attention N={n_new} window={window} seen={seen}: "
            f"rel err {err:.2e} (tolerance 4e-2), tpu_custom_call present")
        assert np.isfinite(np.asarray(got, np.float32)).all()
        assert err < 4e-2, err

    # flash attention forward + backward against the XLA attention, under a
    # fixed random cotangent so the gradients are O(1). Tolerance 3e-2 of
    # the largest reference value: both sides compute in bf16 with fp32
    # accumulation and differ in where they round (scores, probabilities,
    # dO), a handful of 2^-8 roundings.
    q = normal((1, flash_seq, H, D))
    k = normal((1, flash_seq, KV, D))
    v = normal((1, flash_seq, KV, D))
    cot = normal((1, flash_seq, H, D)).astype(jnp.float32)
    scale = 1.0 / float(np.sqrt(D))

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=window,
                              force_pallas=True)
        return jnp.sum(out.astype(jnp.float32) * cot)

    def loss_xla(q, k, v):
        out = _xla_attention(q, k, v, scale, True, window)
        return jnp.sum(out.astype(jnp.float32) * cot)

    exe = _compiled(jax.value_and_grad(loss_flash, argnums=(0, 1, 2)),
                    q, k, v)
    l_f, g_f = exe(q, k, v)
    l_x, g_x = jax.jit(jax.value_and_grad(loss_xla, argnums=(0, 1, 2)))(
        q, k, v)
    errs = [rel_err(a, b) for a, b in zip(g_f, g_x)]
    log(f"kernel flash_attention fwd+bwd [1, {flash_seq}, {H}/{KV}, {D}] "
        f"window={window}: loss {float(l_f):.3f} vs {float(l_x):.3f}, grad "
        f"rel err dq {errs[0]:.2e} dk {errs[1]:.2e} dv {errs[2]:.2e} "
        f"(tolerance 3e-2), tpu_custom_call present")
    assert abs(float(l_f) - float(l_x)) < 3e-2 * max(abs(float(l_x)), 1.0)
    assert max(errs) < 3e-2, errs

    # rms_norm against its XLA branch: one bf16 rounding of the result,
    # tolerance 1e-2 of the largest value.
    x = normal((norm_rows, cfg.hidden_size))
    w = normal((cfg.hidden_size, ))
    exe = _compiled(functools.partial(rms_norm, eps=cfg.rms_norm_eps,
                                      force_pallas=True), x, w)
    err = rel_err(exe(x, w), rms_norm(x, w, eps=cfg.rms_norm_eps,
                                      force_pallas=False))
    log(f"kernel rms_norm [{norm_rows}, {cfg.hidden_size}]: rel err "
        f"{err:.2e} (tolerance 1e-2), tpu_custom_call present")
    assert err < 1e-2, err


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

def serving_depth(cfg, bytes_limit: int) -> int:
    """The greatest depth <= the published one whose bf16 weights leave at
    least a third of device memory for the KV pool."""
    depth = cfg.num_hidden_layers
    while depth > 1 and 2 * param_count(cfg, depth) > 2 * bytes_limit // 3:
        depth -= 1
    return depth


def _post_generate(port: int, prompt, new_tokens: int, stream: bool) -> dict:
    """One ``POST /generate``; returns status, tokens, uid and wall times
    on this client's clock."""
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=900)
    body = {"prompt": [int(t) for t in prompt], "max_new_tokens": new_tokens}
    if stream:
        body["stream"] = True
    t0 = time.monotonic()
    conn.request("POST", "/generate", json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    out = {"status": resp.status, "prompt_len": len(prompt), "stream": stream}
    if stream:
        out["uid"] = int(resp.getheader("X-DS-Request-Id"))
        tokens, t_first = [], None
        for line in resp:  # http.client undoes the chunking
            if line.strip():
                tokens.append(json.loads(line)["token"])
                t_first = t_first or time.monotonic()
        out["tokens"] = tokens
        out["client_ttft_s"] = t_first - t0
    else:
        reply = json.loads(resp.read())
        out["tokens"] = reply.get("tokens")
        out["uid"] = reply.get("uid")
        out["error"] = reply.get("error")
    out["client_total_s"] = time.monotonic() - t0
    conn.close()
    return out


def _get_json(port: int, path: str):
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = json.loads(resp.read())
    conn.close()
    return resp.status, body


def serving_phase(cfg, *, dtype, prompt_lens, new_tokens: int,
                  in_flight: int, logit_prompts, logit_tol: float) -> dict:
    """What ``bin/ds_serve`` does — ``build_llama_engine`` →
    ``ServingScheduler(engine).start()`` → ``create_http_server`` — then
    traffic over HTTP, then the engine's own ``put`` against the flax model
    on the same parameters. Every default stays a default: attention
    backend, KV sizing from the device's memory, fused decode window.
    Returns what was observed; what only a chip can show is asserted by
    :func:`check_serving_on_chip`."""
    import threading
    from concurrent.futures import ThreadPoolExecutor
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.inference.v2 import ServingScheduler
    from deepspeed_tpu.inference.v2.engine_v2 import build_llama_engine
    from deepspeed_tpu.inference.v2.model import _serving_compile_watch
    from deepspeed_tpu.inference.v2.server import create_http_server
    from deepspeed_tpu.models.llama import LlamaForCausalLM, init_llama

    t0 = time.monotonic()
    _, params = init_llama(cfg, seed=SEED, dtype=dtype)
    engine = build_llama_engine(cfg, params=params, dtype=dtype)
    del params
    model = engine.model()
    n_blocks = engine._state_manager.kv_cache.num_blocks
    log(f"serving: depth {cfg.num_hidden_layers} "
        f"({param_count(cfg, cfg.num_hidden_layers) / 1e9:.2f}B parameters), "
        f"{widths(cfg)}; attn_backend={model.attn_backend} "
        f"kv_blocks={n_blocks} x {model.kv_block_size} tokens; engine built "
        f"in {time.monotonic() - t0:.1f} s (observed)")

    sched = ServingScheduler(engine).start()
    httpd = create_http_server(sched, "127.0.0.1", 0)
    port = httpd.server_address[1]
    threading.Thread(target=httpd.serve_forever, daemon=True).start()

    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, size=n) for n in prompt_lens]
    stream_at = min(2, len(prompts) - 1)  # one request streams
    with ThreadPoolExecutor(in_flight) as pool:
        replies = list(pool.map(
            lambda ip: _post_generate(port, ip[1], new_tokens,
                                      stream=ip[0] == stream_at),
            enumerate(prompts)))
    for r in replies:
        assert r["status"] == 200, r
        assert len(r["tokens"]) == new_tokens, r
        assert all(0 <= t < cfg.vocab_size for t in r["tokens"]), r
        # the server's own spans: the first token leaves with the last
        # prefill chunk, the finish event closes the request
        status, tl = _get_json(port, f"/requests/{r['uid']}/trace")
        assert status == 200, tl
        ttft = max(s["t1"] for s in tl["spans"]
                   if s["name"].startswith("prefill"))
        done = next(e["t"] for e in tl["events"] if e["name"] == "finish")
        log(f"  request prompt={r['prompt_len']} stream={r['stream']}: "
            f"status 200, {len(r['tokens'])} tokens, TTFT {ttft:.3f} s, "
            f"decode {(new_tokens - 1) / max(done - ttft, 1e-9):.1f} tok/s, "
            f"client total {r['client_total_s']:.3f} s"
            + (f", client TTFT {r['client_ttft_s']:.3f} s"
               if r["stream"] else "")
            + " (observed, not a benchmark)")
    status, health = _get_json(port, "/health")
    assert status == 200 and health["status"] == "ok", health
    log(f"  GET /health: status={health['status']} "
        f"ttft_mean_s={health.get('ttft_mean_s')} "
        f"decode_tok_s_mean={health.get('decode_tok_s_mean')} "
        f"(observed, not a benchmark)")
    httpd.shutdown()
    httpd.server_close()
    sched.stop(drain=True)

    # the engine's put against the flax model on the same parameters
    flax_model = LlamaForCausalLM(cfg)
    apply = jax.jit(lambda p, ids: flax_model.apply({"params": p}, ids))
    errs = {}
    for j, n in enumerate(logit_prompts):
        prompt = prompts[list(prompt_lens).index(n)]
        uid = 1_000_000 + j
        got = np.asarray(engine.put([uid], [prompt])[0], np.float32)
        engine.flush(uid)
        want = np.asarray(apply(model.params, jnp.asarray(prompt)[None])
                          [0, -1], np.float32)
        assert got.shape == (cfg.vocab_size, ) and np.isfinite(got).all()
        errs[n] = rel_err(got, want)
        log(f"  put({n} tokens) last-position logits vs "
            f"LlamaForCausalLM.apply: rel err {errs[n]:.2e} "
            f"(tolerance {logit_tol:g})")
        assert errs[n] < logit_tol, errs

    keys = [k for k in model._fwd_cache if isinstance(k, tuple)]
    fused_k = sorted({k[3] for k in keys
                      if k[0] in ("fused", "fused_sampled")})
    n_buckets = sorted({k[0][3] for k in keys if isinstance(k[0], tuple)})
    watch = _serving_compile_watch()
    compiles = [watch.counts(k) for k in list(watch._per_key)]
    kv = engine._state_manager.kv_cache.cache
    facts = {
        "attn_backend": model.attn_backend,
        "kv_blocks": n_blocks,
        "weight_bytes": sum(x.nbytes for x in
                            jax.tree_util.tree_leaves(model.params)),
        "pool_bytes": sum(x.nbytes for x in jax.tree_util.tree_leaves(kv)),
        "fused_steps": fused_k,
        "n_buckets": n_buckets,
        "programs": len(keys),
        "compiles": int(sum(c["compiles"] for c in compiles)),
        "compile_seconds": float(sum(c["compile_seconds"] for c in compiles)),
        "logit_rel_err": errs,
        "peak_bytes_in_use": peak_bytes(),
    }
    log(f"  serving programs: {facts['programs']} compiled in "
        f"{facts['compile_seconds']:.1f} s of trace+compile "
        f"({facts['compiles']} compiles, CompileWatch; observed); new-token "
        f"buckets N={n_buckets}; fused decode windows K={fused_k}; "
        f"peak_bytes_in_use={facts['peak_bytes_in_use']}")
    return facts


def check_serving_on_chip(facts: dict, bytes_limit: int) -> None:
    """What only the chip can show. The CPU rehearsal's facts fail here."""
    assert facts["attn_backend"] == "paged", facts
    # sized from the device's free memory, not the 64-block floor a CPU
    # gets: at least a quarter of the device
    assert facts["kv_blocks"] > 64 and \
        facts["pool_bytes"] > bytes_limit // 4, facts
    assert any(k > 1 for k in facts["fused_steps"]), facts
    assert {256, 512} <= set(facts["n_buckets"]), facts
    # the pool is written in place under donation: a copy of it would put
    # the peak half a pool above weights + pool
    assert 0 < facts["peak_bytes_in_use"] < (
        facts["weight_bytes"] + 1.5 * facts["pool_bytes"]), facts


# --------------------------------------------------------------------------
# training
# --------------------------------------------------------------------------

# fp32 master + two Adam moments + fp32 gradient + the bf16 compute copy
_TRAIN_BYTES_PER_PARAM = 4 + 8 + 4 + 2


def training_depth(cfg, bytes_limit: int) -> int:
    """The greatest depth >= 1 whose optimizer state fits 85% of device
    memory; activations and temporaries take the rest."""
    depth = 1
    while (depth < cfg.num_hidden_layers and _TRAIN_BYTES_PER_PARAM
           * param_count(cfg, depth + 1) <= 0.85 * bytes_limit):
        depth += 1
    return depth


def _host_init(cfg):
    """Seeded fp32 parameters that stay in host memory: the engine places
    each shard where it belongs, and no chip ever holds the whole tree."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.llama import init_llama
    with jax.default_device(jax.devices("cpu")[0]):
        # dtype given: the jitted init, in which the forward pass flax runs
        # to shape the parameters is dead code — eagerly it would call the
        # TPU's attention kernel on host arrays
        return init_llama(cfg, seed=SEED, dtype=jnp.float32)


def _train(cfg, ds_config: dict, batch, steps: int, chips=None) -> dict:
    """``deepspeed_tpu.initialize`` then ``steps`` ``train_batch`` calls on
    one repeated batch. Returns losses, step seconds, the fused step's
    program text and cache size, and every device's memory stats while the
    state is still resident. ``chips``: the engine adopts a mesh over the
    first that many devices (its default mesh takes every device the host
    has and spreads the batch over them)."""
    import gc
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.comm import reset_mesh_context
    from deepspeed_tpu.comm.mesh import MeshContext, set_mesh_context

    reset_mesh_context()
    if chips is not None:
        set_mesh_context(MeshContext.create(devices=jax.devices()[:chips]))
    model, params = _host_init(cfg)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=ds_config)
    del params
    losses, seconds = [], []
    for _ in range(steps):
        t0 = time.monotonic()
        losses.append(float(engine.train_batch(iter([(batch, batch)]))))
        jax.block_until_ready(engine.params)
        seconds.append(time.monotonic() - t0)
    step_fn = engine._train_step_fused
    args = jax.device_put((batch, batch),
                          engine.zero_plan.batch_sharding((batch, batch)))
    lowered = step_fn.lower(engine.params, engine.opt_state,
                            engine.scale_state, args, {}, ())
    out = {
        "losses": losses,
        "step_seconds": seconds,
        "step_programs": int(step_fn._cache_size()),
        "kernels_in_step": lowered.as_text().count("tpu_custom_call"),
        "mesh": dict(engine.mesh_ctx.mesh.shape),
        "memory": [d.memory_stats() for d in jax.devices()],
    }
    del engine, step_fn, lowered, args
    gc.collect()
    reset_mesh_context()
    return out


def _train_config(global_batch: int, **extra) -> dict:
    return {"train_batch_size": global_batch,
            "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
            "bf16": {"enabled": True},
            "steps_per_print": 0, **extra}


def _seeded_batch(cfg, rows: int, seq: int):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(SEED)
    return jnp.asarray(rng.integers(0, cfg.vocab_size, size=(rows, seq)),
                       jnp.int32)


def training_phase(cfg, *, seq: int, batch: int = 1, steps: int = 4) -> dict:
    """The trainer through ``deepspeed_tpu.initialize`` and
    ``engine.train_batch``: bf16, AdamW, chunked cross-entropy."""
    import numpy as np
    log(f"training: depth {cfg.num_hidden_layers} "
        f"({param_count(cfg, cfg.num_hidden_layers) / 1e9:.2f}B parameters), "
        f"{widths(cfg)}; bf16, AdamW, ce_chunk_size={cfg.ce_chunk_size}, "
        f"batch {batch} x {seq}")
    assert cfg.ce_chunk_size, "the trainer's chunked cross-entropy is on"
    facts = _train(cfg, _train_config(batch), _seeded_batch(cfg, batch, seq),
                   steps, chips=1)
    losses, secs = facts["losses"], facts["step_seconds"]
    log(f"  losses {[round(x, 4) for x in losses]}")
    assert np.isfinite(losses).all(), losses
    assert all(b < a for a, b in zip(losses, losses[1:])), \
        f"loss not strictly decreasing: {losses}"
    # one program for every step: nothing recompiled after the first
    assert facts["step_programs"] == 1, facts["step_programs"]
    steady = sorted(secs[1:])[len(secs[1:]) // 2]
    facts["peak_bytes_in_use"] = peak_bytes()
    log(f"  step seconds {[round(s, 3) for s in secs]} (observed, not a "
        f"benchmark; the first holds the compile, about "
        f"{secs[0] - steady:.1f} s); {facts['kernels_in_step']} Pallas "
        f"kernels in the fused step; 1 step program after {steps} steps; "
        f"peak_bytes_in_use={facts['peak_bytes_in_use']}")
    return facts


def check_training_on_chip(facts: dict, depth: int) -> None:
    # flash attention is two kernels a layer at least: the forward, and the
    # fused backward (dq, dk and dv from one call)
    assert facts["kernels_in_step"] >= 2 * depth, facts["kernels_in_step"]
    assert facts["peak_bytes_in_use"] > 0, facts


# --------------------------------------------------------------------------
# four chips: ZeRO-3 against plain data parallel
# --------------------------------------------------------------------------

def four_chip_phase(cfg, *, seq: int, deep_layers: int, steps: int = 3,
                    loss_rtol: float = 1e-2) -> dict:
    """ZeRO-3 over ``fsdp: 4`` against stage 0 over ``data: 4``, same seed
    and global batch; then ZeRO-3 alone at ``deep_layers``, whose state is
    more than one chip holds. Tolerance ``loss_rtol``: the arms do the same
    arithmetic in bf16 and differ in the order the gradient is reduced
    (reduce-scatter against all-reduce), which moves a loss of about
    ln(vocab) in its third or fourth digit."""
    import numpy as np
    batch = _seeded_batch(cfg, 4, seq)
    arms = {}
    for name, extra in (
            ("zero3", {"zero_optimization": {"stage": 3},
                       "mesh": {"fsdp": 4}}),
            ("data_parallel", {"zero_optimization": {"stage": 0},
                               "mesh": {"data": 4}})):
        arms[name] = _train(cfg, _train_config(4, **extra), batch, steps)
        used = [m["bytes_in_use"] if m else None for m in arms[name]["memory"]]
        log(f"four chips, {name}: depth {cfg.num_hidden_layers} mesh "
            f"{arms[name]['mesh']}, losses "
            f"{[round(x, 4) for x in arms[name]['losses']]}, per-chip "
            f"bytes_in_use {used}, step seconds "
            f"{[round(s, 2) for s in arms[name]['step_seconds']]} (observed, "
            f"not a benchmark)")
    z3, dp = arms["zero3"]["losses"], arms["data_parallel"]["losses"]
    assert np.isfinite(z3).all() and np.isfinite(dp).all()
    np.testing.assert_allclose(z3, dp, rtol=loss_rtol)
    log(f"  ZeRO-3 and data-parallel losses agree within rtol {loss_rtol:g} "
        f"(largest relative difference "
        f"{max(abs(a - b) / abs(b) for a, b in zip(z3, dp)):.2e})")

    deep_cfg = dataclasses.replace(cfg, num_hidden_layers=deep_layers)
    n = param_count(deep_cfg, deep_layers)
    deep = _train(deep_cfg, _train_config(4, zero_optimization={"stage": 3},
                                          mesh={"fsdp": 4}), batch, steps)
    used = [m["bytes_in_use"] if m else None for m in deep["memory"]]
    log(f"four chips, zero3 at depth {deep_layers}: {n / 1e9:.2f}B "
        f"parameters, {_TRAIN_BYTES_PER_PARAM * n / 1e9:.1f} GB of state, "
        f"losses {[round(x, 4) for x in deep['losses']]}, per-chip "
        f"bytes_in_use {used}, peak "
        f"{[m['peak_bytes_in_use'] if m else None for m in deep['memory']]}")
    assert np.isfinite(deep["losses"]).all(), deep["losses"]
    assert all(b < a for a, b in zip(deep["losses"], deep["losses"][1:]))
    return {"zero3": arms["zero3"], "data_parallel": arms["data_parallel"],
            "deep": deep}


def check_four_chips_on_chip(facts: dict, deep_state_bytes: int,
                             bytes_limit: int) -> None:
    z3 = [m["bytes_in_use"] for m in facts["zero3"]["memory"]]
    dp = [m["bytes_in_use"] for m in facts["data_parallel"]["memory"]]
    assert len(z3) == 4 and all(b > 0 for b in z3 + dp), (z3, dp)
    # a quarter of the state against all of it: well under half
    assert all(a < 0.5 * b for a, b in zip(z3, dp)), (z3, dp)
    # the deep arm's state is more than one chip holds, and it is spread:
    # every chip keeps some, none as much as half
    assert deep_state_bytes > bytes_limit
    assert all(0 < m["bytes_in_use"] < 0.5 * deep_state_bytes
               for m in facts["deep"]["memory"])


# --------------------------------------------------------------------------
# children and parent
# --------------------------------------------------------------------------

def run_phase(name: str) -> None:
    """One child: owns the chip from here to its exit."""
    device = require_tpu()
    import jax.numpy as jnp
    cache = PersistentCacheCounter()
    bytes_limit = device_line(device)
    log(_DEVICE_TAG + json.dumps(device))
    cfg = mistral_config()
    if name == "kernels":
        log(f"kernels: {widths(cfg)}, bf16, page 64")
        kernels_phase(cfg)
    elif name == "serving":
        depth = serving_depth(cfg, bytes_limit)
        facts = serving_phase(
            dataclasses.replace(cfg, num_hidden_layers=depth),
            dtype=jnp.bfloat16,
            prompt_lens=(24, 200, 300, 1500, 3000, 5000), new_tokens=64,
            in_flight=3, logit_prompts=(200, 300),
            # bf16 weights and activations on both sides, fp32 logits: the
            # ragged forward and the flax module round at different places
            # (paged kernel against XLA softmax, fused against separate
            # matmuls), a few 2^-8 roundings per layer that the residual
            # stream carries to the head
            logit_tol=5e-2)
        check_serving_on_chip(facts, bytes_limit)
    elif name == "training":
        depth = training_depth(cfg, bytes_limit)
        facts = training_phase(
            dataclasses.replace(cfg, num_hidden_layers=depth,
                                ce_chunk_size=8000), seq=2048)
        check_training_on_chip(facts, depth)
    elif name == "four_chips":
        assert device["count"] == 4, f"--four-chips needs 4 chips: {device}"
        deep = 4
        facts = four_chip_phase(
            dataclasses.replace(cfg, num_hidden_layers=1, ce_chunk_size=8000),
            seq=2048, deep_layers=deep)
        check_four_chips_on_chip(
            facts, _TRAIN_BYTES_PER_PARAM * param_count(cfg, deep),
            bytes_limit)
    else:
        sys.exit(f"unknown phase {name!r}")
    log(f"{name}: {cache.line()}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the four-chip path and what it is compared "
                         "with (needs four chips)")
    ap.add_argument("--phase", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.phase:
        run_phase(args.phase)
        return
    phases = (["four_chips"] if args.four_chips
              else ["kernels", "serving", "training"])
    device = None
    for name in phases:
        t0 = time.monotonic()
        proc = subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--phase", name],
            stdout=subprocess.PIPE, text=True)
        try:
            for line in proc.stdout:
                if line.startswith(_DEVICE_TAG):
                    device = json.loads(line[len(_DEVICE_TAG):])
                else:
                    print(line, end="", flush=True)
            rc = proc.wait()
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        if rc != 0:
            sys.exit(f"chip_smoke: phase {name} failed (exit code {rc})")
        log(f"phase {name} passed in {time.monotonic() - t0:.1f} s "
            f"(observed)")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
