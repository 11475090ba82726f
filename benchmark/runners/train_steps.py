"""Runner: optimizer steps back to back through ``deepspeed_tpu.initialize``.

Copied from ``chip_smoke._train``: seeded fp32 parameters made on the host,
the engine places each shard; bf16, AdamW, chunked cross-entropy; the mesh
and ZeRO stage come from the cell. Fresh seeded token ids every step from a
host iterator, no gradient accumulation. Every engine default stays a
default, so ``train_batch`` returns the loss as a host number each step.
"""

import time

import numpy as np

from benchmark import flops, traffic as gen
from benchmark.reference import mistral as reference
from benchmark.runners.serve_closed_loop import HF_KEYS

# The trainer computes in bf16 (fp32 master weights cast per step, fp32
# cross-entropy); the reference computes in float32 from the same fp32
# masters. Two points are compared, both on the first batch.
# At initialisation every decoder reads about ln(vocab) = 10.4, so the check
# has to be tight to mean anything: bf16 rounding (2^-8 a value, unbiased
# over 16,384 tokens) moved the loss by 2e-6 to 1e-5 of itself on the chip
# (four seeds, PR 23), and 1e-3 leaves that 100 times over while failing an
# 8-bit matmul path or a dropped term, which move the logits' spread and with
# it the loss by more. After one optimizer step on that batch the loss has
# left ln(vocab) (10.870 -> 9.898 on the chip, PR 23), so a wrong layer,
# window or rotary term shows at order 1e-1 there; the engine and the
# reference differed by 1.3e-5. The second loss must also be lower than the
# first: an optimizer or gradient collective that does not descend fails.
LOSS_RTOL = 1e-3


def run(*, cell, config, seed, seconds, trace, rehearse, t_start, device,
        compiles, out_dir, log) -> dict:
    import dataclasses
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.comm import reset_mesh_context
    from deepspeed_tpu.models.llama import init_llama
    from deepspeed_tpu.module_inject.replace_policy import MistralPolicy

    tr = cell["traffic"]
    rows, seq = int(tr["global_batch"]), int(tr["seq_len"])
    cfg = dataclasses.replace(
        MistralPolicy().config_from_hf({k: config[k] for k in HF_KEYS}),
        ce_chunk_size=int(config["ce_chunk_size"]))
    ds_config = {"train_batch_size": rows,
                 "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                 "bf16": {"enabled": True}, "steps_per_print": 0,
                 **config["ds_config"]}

    reset_mesh_context()
    t0 = time.monotonic()
    with jax.default_device(jax.devices("cpu")[0]):
        # the jitted init on the host: no chip ever holds the whole tree
        model, params = init_llama(cfg, seed=seed % (2**31 - 1),
                                   dtype=jnp.float32)
    t_init = time.monotonic() - t0
    t0 = time.monotonic()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=ds_config)
    del params
    jax.block_until_ready(engine.params)
    t_place = time.monotonic() - t0
    n_params = flops.param_count(config)
    log(f"training: depth {cfg.num_hidden_layers} ({n_params / 1e9:.2f}B "
        f"parameters), mesh {dict(engine.mesh_ctx.mesh.shape)}, batch {rows} x "
        f"{seq}; host init {t_init:.1f} s, initialize+place {t_place:.1f} s")

    batches = gen.token_batches(seed, rows, seq, cfg.vocab_size)

    def step() -> float:
        batch = jnp.asarray(next(batches))
        return float(engine.train_batch(iter([(batch, batch)])))

    # correctness: the reference's loss on the same parameters and the first
    # batch, before each of the first two steps donates them
    first = next(batches)
    ids = jax.device_put(jnp.asarray(first),
                         engine.zero_plan.batch_sharding((first, ))[0])
    want, got, t_check, t_steps = [], [], 0.0, []
    for _ in range(2):
        t0 = time.monotonic()
        want.append(float(reference.cross_entropy(engine.params, ids, config)))
        t_check += time.monotonic() - t0
        t0 = time.monotonic()
        got.append(float(engine.train_batch(iter([(ids, ids)]))))
        jax.block_until_ready(engine.params)
        t_steps.append(time.monotonic() - t0)
    losses = list(got)
    for _ in range(int(tr["warmup_steps"])):
        losses.append(step())
    jax.block_until_ready(engine.params)
    rel = [abs(g - w) / abs(w) for g, w in zip(got, want)]
    loss_ok = max(rel) <= LOSS_RTOL and got[1] < got[0]
    log(f"correctness: loss {got[0]:.5f} at initialisation and {got[1]:.5f} "
        f"after one step on the same batch, float32 reference {want[0]:.5f} "
        f"and {want[1]:.5f} (relative difference {rel[0]:.1e}, {rel[1]:.1e}; "
        f"limit {LOSS_RTOL:g}; must descend): "
        f"{'ok' if loss_ok else 'FAILED'}; first step {t_steps[0]:.1f} s")

    # ---- the measured window ----
    t_open = time.monotonic()
    setup = compiles.snapshot()
    step_s, n_trace = [], int(tr["trace_steps"])
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(out_dir, profiler_options=opts)
    while time.monotonic() - t_open < seconds:
        t0 = time.monotonic()
        losses.append(step())
        step_s.append(time.monotonic() - t0)
        if trace and len(step_s) == n_trace:
            jax.block_until_ready(engine.params)
            jax.profiler.stop_trace()
            trace = False
    jax.block_until_ready(engine.params)
    t_close = time.monotonic()
    if trace:
        jax.profiler.stop_trace()

    programs = int(engine._train_step_fused._cache_size())
    finite = bool(np.isfinite(losses).all())
    tokens = len(step_s) * rows * seq
    e2e = {"setup_s": t_open - t_start,
           "train_tok_s": tokens / (t_close - t_open)}
    notes = {"setup": setup, "host_init_s": t_init, "initialize_s": t_place,
             "check_s": t_check, "first_step_s": t_steps[0],
             "steps": len(step_s), "step_s_median": float(np.median(step_s)),
             "loss_first_two": got, "loss_reference": want,
             "loss_last": losses[-1], "step_programs": programs,
             "n_params": n_params,
             "bytes_in_use": [int((d.memory_stats() or {}).get("bytes_in_use", 0))
                              for d in jax.devices()[:cell["chips"]]]}
    correct = loss_ok and finite and programs == 1
    return {"correct": correct, "attempted": len(step_s),
            "failed": 0 if finite else 1, "end_to_end": e2e, "notes": notes, "setup": setup,
            "trace_steps": min(n_trace, len(step_s)), "tokens_per_step": rows * seq,
            "train_flops_per_token": flops.train_flops_per_token(config, seq),
            "chips": cell["chips"]}
