"""Runner: optimizer steps of an OLMoE decoder back to back through
``deepspeed_tpu.initialize``, on one chip.

The training runner's flow (``train_steps.py``) for a second architecture:
the configuration goes through ``OlmoePolicy.config_from_hf``, the plain
reference is ``reference/olmoe.py``, the FLOP count ``moe_cost.py``. Seeded
fp32 parameters made on the host and placed by the engine; bf16, AdamW,
chunked cross-entropy, the router's balance loss added by the engine. Fresh
seeded token ids every step from a host iterator, no gradient accumulation.
Every engine default stays a default, so ``train_batch`` returns the loss as
a host number each step.
"""

import time

import numpy as np

from benchmark import moe_cost, traffic as gen
from benchmark.reference import olmoe as reference

# (a) The loss, balance term included on both sides, at initialisation and
# after one optimizer step on the same batch, against the float32 reference
# on the same fp32 masters. As in the dense cell (``train_steps.py`` says
# why): bf16 rounding is unbiased over 16,384 tokens and moves a loss near
# ln(vocab) by 1e-5 of itself, so 1e-3 leaves that 100 times over while an
# 8-bit matmul or a dropped term moves it by more; the second loss must be
# lower than the first.
LOSS_RTOL = 1e-3
# (b) A loss near ln 50304 hardly sees the router, so the program's logits
# (bf16 compute, float32 out) on the last LOGIT_POSITIONS positions of the
# first sequence against the reference's: the relative L2 distance over the
# vocabulary, position by position. Routing is a discontinuity: where a
# token's 8th and 9th largest router logits lie closer than bf16's rounding
# of the router's input, the program rightly chooses another expert than the
# reference and that position's logits differ by tens of percent (2% to 9% of
# the positions on the chip, 16 seeds). So the reference also gives each
# position's routing margin (8th less 9th router logit), positions under
# ROUTER_TIE_MARGIN are left out, and EVERY other position must lie within
# LOGIT_RTOL. At the published widths (one sequence of 1,024 tokens, seeded
# weights, on a CPU, PR 26): positions that chose another expert had margins
# up to 0.017, so 0.04 leaves that twice over and keeps 6 positions in 10;
# bf16 compute then reads 1.0e-2 to 1.7e-2 at every kept position (on the
# chip, six seeds: worst kept position 1.32e-2 to 1.57e-2). Computed
# in the nearest precision below (the expert matrices rounded to 8 bits, fp8
# e4m3) every kept position reads 3.9e-2 or more: not correct. A renormalised
# top-8 reads 1.2e-1 or more at every position; one expert of 64 dropped,
# 1.7e-1 or more at each kept position routed to it.
LOGIT_POSITIONS = 256
LOGIT_RTOL = 3e-2
ROUTER_TIE_MARGIN = 0.04
# (c) The per-expert assignment counts of the first batch against the
# reference's. Half the L1 distance between the two count vectors is the net
# number of assignments that moved between experts. Near-ties move some 4% of
# the tokens' eighth choice (above), 0.5% of the assignments, in both
# directions, so the net over 64 experts is far smaller: the chip read 148
# to 192 of 131,072 (1.1e-3 to 1.5e-3) on 14 seeds. The limit is 0.5% of all
# assignments, which a transposed, unnormalised or misplaced router passes
# by an order of magnitude. Every token must keep its top_k experts: both
# vectors sum to tokens * top_k * layers, nothing dropped.
COUNT_MOVED_SHARE = 5e-3


def run(*, cell, config, seed, seconds, trace, rehearse, t_start, device,
        compiles, out_dir, log) -> dict:
    import dataclasses
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.comm import reset_mesh_context
    from deepspeed_tpu.comm.mesh import MeshContext, set_mesh_context
    from deepspeed_tpu.models.llama import init_llama
    from deepspeed_tpu.module_inject.replace_policy import OlmoePolicy
    from deepspeed_tpu.observability import get_registry

    tr = cell["traffic"]
    rows, seq = int(tr["global_batch"]), int(tr["seq_len"])
    cfg = dataclasses.replace(OlmoePolicy().config_from_hf(config),
                              ce_chunk_size=int(config["ce_chunk_size"]))
    ds_config = {"train_batch_size": rows,
                 "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
                 "bf16": {"enabled": True}, "steps_per_print": 0,
                 **config["ds_config"]}

    # the engine adopts a mesh that exists: the cell's chips and no more, so
    # a host with four runs the one-chip program (its default mesh would
    # spread the batch over all of them; found on the four-chip host, PR 26)
    reset_mesh_context()
    set_mesh_context(MeshContext.create(devices=jax.devices()[:cell["chips"]]))
    t0 = time.monotonic()
    with jax.default_device(jax.devices("cpu")[0]):
        # the jitted init on the host, placed by the engine
        model, params = init_llama(cfg, seed=seed % (2**31 - 1),
                                   dtype=jnp.float32)
    t_init = time.monotonic() - t0
    t0 = time.monotonic()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=ds_config)
    del params
    jax.block_until_ready(engine.params)
    t_place = time.monotonic() - t0
    n_params = moe_cost.param_count(config)
    top_k, layers = cfg.num_experts_per_tok, cfg.num_hidden_layers
    log(f"training: depth {layers} ({n_params / 1e9:.3f}B parameters, "
        f"{cfg.num_local_experts} experts top-{top_k}), mesh "
        f"{dict(engine.mesh_ctx.mesh.shape)}, batch {rows} x {seq}; host init "
        f"{t_init:.1f} s, initialize+place {t_place:.1f} s")

    batches = gen.token_batches(seed, rows, seq, cfg.vocab_size)

    def step() -> float:
        batch = jnp.asarray(next(batches))
        return float(engine.train_batch(iter([(batch, batch)])))

    # correctness, all on the first batch, before the steps donate the
    # parameters they read
    first = next(batches)
    ids = jax.device_put(jnp.asarray(first),
                         engine.zero_plan.batch_sharding((first, ))[0])
    t0 = time.monotonic()
    last = min(LOGIT_POSITIONS, seq)
    want_lg, margin = reference.logits_and_margin(engine.params, ids[:1],
                                                  config, last=last)
    want_lg, clear = np.asarray(want_lg)[0], np.asarray(margin)[0] >= ROUTER_TIE_MARGIN
    got_lg = np.asarray(engine.eval_batch(ids[:1]), np.float32)[0, -last:]
    logit_err = (np.linalg.norm(got_lg - want_lg, axis=-1)
                 / np.linalg.norm(want_lg, axis=-1))
    logit_worst = float(logit_err[clear].max()) if clear.any() else float("inf")
    logit_over = float(np.mean(logit_err > LOGIT_RTOL))   # the near-ties' share
    logit_err = float(np.median(logit_err))
    del want_lg, got_lg
    t_check = time.monotonic() - t0
    want, got, t_steps, counts = [], [], [], None
    for i in range(2):
        t0 = time.monotonic()
        parts = reference.loss_parts(engine.params, ids, config)
        want.append(float(parts["ce"] + parts["aux"]))
        t_check += time.monotonic() - t0
        t0 = time.monotonic()
        got.append(float(engine.train_batch(iter([(ids, ids)]))))
        jax.block_until_ready(engine.params)
        t_steps.append(time.monotonic() - t0)
        if i == 0:
            stats = engine.moe_stats()
            counts = (np.asarray(stats["expert_counts"], np.int64),
                      np.asarray(parts["counts"], np.int64))
    losses = list(got)
    for _ in range(int(tr["warmup_steps"])):
        losses.append(step())
    jax.block_until_ready(engine.params)
    rel = [abs(g - w) / abs(w) for g, w in zip(got, want)]
    loss_ok = max(rel) <= LOSS_RTOL and got[1] < got[0]
    assigned = rows * seq * top_k * layers
    moved = int(np.abs(counts[0] - counts[1]).sum()) // 2
    counts_ok = (int(counts[0].sum()) == assigned == int(counts[1].sum())
                 and moved <= COUNT_MOVED_SHARE * assigned)
    logits_ok = logit_worst <= LOGIT_RTOL and clear.mean() >= 0.25
    log(f"correctness: loss {got[0]:.5f} at initialisation and {got[1]:.5f} "
        f"after one step on the same batch, float32 reference {want[0]:.5f} "
        f"and {want[1]:.5f} (relative difference {rel[0]:.1e}, {rel[1]:.1e}; "
        f"limit {LOSS_RTOL:g}; must descend): {'ok' if loss_ok else 'FAILED'}; "
        f"logits of the last {last} positions, relative distance median "
        f"{logit_err:.2e}, worst {logit_worst:.2e} over the {int(clear.sum())} "
        f"whose routing margin is {ROUTER_TIE_MARGIN:g} or more (limit "
        f"{LOGIT_RTOL:g}; {logit_over:.3f} of all are over it): "
        f"{'ok' if logits_ok else 'FAILED'}; expert counts sum "
        f"{int(counts[0].sum())} of {assigned}, {moved} assignments moved "
        f"against the reference ({moved / assigned:.2e} of all, limit "
        f"{COUNT_MOVED_SHARE:g}), busiest expert {int(counts[0].max())} of a "
        f"mean {counts[0].mean():.0f}: {'ok' if counts_ok else 'FAILED'}; "
        f"first step {t_steps[0]:.1f} s")

    # ---- the measured window ----
    gauge = get_registry().get("ds_moe_expert_load_max_over_mean")
    t_open = time.monotonic()
    setup = compiles.snapshot()
    step_s, load_samples, n_trace = [], [], int(tr["trace_steps"])
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(out_dir, profiler_options=opts)
    while time.monotonic() - t_open < seconds:
        t0 = time.monotonic()
        losses.append(step())
        step_s.append(time.monotonic() - t0)
        if trace:
            if gauge is not None:
                load_samples.append(float(gauge.value))
            if len(step_s) == n_trace:
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
             # the loss is read every step, so a host stall idles the chip:
             # the longest steps say whether a slow run lost one or many
             "step_s_longest": sorted(step_s)[-3:],
             "loss_first_two": got, "loss_reference": want,
             "logit_rel_err_median": logit_err,
             "logit_rel_err_worst_clear": logit_worst,
             "logit_positions_clear": int(clear.sum()),
             "logit_positions_over": logit_over, "assignments_moved": moved,
             "expert_counts": counts[0].tolist(),
             "loss_last": losses[-1], "step_programs": programs,
             "n_params": n_params,
             "bytes_in_use": [int((d.memory_stats() or {}).get("bytes_in_use", 0))
                              for d in jax.devices()[:cell["chips"]]]}
    correct = loss_ok and logits_ok and counts_ok and finite and programs == 1
    return {"correct": correct, "attempted": len(step_s),
            "failed": 0 if finite else 1, "end_to_end": e2e, "notes": notes,
            "setup": setup, "trace_steps": min(n_trace, len(step_s)),
            "tokens_per_step": rows * seq, "moe_load_samples": load_samples,
            "train_flops_per_token": moe_cost.train_flops_per_token(config, seq),
            "chips": cell["chips"]}
