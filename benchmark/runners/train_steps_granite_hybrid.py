"""Runner: optimizer steps of a Granite 4.0-H dense hybrid (Mamba-2 layers
with an attention layer among them) back to back through
``deepspeed_tpu.initialize``, one long sequence a step.

The training runner's flow (``train_steps_lfm2_moe.py``) for a fourth
architecture: the published keys go through
``GraniteMoeHybridPolicy.config_from_hf``; the plain reference is
``reference/granite_hybrid.py``, the FLOP count ``granite_cost.py``. Seeded
fp32 parameters made on the host and placed by the engine; bf16, AdamW,
chunked cross-entropy and recomputation as the file says, the ten layers
unrolled (``scan_layers`` refuses unlike layers: ROADMAP R9a). Fresh seeded
token ids every step, no gradient accumulation, the loss read each step.

How the reference pass is ordered against the engine's memory. The training
state is 12.7 GB of the chip's 16.9 and the float32 reference's gradient pass
at 16,384 tokens needs 10 GB more, so the two never share the chip: the
reference runs FIRST, on the host-made parameters put on the chip for it
alone. It gives the loss, the gradients, 256 positions' logits and the
largest state of the first batch, and the loss after one step of AdamW taken
on the host from its own gradients; everything goes to the host as numpy and
the chip is emptied. Only then is the engine built from the same host
parameters. ``memory_peak_bytes`` is the process's peak; the notes carry the
peak after the reference pass (``reference_peak_bytes``), and where the
run's peak is larger it is the step's own.

A fourth copy of the training runner's window loop (ROADMAP D12): what the
LFM2 runner's functions do as written is imported (``first_moment``, the
optimizer's constants); its ``first_step`` and ``readings`` read a router
and the last positions, so this file has its own.
"""

import gc
import time

import numpy as np

from benchmark import granite_cost, traffic as gen
from benchmark.reference import granite_hybrid as reference
from benchmark.runners.train_steps_lfm2_moe import (ADAM_B1, ADAM_EPS, LR,
                                                    first_moment)

# ``correct`` is decided on what the timed program gave at the timed sizes:
# the first call of the fused step on the first batch of 1 x 16,384 tokens
# (its loss, its gradients as AdamW's first moment holds them after one step
# from zero, ``mu / (1 - b1)``, the parameters it wrote, the state it
# reports) and the forward pass of the same batch, against
# ``reference.step_parts`` on the same fp32 masters. Each limit lies between
# what this program reads and what a wrong one would: the readings are
# ``calibrate_granite_hybrid.py``'s on the chip at these sizes (PERF.md
# section 6 has the table), where the sound program against a reference made
# wrong stands for a wrong program against the sound reference.
#
# (a) The loss at initialisation and after one optimizer step on the same
# batch, as in the other training cells: bf16 rounding is unbiased over
# 16,384 tokens and moves a loss near ln(vocab) by 1e-5 of itself, so 1e-3
# leaves that many times over while a dropped term moves it by more; the
# second loss must be lower than the first. The reference's second loss is
# taken after ITS OWN AdamW step (``-lr g / (|g| + eps)`` on its own
# gradients), so it also holds the direction of the program's update.
LOSS_RTOL = 1e-3
# (b) A loss near ln 12,544 hardly sees the mixers, so: the logits (bf16
# compute, float32 out) of LOGIT_POSITIONS positions, half of them spread
# evenly over the sequence and half the last ones (the last chunk of the
# scan included), relative L2 over the vocabulary position by position, by
# their median and 90th percentile. The median reads 1.842e-2 to 1.901e-2
# over twenty seeds: it is the rounding of ten bf16 layers and hardly knows
# the seed; the 90th percentile 2.00e-2 to 2.07e-2. With the state dropped at
# the chunks' ends they read 9.1e-2 to 1.24e-1 and 2.3e-1 to 2.8e-1, without
# ``residual_multiplier`` 1.06 and 1.10, without ``softplus`` NaN. With the
# scan's state or its decay rounded to bf16 after every token the reading
# depends on the seed (how large the states grow): 3.17e-2 / 3.77e-2 and
# 4.2e-2 / 4.9e-2 on two seeds, but 2.24e-2 / 2.48e-2 on a third, UNDER
# these limits: the gradients tell bf16 on every seed, the logits on two of
# three. A rotary embedding does not move them (1.875e-2: at initialisation
# the scores are near zero and the softmax near uniform whatever q and k
# are): the gradients tell it.
LOGIT_POSITIONS = 256
LOGIT_MEDIAN_RTOL = 2.3e-2
LOGIT_P90_RTOL = 2.6e-2
# The step's gradients against ``jax.grad`` of the reference, relative L2
# leaf by leaf, by the worst leaf of two kinds. A leaf that is a sum over all
# 16,384 tokens of terms of one sign pattern (every matrix, the taps, the
# norms, the embedding) reads 2.3e-2 to 3.2e-2 whatever its layer and seed
# (the worst of them 3.02e-2 to 3.20e-2 over twenty seeds); with the state in
# bf16 4.72e-2, 5.7e-2 and 9.8e-2 on three seeds, with the decay in bf16
# 5.8e-2, 5.8e-2 and 1.43e-1; 3.9e-1 to 4.5e-1 with the carry dropped, 1.40
# at q and k with a rotary embedding, 1.27 without ``residual_multiplier``.
# The limit is the geometric mean of 3.20e-2 and 4.72e-2, rounded down: a
# fifth of room either way (it was 4.0e-2 until the third seed's 4.72e-2).
# The scan's own per-head leaves (``A_log``, ``dt_bias``, ``D``: 64 values
# each, sums of terms of either sign that largely cancel) read 1.2e-2 to
# 9.4e-2, and the worst of the 27 of them 4.2e-2 to 9.4e-2 by seed (21 seeds:
# the logarithms have a mean of ln 5.8e-2 and a deviation of 0.21, and 9.4e-2
# is 2.3 of them out: a heavier tail than a normal's); 1.48e-1 to 2.9e-1 with
# the state in bf16, 1.71e-1 to 3.8e-1 with the decay, 1.25 to 1.83 with the
# carry dropped. Their limit leaves the most room above the reading, since a
# fresh seed reads higher and one run with ``correct`` false refuses a PR:
# 1.3e-1 is 3.8 deviations out (1.1e-1, the first choice, was 3.0: one run in
# 800 by the fit and more by the tail), and 12% under the least bf16 reading;
# no wrong way above is told by this limit alone.
GRAD_RTOL = 3.8e-2
GRAD_SCAN_RTOL = 1.3e-1
SCAN_LEAVES = ("['A_log']", "['dt_bias']", "['D']")
# The parameters the step wrote against AdamW's first step from zero moments
# on those gradients, as in the LFM2 cell: float32 on both sides, 1.0e-5 on
# the chip; a rule without the bias correction reads 0.9 or more.
UPDATE_RTOL = 1e-4
# (c) The largest |S| the program's scans held (``engine.ssm_stats()``, of
# the states at the chunks' ends, which are what its kernels keep) against
# the reference's over the same tokens: finite, and within this factor
# either way (read: 0.983 to 1.017; 0.68 and 0.83 without
# ``residual_multiplier``);
# and the mean ``dt`` within DT_MEAN_RTOL (read: 1e-5; it is computed in
# float32 on both sides from a bf16 projection).
STATE_ABSMAX_FACTOR = 1.1
DT_MEAN_RTOL = 2e-2
# (d) The seeded weights come from the program's own ``init_llama`` on both
# sides, so what the configuration's ``assumed`` says of the initial
# ``A_log``, ``dt_bias``, ``D``, taps and convolution bias is checked on the
# reference's side (``reference.initialisation_readings``, plain numpy on the
# host parameters): each reading is a distance in standard errors of the
# stated distribution's own statistic (or exact, for ``A_log`` and ``D``), so
# a sound draw reads under 4 or so whatever the size and a wrong rule reads
# tens to thousands.
INIT_SIGMAS = 6.0
# A rehearsal (tests only: widths of 64 on a CPU, 96 tokens) checks the flow
# and not the chip: its sums are short, so its distances are larger (the
# scan's leaves up to 9e-2, the others 4.5e-2). It is held to this many times
# the limits of the logits and the gradients, and to the others as they are.
REHEARSAL_SLACK = 3.0


def model_config(config: dict):
    """``LlamaConfig`` of the file: the published keys through the policy,
    the training recipe's keys set beside them."""
    import dataclasses
    from deepspeed_tpu.module_inject.replace_policy import GraniteMoeHybridPolicy
    cfg = GraniteMoeHybridPolicy().config_from_hf(config)
    return dataclasses.replace(
        cfg, ce_chunk_size=int(config["ce_chunk_size"]), remat=bool(config["remat"]),
        remat_policy=config.get("remat_policy"))


def logit_positions(seq: int) -> np.ndarray:
    """LOGIT_POSITIONS positions: half spread evenly, half the last ones."""
    half = LOGIT_POSITIONS // 2
    if seq <= LOGIT_POSITIONS:
        return np.arange(seq)
    return np.concatenate([np.arange(half) * ((seq - half) // half),
                           np.arange(seq - half, seq)])


def adamw_first_step(g):
    """AdamW's first step from zero moments, no decay: ``-lr g / (|g| +
    eps)``, float32 as the engine stores it."""
    update = np.abs(g)
    update += np.float32(ADAM_EPS)
    np.divide(g, update, out=update)
    update *= np.float32(-LR)
    return update


def host_parameters(config: dict, seed: int):
    """-> (the ``LlamaConfig``, its seeded fp32 parameters as numpy on the
    host, one ``layers_<i>`` a layer, seconds)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.llama import init_llama
    t0 = time.monotonic()
    cfg = model_config(config)
    with jax.default_device(jax.devices("cpu")[0]):
        _, params = init_llama(cfg, seed=seed % (2**31 - 1), dtype=jnp.float32)
    params = jax.tree_util.tree_map(np.asarray, params)
    return cfg, params, time.monotonic() - t0


def reference_pass(params, ids, config: dict, positions, wrong=frozenset()) -> dict:
    """The reference alone on the chip: ``reference.step_parts`` on the host
    parameters, then its loss after AdamW's first step on its own gradients
    (``ce_after``). Everything it returns is on the host."""
    import jax
    on_chip = jax.device_put(params, jax.devices()[0])
    want = reference.step_parts(on_chip, ids, config, positions, wrong)
    del on_chip
    stepped = jax.tree_util.tree_map(lambda p, g: p + adamw_first_step(g),
                                     params, want["grads"])
    stepped = jax.device_put(stepped, jax.devices()[0])
    want["ce_after"] = reference.step_parts(stepped, ids, config, positions[:1], wrong,
                                            gradients=False)["ce"]
    del stepped
    want["peak_bytes"] = int((jax.devices()[0].memory_stats() or {})
                             .get("peak_bytes_in_use", 0))
    return want


def build_engine(cell, config, params):
    """-> (engine, its ``LlamaConfig``, seconds of ``initialize`` and
    placement): the host parameters placed by the engine on the cell's
    chips."""
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.comm import reset_mesh_context
    from deepspeed_tpu.comm.mesh import MeshContext, set_mesh_context
    from deepspeed_tpu.models.llama import LlamaForCausalLM

    cfg = model_config(config)
    ds_config = {"train_batch_size": int(cell["traffic"]["global_batch"]),
                 "optimizer": {"type": "AdamW", "params": {"lr": LR}},
                 "bf16": {"enabled": True}, "steps_per_print": 0,
                 **config["ds_config"]}
    # the engine adopts a mesh that exists: the cell's chips and no more
    reset_mesh_context()
    set_mesh_context(MeshContext.create(devices=jax.devices()[:cell["chips"]]))
    t0 = time.monotonic()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(cfg), model_parameters=params, config=ds_config)
    jax.block_until_ready(engine.params)
    return engine, cfg, time.monotonic() - t0


def first_step(engine, ids, positions) -> dict:
    """The timed program on the first batch: the forward pass's logits at
    ``positions``, then the fused step's first call: its ``loss``, its
    ``grads`` (out of AdamW's first moment), the parameters ``before`` and
    ``after`` it, what its scans sowed (``stats``), the seconds it took; then
    the loss of a second step on the same batch (``loss_after``). numpy,
    float32."""
    import jax

    def host(tree):
        return jax.tree_util.tree_map(np.asarray, tree)

    logits = np.asarray(engine.eval_batch(ids)[:, positions], np.float32)
    before = host(engine.params)
    t0 = time.monotonic()
    loss = float(engine.train_batch(iter([(ids, ids)])))
    jax.block_until_ready(engine.params)
    seconds = time.monotonic() - t0
    grads = host(jax.tree_util.tree_map(
        lambda m: np.asarray(m, np.float32) / (1 - ADAM_B1),
        first_moment(engine.opt_state)))
    got = {"logits": logits, "loss": loss, "grads": grads, "before": before,
           "after": host(engine.params), "stats": engine.ssm_stats(),
           "seconds": seconds}
    got["loss_after"] = float(engine.train_batch(iter([(ids, ids)])))
    return got


def readings(got: dict, want: dict) -> dict:
    """Every distance ``correct`` is decided on, between the program's first
    step (``first_step``) and the reference's (``reference.step_parts``)."""
    import jax
    d = got["logits"] - want["logits"]
    err = (np.linalg.norm(d, axis=-1) / np.linalg.norm(want["logits"], axis=-1)).ravel()

    def norm(x) -> float:
        return float(np.sqrt(np.vdot(x, x)))

    grad_err, off_sq, update_sq = {}, 0.0, 0.0
    for (path, g), w, old, new in zip(
            jax.tree_util.tree_flatten_with_path(got["grads"])[0],
            *(jax.tree_util.tree_leaves(tree)
              for tree in (want["grads"], got["before"], got["after"]))):
        grad_err[jax.tree_util.keystr(path)] = norm(g - w) / norm(w)
        update = adamw_first_step(g)
        off_sq += norm(new - (old + update))**2
        update_sq += norm(update)**2
    top, dt = float(got["stats"]["state_absmax"]), float(got["stats"]["dt_mean"])
    scan = {n: e for n, e in grad_err.items() if n.endswith(SCAN_LEAVES)}
    summed = {n: e for n, e in grad_err.items() if n not in scan}
    return {"logit_median": float(np.quantile(err, 0.5)),
            "logit_p90": float(np.quantile(err, 0.9)), "logit_worst": float(err.max()),
            "grad_worst": max(summed.items(), key=lambda kv: kv[1]),
            "grad_scan_worst": max(scan.items(), key=lambda kv: kv[1]),
            "grad_err": grad_err, "update_err": float(np.sqrt(off_sq / update_sq)),
            "loss_err": abs(got["loss"] - want["ce"]) / abs(want["ce"]),
            "loss_after_err": (abs(got["loss_after"] - want["ce_after"])
                               / abs(want["ce_after"])),
            "descends": bool(got["loss_after"] < got["loss"]),
            "state_absmax": (top, want["state_absmax_chunks"], want["state_absmax"]),
            "state_absmax_ratio": top / want["state_absmax_chunks"],
            "dt_mean": (dt, want["dt_mean"]),
            "dt_mean_err": abs(dt - want["dt_mean"]) / want["dt_mean"]}


def verdicts(r: dict, init: dict, slack: float = 1.0) -> dict:
    """Each part of ``correct`` that the readings decide, by the limits
    above: what ``run`` reports and what the calibration holds every wrong
    reference to. NaN fails (no comparison with it holds)."""
    ratio = r["state_absmax_ratio"]
    return {
        "loss": bool(max(r["loss_err"], r["loss_after_err"]) <= LOSS_RTOL
                     and r["descends"]),
        "logits": bool(r["logit_median"] <= slack * LOGIT_MEDIAN_RTOL
                       and r["logit_p90"] <= slack * LOGIT_P90_RTOL),
        "grads": bool(r["grad_worst"][1] <= slack * GRAD_RTOL
                      and r["grad_scan_worst"][1] <= slack * GRAD_SCAN_RTOL
                      and r["update_err"] <= UPDATE_RTOL),
        "state": bool(np.isfinite(ratio)
                      and 1 / STATE_ABSMAX_FACTOR <= ratio <= STATE_ABSMAX_FACTOR
                      and r["dt_mean_err"] <= DT_MEAN_RTOL),
        "init": bool(init and max(init.values()) <= INIT_SIGMAS)}


def run(*, cell, config, seed, seconds, trace, rehearse, t_start, device,
        compiles, out_dir, log) -> dict:
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.observability import get_registry

    tr = cell["traffic"]
    rows, seq = int(tr["global_batch"]), int(tr["seq_len"])
    layer_cfg, params, t_init = host_parameters(config, seed)
    n_params = granite_cost.param_count(config)
    batches = gen.token_batches(seed, rows, seq, layer_cfg.vocab_size)
    first = next(batches)
    positions = logit_positions(seq)
    init = reference.initialisation_readings(params, config)

    # correctness, all on the first batch: the reference before the engine
    # exists (the docstring says why)
    t0 = time.monotonic()
    want = reference_pass(params, jnp.asarray(first), config, positions)
    t_reference = time.monotonic() - t0
    jax.clear_caches()      # the reference's programs hold nothing more

    engine, cfg, t_place = build_engine(cell, config, params)
    del params
    kinds = "/".join(s.operator for s in cfg.layer_specs)
    log(f"training: depth {cfg.num_hidden_layers} ({kinds}; {n_params / 1e9:.3f}B "
        f"parameters, vocabulary {cfg.vocab_size}), mesh "
        f"{dict(engine.mesh_ctx.mesh.shape)}, batch {rows} x {seq}; host init "
        f"{t_init:.1f} s, reference {t_reference:.1f} s (peak "
        f"{want['peak_bytes'] / 1e9:.2f} GB), initialize+place {t_place:.1f} s")

    def step() -> float:
        batch = jnp.asarray(next(batches))
        return float(engine.train_batch(iter([(batch, batch)])))

    ids = jax.device_put(jnp.asarray(first),
                         engine.zero_plan.batch_sharding((first, ))[0])
    t0 = time.monotonic()
    got = first_step(engine, ids, positions)
    t_program = time.monotonic() - t0 - got["seconds"]
    t0 = time.monotonic()
    r = readings(got, want)
    del want["grads"], got["grads"], got["before"], got["after"]
    gc.collect()    # 12 GB of host arrays: freed now, not inside the window
    t_check = t_reference + t_program + time.monotonic() - t0
    losses = [got["loss"], got["loss_after"]]
    for _ in range(int(tr["warmup_steps"])):
        losses.append(step())
    jax.block_until_ready(engine.params)
    slack = REHEARSAL_SLACK if rehearse else 1.0
    ok = verdicts(r, init, slack)
    said = {name: "ok" if good else "FAILED" for name, good in ok.items()}
    ratio = r["state_absmax_ratio"]
    log(f"correctness: loss {got['loss']:.5f} at initialisation and "
        f"{got['loss_after']:.5f} after one step on the same batch, float32 reference "
        f"{want['ce']:.5f} and {want['ce_after']:.5f} (relative difference "
        f"{r['loss_err']:.1e}, {r['loss_after_err']:.1e}; "
        f"limit {LOSS_RTOL:g}; must descend): {said['loss']}; "
        f"logits of {positions.size} positions across the sequence, relative "
        f"distance median {r['logit_median']:.3e} (limit {slack * LOGIT_MEDIAN_RTOL:g}), "
        f"90th percentile {r['logit_p90']:.3e} (limit {slack * LOGIT_P90_RTOL:g}), worst "
        f"{r['logit_worst']:.2e}: {said['logits']}; the step's "
        f"gradients, relative distance of the worst leaf {r['grad_worst'][1]:.3e} at "
        f"{r['grad_worst'][0]} (limit {slack * GRAD_RTOL:g}), of the scan's per-head "
        f"leaves {r['grad_scan_worst'][1]:.3e} at {r['grad_scan_worst'][0]} (limit "
        f"{slack * GRAD_SCAN_RTOL:g}), the parameters' change "
        f"against AdamW's on those gradients {r['update_err']:.1e} (limit "
        f"{UPDATE_RTOL:g}): {said['grads']}; largest |S| "
        f"{r['state_absmax'][0]:.4g} against the reference's {r['state_absmax'][1]:.4g} "
        f"at the chunks' ends ({r['state_absmax'][2]:.4g} over every token; ratio "
        f"{ratio:.4f}, within a factor {STATE_ABSMAX_FACTOR:g}), mean dt "
        f"{r['dt_mean'][0]:.5f} against {r['dt_mean'][1]:.5f} (limit {DT_MEAN_RTOL:g}): "
        f"{said['state']}; the seeded A_log, dt_bias, D, taps and convolution bias "
        f"against what the configuration assumes, worst {max(init, key=init.get)} at "
        f"{max(init.values()):.2f} standard errors (limit {INIT_SIGMAS:g}): "
        f"{said['init']}; first step {got['seconds']:.1f} s")

    # ---- the measured window ----
    t_open = time.monotonic()
    setup = compiles.snapshot()
    step_s = []
    n_trace = int(tr["trace_steps"])
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
    reg = get_registry()
    gauges = {name: (reg.get(name).value if reg.get(name) is not None else None)
              for name in ("ds_ssm_state_absmax", "ds_ssm_dt_mean")}
    last = engine.ssm_stats()
    e2e = {"setup_s": t_open - t_start,
           "train_tok_s": tokens / (t_close - t_open)}
    notes = {"setup": setup, "host_init_s": t_init, "initialize_s": t_place,
             "check_s": t_check, "check_reference_s": t_reference,
             "check_program_s": t_program, "first_step_s": got["seconds"],
             "reference_peak_bytes": want["peak_bytes"],
             "steps": len(step_s), "step_s_median": float(np.median(step_s)),
             "step_s_first": step_s[0],
             "step_s_longest": sorted(step_s)[-3:],
             "step_longest_index": int(np.argmax(step_s)),
             "loss_first_two": losses[:2],
             "loss_reference": [want["ce"], want["ce_after"]],
             "logit_rel_err_median": r["logit_median"],
             "logit_rel_err_p90": r["logit_p90"], "logit_rel_err_worst": r["logit_worst"],
             "grad_rel_err": r["grad_err"], "update_rel_err": r["update_err"],
             "state_absmax": r["state_absmax"], "dt_mean": r["dt_mean"],
             "initialisation_sigmas": init, "verdicts": ok,
             "ssm_stats_last_step": {k: float(v) for k, v in last.items()},
             "gauges": gauges,
             "model_layers": {m.labels["kind"]: m.value
                              for m in reg.series("ds_model_layers")},
             "loss_last": losses[-1], "step_programs": programs,
             "n_params": n_params,
             "bytes_in_use": [int((d.memory_stats() or {}).get("bytes_in_use", 0))
                              for d in jax.devices()[:cell["chips"]]]}
    correct = all(ok.values()) and finite and programs == 1
    return {"correct": correct, "attempted": len(step_s),
            "failed": 0 if finite else 1, "end_to_end": e2e, "notes": notes,
            "setup": setup, "trace_steps": min(n_trace, len(step_s)),
            "tokens_per_step": rows * seq,
            "train_flops_per_token": granite_cost.train_flops_per_token(config, seq),
            "chips": cell["chips"]}
