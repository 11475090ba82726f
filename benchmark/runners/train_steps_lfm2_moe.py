"""Runner: optimizer steps of an LFM2-MoE decoder back to back through
``deepspeed_tpu.initialize``, on one chip that holds a share of the experts.

The training runner's flow (``train_steps.py``, ``train_steps_olmoe.py``) for
a third architecture: the published keys go through
``Lfm2MoePolicy.config_from_hf`` with the router at its published width, and
the deployment's share (the file's ``num_experts`` held, the first of the
chips that share a layer) is set on the result; the plain reference is
``reference/lfm2_moe.py``, the FLOP count ``lfm2_cost.py``. Seeded fp32
parameters made on the host, the selection bias seeded ``N(0,
expert_bias_std)`` and held constant, placed by the engine; bf16, AdamW,
chunked cross-entropy, recomputation as the file says. Fresh seeded token
ids every step, no gradient accumulation, the loss read each step.
"""

import time

import numpy as np

from benchmark import lfm2_cost, traffic as gen
from benchmark.reference import lfm2_moe as reference

# ``correct`` is decided on what the timed program gave at the timed sizes:
# the first call of the fused step on the first batch of 4 x 8,192 tokens (its
# loss, its gradients as AdamW's first moment holds them after one step from
# zero, ``mu / (1 - b1)``, the parameters it wrote, its router's counts) and
# the forward pass of the same batch, against ``reference.step_parts`` on the
# same fp32 masters. Each limit lies between what this program reads and what
# a wrong one would: the readings are ``calibrate_lfm2_moe.py``'s on the chip
# at these sizes (seeds 2147480701 and 2147483679, PR 31; PERF.md section 6
# has the table), where the sound program against a reference made wrong
# stands for a wrong program against the sound reference.
#
# (a) The loss at initialisation and after one optimizer step on the same
# batch. As in the other training cells (``train_steps.py`` says why): bf16
# rounding is unbiased over 32,768 tokens and moves a loss near ln(vocab) by
# 1e-5 of itself (read: 5e-6 to 2e-5), so 1e-3 leaves that 50 times over
# while an 8-bit matmul or a dropped term moves it by more; the second loss
# must be lower than the first.
LOSS_RTOL = 1e-3
# (b) A loss near ln 8192 hardly sees the router or the convolutions, so:
# the logits (bf16 compute, float32 out) of the last LOGIT_POSITIONS positions
# of each of the batch's sequences, relative L2 over the vocabulary position
# by position, by their median and 90th percentile. Routing is a
# discontinuity: five bf16 layers move the stream the router reads by 2%, a
# token whose 4th and 5th ``s + bias`` lie within some 1e-2 may rightly choose
# another expert, and through the taps and attention the positions after it
# differ by 4-27%; keeping positions by their routing margin (OLMoE's way)
# keeps an eighth of them (117-143 of 1,024 at a margin of 1e-2 here), so the
# two order statistics, which a minority of flips cannot move. The median
# reads 2.02e-2 and 2.03e-2 (2.00e-2 to 2.05e-2 over fourteen earlier runs
# of one sequence): it is the rounding of five bf16 layers and hardly knows
# the seed. With the held experts' matrices alone in fp8 it reads 2.32e-2 and
# 2.33e-2, with the convolutions' too 1.59e-1 and 1.62e-1, without the
# renormalisation 5.2e-1. The 90th percentile reads 5.9e-2; 2.1e-1 with
# convolutions and experts in fp8 (6.1e-2 with the experts alone: the median
# tells those).
LOGIT_POSITIONS = 256
LOGIT_MEDIAN_RTOL = 2.18e-2
LOGIT_P90_RTOL = 1.1e-1
ROUTER_TIE_MARGIN = 1e-2        # reported beside them, not judged
# A router that weights the chosen experts by ``s + bias`` moves the logits
# by a fifth of what bf16 does (median 2.07e-2 for 2.03e-2), so no distance
# tells it; a direction does. With ``d`` the program's logits less the
# reference's and ``v`` the wrong router's (the reference run with
# ``weigh_biased``) less the reference's, ``<d, v> / <v, v>`` is how far along
# the way to the wrong router the program lies: 0 for this program but for
# rounding, which has no part along ``v`` to speak of (read: -0.009 and
# 0.004), 1 for one that weights by the biased score (read: 1.009 and 0.996).
# Taken over the quiet positions: those where ``d`` and ``v`` are both within
# QUIET times their medians (three in four), because a near-tie that the
# wrong router's 0.4% flips is one that bf16's 2% flips too, and to the same
# runner-up, so over the flipped positions alone the share reads 0.2 to 1.1
# for this program. Half way is the limit: the program must lie nearer the
# reference.
WRONG_ROUTER_SHARE = 0.5
QUIET = 1.5
# The step's gradients against ``jax.grad`` of the reference, relative L2
# leaf by leaf, by the worst leaf of two kinds. Every leaf outside the expert
# blocks (embedding, norms, the convolutions' taps and projections, attention,
# the dense FFN) sees a flipped token only through the little its experts add
# to the stream: each reads 5.2e-2 to 6.7e-2 (the final norm 1.1e-2), and
# 2.3e-1 to 2.6e-1 with convolutions and experts in fp8, 6.5e-1 and more
# without the renormalisation. This is what holds ``short_conv_bwd`` (taps,
# ``in_proj``) on the chip. A leaf inside them (the norm the router reads,
# the router, the held w1 / w3 / w2) is a sum over its own tokens of
# gradients that random ids leave uncorrelated, so a share ``f`` of its rows
# flipped reads ``sqrt(2 f)``: the router 3.3e-1, the experts and the norm
# 2.3e-1 to 2.4e-1; in fp8 7.6e-1 and 5.8e-1, and a wrong ``share_dispatch``
# / ``share_combine`` backward, which that limit is there for, 1 or more.
# The held experts alone in fp8 move neither kind (6.3e-2 to 7.1e-2 and
# 3.4e-1 to 3.5e-1): the logits' median tells them.
GRAD_RTOL = 1.2e-1
GRAD_ROUTED_RTOL = 5e-1
# The parameters the step wrote against AdamW's first step from zero moments
# (``-lr g / (|g| + eps)``, no decay) worked on the host from that same
# gradient and added to the float32 masters, relative L2 of the difference
# over all of them to the update: float32 on both sides, 9e-6 on the chip
# (last places of ``p + update``), 2e-5 to 4e-5 at a rehearsal's sizes; a rule
# without the bias correction reads 2.2, ascent 2.
LR, ADAM_B1, ADAM_EPS = 1e-4, 0.9, 1e-8
UPDATE_RTOL = 1e-4
# (c) The per-expert assignment counts of the first batch, over the router's
# 64 experts, against the reference's: both sum to tokens * top_k * expert
# layers (every token keeps its top_k experts: nothing dropped), the
# assignments that moved between experts (half the summed differences: near-
# ties, in both directions) stay under COUNT_MOVED_SHARE of all (read: 7.7e-4
# to 9.1e-4; OLMoE's cell, at top-8 of a softmax, 2e-3 to 4e-3), and the rows
# sent to the experts held here (the program's own ``rows_held``, which its
# static rows array must cover or fall back) agree with the reference's
# within ROWS_HELD_RTOL (read: 6e-4). 0.5% is what a transposed, unbiased or
# misplaced router passes by an order of magnitude.
COUNT_MOVED_SHARE = 3e-3
ROWS_HELD_RTOL = 5e-3
# A rehearsal (tests only: widths of 64 on a CPU, 256 tokens) checks the flow
# and not the chip: its sums are short, so its distances are up to twice
# these (median 2.1e-2 to 2.5e-2, gradients 4e-2 to 1.1e-1 and 2.3e-1 to
# 4.3e-1, 3e-3 to 4.4e-3 of the assignments moved, one row of its 545 held
# is 1.8e-3). It is held to this many times the limits of the logits'
# distances, the gradients, the assignments moved and the rows held, and to
# the others as they are.
REHEARSAL_SLACK = 3.0


def seed_selection_bias(params, seed: int, std: float):
    """The tree with every expert layer's ``expert_bias`` drawn ``N(0,
    std)`` from ``seed`` and the layer's name (it is born zero, and a zero
    bias would not tell choosing by ``s + bias`` from choosing by ``s``)."""
    model = dict(params["model"])
    for name in sorted(model):
        moe = model[name].get("block_sparse_moe") if name.startswith("layers_") else None
        if moe is None or "expert_bias" not in moe:
            continue
        rng = np.random.default_rng([seed, int(name.split("_")[1])])
        bias = (std * rng.standard_normal(moe["expert_bias"].shape)).astype(np.float32)
        model[name] = {**model[name], "block_sparse_moe": {**moe, "expert_bias": bias}}
    return {**params, "model": model}


def model_config(config: dict):
    """``LlamaConfig`` of the file: the published keys through the policy,
    the router at its published width, this chip's share set beside it."""
    import dataclasses
    from deepspeed_tpu.module_inject.replace_policy import Lfm2MoePolicy
    width = lfm2_cost.router_width(config)
    cfg = Lfm2MoePolicy().config_from_hf({**config, "num_experts": width})
    return dataclasses.replace(
        cfg, moe_experts_held=int(config["num_experts"]), moe_share_index=0,
        ce_chunk_size=int(config["ce_chunk_size"]), remat=bool(config["remat"]),
        remat_policy=config.get("remat_policy"))


def first_moment(opt_state):
    """AdamW's ``mu`` tree out of the engine's optimizer state."""
    import jax
    has = lambda node: hasattr(node, "mu")      # noqa: E731
    return next(node.mu for node in jax.tree_util.tree_leaves(opt_state, is_leaf=has)
                if has(node))


def first_step(engine, ids, last: int) -> dict:
    """The timed program on the first batch: the forward pass's logits of each
    sequence's last ``last`` positions, then the fused step's first call: its
    ``loss``, its ``grads`` (out of AdamW's first moment), the parameters
    ``before`` and ``after`` it, its router's ``stats``; all numpy, float32."""
    import jax
    host = lambda tree: jax.tree_util.tree_map(np.asarray, tree)     # noqa: E731
    logits = np.asarray(engine.eval_batch(ids)[:, -last:], np.float32)
    before = host(engine.params)
    t0 = time.monotonic()
    loss = float(engine.train_batch(iter([(ids, ids)])))
    jax.block_until_ready(engine.params)
    seconds = time.monotonic() - t0
    grads = jax.tree_util.tree_map(lambda m: np.asarray(m, np.float32) / (1 - ADAM_B1),
                                   first_moment(engine.opt_state))
    return {"logits": logits, "loss": loss, "grads": grads, "before": before,
            "after": host(engine.params), "stats": engine.moe_stats(),
            "seconds": seconds}


def readings(got: dict, want: dict, wrong_logits) -> dict:
    """Every distance ``correct`` is decided on, between the program's first
    step (``first_step``) and the reference's (``reference.step_parts``),
    with the wrong router's logits for the direction."""
    import jax
    d, v = got["logits"] - want["logits"], wrong_logits - want["logits"]
    size = np.linalg.norm(want["logits"], axis=-1)
    err, wrong_err = np.linalg.norm(d, axis=-1) / size, np.linalg.norm(v, axis=-1) / size
    quiet = ((err <= QUIET * np.median(err)) & (wrong_err <= QUIET * np.median(wrong_err)))
    err = err.ravel()

    def norm(x) -> float:
        return float(np.sqrt(np.vdot(x, x)))

    grad_err, off_sq, update_sq = {}, 0.0, 0.0
    for (path, g), w, old, new in zip(
            jax.tree_util.tree_flatten_with_path(got["grads"])[0],
            *(jax.tree_util.tree_leaves(tree)
              for tree in (want["grads"], got["before"], got["after"]))):
        if np.any(w) or np.any(g):
            grad_err[jax.tree_util.keystr(path)] = norm(g - w) / norm(w)
        # AdamW's first step from zero moments, no decay: -lr g / (|g| + eps),
        # added to the float32 master in float32 as the engine stores it
        update = np.abs(g)
        update += np.float32(ADAM_EPS)
        np.divide(g, update, out=update)
        update *= np.float32(-LR)
        off_sq += norm(new - (old + update))**2
        update_sq += norm(update)**2
    moe_layers = {n.split("']['")[1] for n in grad_err if "block_sparse_moe" in n}
    routed = {n: e for n, e in grad_err.items() if "block_sparse_moe" in n
              or ("ffn_norm" in n and n.split("']['")[1] in moe_layers)}
    dense = {n: e for n, e in grad_err.items() if n not in routed}
    counts = (np.asarray(got["stats"]["expert_counts"], np.int64),
              np.asarray(want["counts"], np.int64))
    return {"logit_median": float(np.quantile(err, 0.5)),
            "logit_p90": float(np.quantile(err, 0.9)), "logit_worst": float(err.max()),
            "positions_clear": int((want["margin"] >= ROUTER_TIE_MARGIN).sum()),
            "positions_quiet": int(quiet.sum()),
            "wrong_router_share": float(np.vdot(d[quiet], v[quiet])
                                        / np.vdot(v[quiet], v[quiet])),
            "grad_worst": max(dense.items(), key=lambda kv: kv[1]),
            "grad_routed_worst": max(routed.items(), key=lambda kv: kv[1]),
            "grad_err": grad_err, "update_err": float(np.sqrt(off_sq / update_sq)),
            "loss_err": abs(got["loss"] - want["ce"]) / abs(want["ce"]),
            "counts": counts,
            "moved": int(np.abs(counts[0] - counts[1]).sum()) // 2,
            "rows_held": (int(np.sum(got["stats"]["rows_held"])), int(want["rows_held"]))}


def build_engine(cell, config, seed: int):
    """-> (engine, its ``LlamaConfig``, seconds of the host's init, seconds of
    ``initialize`` and placement): seeded fp32 parameters made on the host,
    the selection bias seeded, placed by the engine on the cell's chips."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.comm import reset_mesh_context
    from deepspeed_tpu.comm.mesh import MeshContext, set_mesh_context
    from deepspeed_tpu.models.llama import init_llama

    cfg = model_config(config)
    ds_config = {"train_batch_size": int(cell["traffic"]["global_batch"]),
                 "optimizer": {"type": "AdamW", "params": {"lr": LR}},
                 "bf16": {"enabled": True}, "steps_per_print": 0,
                 **config["ds_config"]}
    # the engine adopts a mesh that exists: the cell's chips and no more
    reset_mesh_context()
    set_mesh_context(MeshContext.create(devices=jax.devices()[:cell["chips"]]))
    t0 = time.monotonic()
    with jax.default_device(jax.devices("cpu")[0]):
        # the jitted init on the host, placed by the engine
        model, params = init_llama(cfg, seed=seed % (2**31 - 1),
                                   dtype=jnp.float32)
    params = seed_selection_bias(params, seed, float(config["expert_bias_std"]))
    t_init = time.monotonic() - t0
    t0 = time.monotonic()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=ds_config)
    del params
    jax.block_until_ready(engine.params)
    return engine, cfg, t_init, time.monotonic() - t0


def run(*, cell, config, seed, seconds, trace, rehearse, t_start, device,
        compiles, out_dir, log) -> dict:
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.observability import get_registry

    tr = cell["traffic"]
    rows, seq = int(tr["global_batch"]), int(tr["seq_len"])
    engine, cfg, t_init, t_place = build_engine(cell, config, seed)
    n_params = lfm2_cost.param_count(config)
    top_k = cfg.num_experts_per_tok
    moe_layers = sum(spec.ffn == "moe" for spec in cfg.layer_specs)
    assigned = rows * seq * top_k * moe_layers
    log(f"training: depth {cfg.num_hidden_layers} "
        f"({'/'.join(s.operator + '+' + s.ffn for s in cfg.layer_specs)}; "
        f"{n_params / 1e9:.3f}B parameters, {cfg.experts_held_} of "
        f"{cfg.num_local_experts} experts held, top-{top_k}), mesh "
        f"{dict(engine.mesh_ctx.mesh.shape)}, batch {rows} x {seq}; host init "
        f"{t_init:.1f} s, initialize+place {t_place:.1f} s")

    batches = gen.token_batches(seed, rows, seq, cfg.vocab_size)

    def step() -> float:
        batch = jnp.asarray(next(batches))
        return float(engine.train_batch(iter([(batch, batch)])))

    def rows_held() -> int:
        return int(np.sum(engine.moe_stats()["rows_held"]))

    # correctness, all on the first batch: the reference before the step
    # donates the parameters both read
    first = next(batches)
    ids = jax.device_put(jnp.asarray(first),
                         engine.zero_plan.batch_sharding((first, ))[0])
    last = min(LOGIT_POSITIONS, seq)
    t0 = time.monotonic()
    want = reference.step_parts(engine.params, ids, config, last)
    wrong = reference.step_parts(engine.params, ids, config, last, weigh_biased=True,
                                 gradients=False)["logits"]
    t_reference = time.monotonic() - t0
    t0 = time.monotonic()
    got = first_step(engine, ids, last)
    t_program = time.monotonic() - t0 - got["seconds"]
    t0 = time.monotonic()
    r = readings(got, want, wrong)
    del want["grads"], got["grads"], got["before"], got["after"], wrong
    want_after = reference.step_parts(engine.params, ids, config, last,
                                      gradients=False)["ce"]
    t_check = t_reference + t_program + time.monotonic() - t0
    got_after = float(engine.train_batch(iter([(ids, ids)])))
    losses = [got["loss"], got_after]
    for _ in range(int(tr["warmup_steps"])):
        losses.append(step())
    jax.block_until_ready(engine.params)
    slack = REHEARSAL_SLACK if rehearse else 1.0
    rel = [r["loss_err"], abs(got_after - want_after) / abs(want_after)]
    loss_ok = max(rel) <= LOSS_RTOL and got_after < got["loss"]
    counts, held = r["counts"], r["rows_held"]
    fell_back = int(np.sum(got["stats"]["share_fallback"]))
    held_rel = abs(held[0] - held[1]) / max(held[1], 1)
    counts_ok = (int(counts[0].sum()) == assigned == int(counts[1].sum())
                 and counts[0].shape == counts[1].shape == (cfg.num_local_experts, )
                 and held[0] == int(counts[0][:cfg.experts_held_].sum())
                 and r["moved"] <= slack * COUNT_MOVED_SHARE * assigned
                 and held_rel <= slack * ROWS_HELD_RTOL)
    logits_ok = (r["logit_median"] <= slack * LOGIT_MEDIAN_RTOL
                 and r["logit_p90"] <= slack * LOGIT_P90_RTOL
                 and r["wrong_router_share"] <= WRONG_ROUTER_SHARE)
    grads_ok = (r["grad_worst"][1] <= slack * GRAD_RTOL
                and r["grad_routed_worst"][1] <= slack * GRAD_ROUTED_RTOL
                and r["update_err"] <= UPDATE_RTOL)
    log(f"correctness: loss {got['loss']:.5f} at initialisation and {got_after:.5f} "
        f"after one step on the same batch, float32 reference {want['ce']:.5f} "
        f"and {want_after:.5f} (relative difference {rel[0]:.1e}, {rel[1]:.1e}; "
        f"limit {LOSS_RTOL:g}; must descend): {'ok' if loss_ok else 'FAILED'}; "
        f"logits of the last {last} positions of {rows} sequences, relative "
        f"distance median {r['logit_median']:.3e} (limit {slack * LOGIT_MEDIAN_RTOL:g}), "
        f"90th percentile {r['logit_p90']:.2e} (limit {slack * LOGIT_P90_RTOL:g}), worst "
        f"{r['logit_worst']:.2e}, {r['positions_clear']} positions with a routing "
        f"margin of {ROUTER_TIE_MARGIN:g} or more; {r['wrong_router_share']:.3f} of "
        f"the way to a router that weights by the biased score (limit "
        f"{WRONG_ROUTER_SHARE:g}): {'ok' if logits_ok else 'FAILED'}; the step's "
        f"gradients, relative distance of the worst leaf outside the expert "
        f"blocks {r['grad_worst'][1]:.2e} at {r['grad_worst'][0]} (limit "
        f"{slack * GRAD_RTOL:g}), inside them {r['grad_routed_worst'][1]:.2e} at "
        f"{r['grad_routed_worst'][0]} (limit {slack * GRAD_ROUTED_RTOL:g}), the parameters' "
        f"change against AdamW's on those gradients {r['update_err']:.1e} (limit "
        f"{UPDATE_RTOL:g}): {'ok' if grads_ok else 'FAILED'}; expert counts sum "
        f"{int(counts[0].sum())} of {assigned} over {counts[0].size} experts, "
        f"{r['moved']} assignments moved against the reference "
        f"({r['moved'] / assigned:.2e} of all, limit {slack * COUNT_MOVED_SHARE:g}), "
        f"rows held {held[0]} against the reference's {held[1]} (relative "
        f"difference {held_rel:.1e}, limit {slack * ROWS_HELD_RTOL:g}; "
        f"{100.0 * held[0] / assigned:.2f}% of all; {fell_back} layers took the "
        f"pass over all rows): {'ok' if counts_ok else 'FAILED'}; "
        f"first step {got['seconds']:.1f} s")

    # ---- the measured window ----
    gauge = get_registry().get("ds_moe_expert_load_max_over_mean")
    t_open = time.monotonic()
    setup = compiles.snapshot()
    step_s, load_samples, held_samples = [], [], []
    n_trace = int(tr["trace_steps"])
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(out_dir, profiler_options=opts)
    while time.monotonic() - t_open < seconds:
        t0 = time.monotonic()
        losses.append(step())
        step_s.append(time.monotonic() - t0)
        if trace:
            # the step has ended (its loss was read): neither read waits
            held_samples.append(rows_held())
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
    held_last = rows_held()         # of the window's last step, after it

    programs = int(engine._train_step_fused._cache_size())
    finite = bool(np.isfinite(losses).all())
    tokens = len(step_s) * rows * seq
    fallbacks = get_registry().get("ds_moe_share_fallback_total")
    e2e = {"setup_s": t_open - t_start,
           "train_tok_s": tokens / (t_close - t_open)}
    notes = {"setup": setup, "host_init_s": t_init, "initialize_s": t_place,
             "check_s": t_check, "check_reference_s": t_reference,
             "check_program_s": t_program, "first_step_s": got["seconds"],
             "steps": len(step_s), "step_s_median": float(np.median(step_s)),
             "step_s_longest": sorted(step_s)[-3:],
             "loss_first_two": losses[:2], "loss_reference": [want["ce"], want_after],
             "logit_rel_err_median": r["logit_median"],
             "logit_rel_err_p90": r["logit_p90"], "logit_rel_err_worst": r["logit_worst"],
             "logit_positions_clear": r["positions_clear"],
             "wrong_router_share": r["wrong_router_share"],
             "grad_rel_err": r["grad_err"], "update_rel_err": r["update_err"],
             "assignments_moved": r["moved"],
             "rows_held_first_batch": held, "rows_held_pct_first_batch":
             100.0 * held[0] / assigned,
             "rows_held_pct_last_step": 100.0 * held_last / assigned,
             "share_fallback_layers": (fallbacks.value if fallbacks is not None
                                       else None),
             "expert_counts": counts[0].tolist(),
             "loss_last": losses[-1], "step_programs": programs,
             "n_params": n_params,
             "bytes_in_use": [int((d.memory_stats() or {}).get("bytes_in_use", 0))
                              for d in jax.devices()[:cell["chips"]]]}
    correct = (loss_ok and logits_ok and grads_ok and counts_ok and finite
               and programs == 1)
    mean_held = float(np.mean(held_samples)) if held_samples else float(held[0])
    return {"correct": correct, "attempted": len(step_s),
            "failed": 0 if finite else 1, "end_to_end": e2e, "notes": notes,
            "setup": setup, "trace_steps": min(n_trace, len(step_s)),
            "tokens_per_step": rows * seq, "moe_load_samples": load_samples,
            "moe_rows_held_samples": held_samples,
            # the mean rows held a layer and step: what a weights' gradient
            # call of the grouped matmul multiplied (moe_cost.call_flops)
            "moe_rows_per_step": mean_held / moe_layers,
            "train_flops_per_token": lfm2_cost.train_flops_per_token(config, seq),
            "chips": cell["chips"]}
