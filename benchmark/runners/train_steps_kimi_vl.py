"""Runner: optimizer steps of Kimi-VL-A3B's language model (``deepseek_v3``
blocks: latent attention, a leading dense layer, then a sigmoid router over
64 experts beside an ungated shared expert) back to back through
``deepspeed_tpu.initialize``, on one chip that holds a share of the experts
and of the vocabulary.

The training runner's flow (``train_steps_sdar_moe.py``) for a sixth
architecture: the published keys go through
``DeepseekV3Policy.config_from_hf`` with the router at its published width,
and the deployment's share (the file's ``n_routed_experts`` held, the first
of the chips that share a layer) is set on the result; the plain reference is
``reference/kimi_vl.py``, the FLOP count ``kimi_cost.py``. Seeded fp32
parameters made on the host (the embedding rows at the file's
``embedding_std``, the selection bias ``N(0, expert_bias_std)`` and held
constant) and placed by the engine; bf16, AdamW, chunked cross-entropy,
recomputation as the file says. Fresh seeded token ids every step out of the
vocabulary slice, no gradient accumulation, the loss read each step.

The reference runs FIRST, on the host-made parameters put on the chip for it
alone (the Granite and SDAR runners' order: 8 GB of training state and a
float32 gradient pass over 8,192 positions do not share the chip). What it
gives goes to the host as numpy and the chip is emptied; only then is the
engine built. A sixth copy of the training runner's window loop (ROADMAP
D12); the LFM2 runner's ``first_moment``, ``seed_selection_bias`` and AdamW
constants are imported.
"""

import gc
import time

import numpy as np

from benchmark import kimi_cost, traffic as gen
from benchmark.reference import kimi_vl as reference
from benchmark.runners.train_steps_lfm2_moe import (ADAM_B1, ADAM_EPS, first_moment,
                                                    seed_selection_bias)

# AdamW's rate: that of continued training of a trained checkpoint, which the
# cell stands for (the SDAR runner says what 1e-4 does to a first step from
# zero moments: every one of 669M parameters moves by the rate).
LR = 1e-5

# ``correct`` is decided on what the timed program gave at the timed sizes:
# the first call of the fused step on the first batch of 4 x 8,192 tokens (its
# loss, its gradients as AdamW's first moment holds them after one step from
# zero, ``mu / (1 - b1)``, the parameters it wrote, its router's counts, its
# latent's statistics) and the forward pass of the same batch, against
# ``reference.step_parts`` on the same fp32 masters and ids. Each limit lies
# between what this program reads and what a wrong one would: the readings
# are ``calibrate_kimi_vl.py``'s on the chip at these sizes (seeds 2147480901
# and 41, PR 40: ``readings/kimi_vl_calibration.jsonl``; PERF.md section 6
# has the table), and a third seed's from the cell's first run, where the
# sound program against a reference made wrong stands for a wrong program
# against the sound reference. The precision below the configuration's bf16
# is fp8 (every matmul's operands at three mantissa bits): it fails the
# logits, the gradients, the assignments moved and the latent's statistics and
# passes the losses and the rows held. A reference at bf16 operands reads as
# the sound one does (median 1.05e-2, worst leaf 7.2e-2) and is required of
# nothing.
#
# (a) The loss at initialisation and after one optimizer step on the same
# batch (the reference's second loss after ITS OWN AdamW step): read 1.1e-5
# to 1.9e-5 and 1.4e-5 to 2.1e-5 of the loss; the harness's limit for every
# training cell leaves that fifty times over. No shared expert reads 4.1e-3
# after the step, a gated one 1.8e-3, the missing 2.446 1.2e-3 to 1.3e-3 (fp8
# 6e-5 to 1.8e-4: a loss near ln 20,480 hardly sees the precision). The second
# loss must be lower than the first.
LOSS_RTOL = 1e-3
LOSS_AFTER_RTOL = 1e-3
# (b) The logits (bf16 compute, float32 out) at LOGIT_POSITIONS positions
# spread evenly over each sequence (so over every depth of context), relative
# L2 over the vocabulary position by position, by their median and 90th
# percentile: routing is a discontinuity (the LFM2 runner says why two order
# statistics and no margin filter). The median reads 1.05e-2 on all three
# seeds (the rounding of six bf16 layers hardly knows the seed): scores over
# sqrt(128) 4.0e-2 and 4.1e-2, the rope on the wrong slice 9.6e-2, fp8 1.00e-1,
# no 2.446 2.1e-1, a gated shared expert 4.5e-1, none 8.5e-1; the limit 1.9
# times over the reading and 2.0 under the least of those. A dropped
# ``kv_a_layernorm`` (the latent's rms is 1.0002 before it at seeded weights)
# reads 1.33e-2 and a top-6 of the unbiased scores 1.11e-2: the median tells
# neither. The 90th percentile reads 1.14e-2 to 1.15e-2 (4e-4 of the
# assignments move: under a tenth of the positions feel one): the dropped norm
# 2.99e-2, the unbiased top-6 1.46e-1, fp8 2.1e-1 and 2.3e-1; the limit 1.9
# over the reading, 1.36 under the dropped norm's.
LOGIT_POSITIONS = 256
LOGIT_MEDIAN_RTOL = 2.0e-2
LOGIT_P90_RTOL = 2.2e-2
# The step's gradients against ``jax.grad`` of the reference, relative L2 leaf
# by leaf, by kind. Outside the expert blocks (embedding, head, norms, latent
# attention's five leaves, the dense FFN) the worst leaf reads 6.5e-2 to
# 7.4e-2 (a ``q_proj``; ``kv_a_proj_with_mqa`` 5.0e-2 to 5.9e-2, ``kv_b_proj``
# 5.2e-2 to 5.9e-2): scores over sqrt(128) 3.6e-1, fp8 4.3e-1, no 2.446
# 3.4e-1, a gated shared expert 7.1e-1, the rope on the wrong slice 8.8e-1; a
# dropped ``kv_a_layernorm`` leaves that leaf with no gradient on one side
# (reads inf; ``kv_a_proj_with_mqa`` 1.25e-1 beside it). Inside them (the
# norm the router reads, the held w1 / w3 / w2) 1.67e-1 to 1.75e-1 where the
# shared expert of the same block reads 5.6e-2 to 5.9e-2: fp8 4.5e-1 and
# 4.6e-1, a gated shared expert 1.4, none inf. The routers' own kernels by
# their median layer (sums of terms of either sign) 1.83e-1 to 1.93e-1: fp8
# 5.1e-1 and 5.3e-1, no 2.446 1.7. Each limit 1.7 to 2.0 over the reading and
# 1.5 to 2.4 under fp8's least. The unbiased top-6 reads 1.3e-1, 3.1e-1 and
# 3.4e-1 to 3.6e-1: the routing tells it.
#
# WHAT THE ROUTED LEAVES' 0.17 IS MADE OF (``calibrate_kimi_vl.py --routing``,
# seed 2147480901 on the chip, PR 40; ``readings/``'s ``routed_as_program``
# line): the ROUTING, not the share's path. bf16's rounding of the stream
# flips the router's near-ties, and a flipped token's whole row leaves one
# expert's sum and joins another's. Token by token a bf16 program's router and
# the reference's differ on 0.95% of the assignments (5.4% of the tokens have
# one such), by expert layer 0.47, 0.74, 0.91, 1.18 and 1.45% as the streams
# drift apart; rows that random ids leave uncorrelated then read ``sqrt(2 f)``
# = 0.097, 0.122, 0.135, 0.154, 0.170, and the held experts read 0.106, 0.123,
# 0.137, 0.155, 0.167 (the worst leaf is always the deepest layer's). The
# ``moved`` of (c) below is NOT that share: it is the net difference of the
# per-expert counts, in which a token an expert lost and one it won cancel
# (4.6e-4 here, a twentieth). The model's own ``jax.grad`` as one program
# whose choice is known (it reads as the step does, every leaf to two places)
# against the reference ROUTED ALIKE: the held experts 1.43e-2 to 1.56e-2
# (the shared expert 1.40e-2 to 1.53e-2, the routers 1.5e-2 to 1.9e-2, every
# leaf outside the blocks 1.1e-2 to 1.8e-2). So the share's path adds nothing
# of its own, and three quarters of every other leaf's distance is flipped
# tokens too; two bf16 programs of this model (the step and that one) are
# 3.5e-2 to 1.0e-1 apart on the held experts, for the same reason. The fused
# step hands out counts, not its choice, so this runner cannot route the
# reference alike and hold every leaf to 4e-2: PERF.md section 7 asks that
# of D12's runner.
GRAD_RTOL = 1.5e-1
GRAD_ROUTED_RTOL = 3e-1
GRAD_ROUTER_RTOL = 3.3e-1
# The parameters the step wrote against AdamW's first step from zero moments
# on those gradients (``-lr g / (|g| + eps)``, no decay), float32 on both
# sides, LEAF BY LEAF and held by the worst leaf: 2.6e-4 to 3.2e-4 on four
# seeds, always a norm's weights (at 1.0 the last place of ``p + update`` is
# 0.6% of a 1e-5 update, and the sides' updates differ in theirs; pooled over
# all 669M values it read 3.9e-5, and a ``kv_a_layernorm`` of 512 values left
# unwritten would have read 8.7e-4 there and passed). A leaf not written
# reads 1, a rule without the bias correction or an ascent 1 to 2; a leaf with
# no gradient (the selection bias) has to stand as it was.
UPDATE_RTOL = 1e-3
# (c) The per-expert assignment counts of the first batch, over the router's
# 64 experts, against the reference's: both sum to tokens * top_k * expert
# layers = 983,040 (nothing dropped); the assignments that moved between the
# experts' COUNTS (half the summed differences: a net figure, a twentieth of
# the share that differs token by token, see above) read 3.9e-4 to 5.3e-4 of
# all (fp8 1.88e-3 and 1.90e-3, scores over
# sqrt(128) 1.1e-3, a gated shared expert 5.3e-3, the unbiased top-6 9.6e-3
# and 9.8e-3); the rows sent to the experts held agree within 1.4e-4 to 2.8e-4
# (5.5e-4 against the bf16 reference; the unbiased top-6 6.3e-3 and 1.1e-2,
# which this limit is there for; fp8 2e-4: not told here); no layer took the
# pass over all rows.
COUNT_MOVED_SHARE = 1.2e-3
ROWS_HELD_RTOL = 2.5e-3
# (d) The latent's statistics (``mla_stats``: the rms of the latent before
# ``kv_a_layernorm`` and of the rope key before the rotation, the layers'
# means over the batch) against the reference's: 4e-6 to 6e-6 and 1.6e-5 to
# 2.7e-5. With seeded weights the latent's rms is 1.0002 before its norm and
# 1 after it, so a program that reads it after the norm is 1.7e-4 off, and one
# whose ``kv_a_proj_with_mqa`` splits elsewhere further; fp8 reads 2.9e-4 to
# 4.7e-4.
LATENT_RTOL = 1e-4
# A rehearsal (tests only: widths of 64 on a CPU, 256 tokens) checks the flow
# and not the chip: its sums are short, so it is held to this many times the
# limits of the logits' distances, the gradients, the assignments moved, the
# rows held and the latent's statistics, and to the others as they are.
REHEARSAL_SLACK = 4.0


def adamw_first_step(g):
    """AdamW's first step from zero moments, no decay: ``-lr g / (|g| +
    eps)``, float32 as the engine stores it."""
    update = np.abs(g)
    update += np.float32(ADAM_EPS)
    np.divide(g, update, out=update)
    update *= np.float32(-LR)
    return update


def model_config(config: dict):
    """``LlamaConfig`` of the file: the published keys through the policy,
    the router at its published width, this chip's share and the training
    recipe's keys set beside it."""
    import dataclasses
    from deepspeed_tpu.module_inject.replace_policy import DeepseekV3Policy
    cfg = DeepseekV3Policy().config_from_hf(
        {**config, "n_routed_experts": kimi_cost.router_width(config)})
    return dataclasses.replace(
        cfg, moe_experts_held=int(config["n_routed_experts"]), moe_share_index=0,
        ce_chunk_size=int(config["ce_chunk_size"]), remat=bool(config["remat"]),
        remat_policy=config.get("remat_policy"))


def host_parameters(config: dict, seed: int):
    """-> (the ``LlamaConfig``, its seeded fp32 parameters as numpy on the
    host, seconds)."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.llama import init_llama
    t0 = time.monotonic()
    cfg = model_config(config)
    with jax.default_device(jax.devices("cpu")[0]):
        _, params = init_llama(cfg, seed=seed % (2**31 - 1), dtype=jnp.float32)
    params = jax.tree_util.tree_map(np.asarray, params)
    # the embedding at the configuration's ``embedding_std`` (its ``assumed``
    # says why) and the selection bias seeded (it is born zero)
    table = params["model"]["embed_tokens"]
    table["embedding"] = table["embedding"] * np.float32(
        float(config["embedding_std"]) / table["embedding"].std())
    params = seed_selection_bias(params, seed, float(config["expert_bias_std"]))
    return cfg, params, time.monotonic() - t0


def logit_positions(rows: int, seq: int) -> np.ndarray:
    """[rows, n]: LOGIT_POSITIONS positions in all, evenly spread over each
    sequence's positions that have a next token."""
    n = min(max(LOGIT_POSITIONS // rows, 1), seq - 1)
    at = np.linspace(0, seq - 2, n).astype(int)
    return np.stack([at] * rows)


def reference_pass(params, ids, config: dict, at, wrong=frozenset(), choice=None) -> dict:
    """The reference alone on the chip: ``reference.step_parts`` on the host
    parameters, then its loss after AdamW's first step on its own gradients
    (``ce_after``). Everything it returns is on the host. ``choice`` (the
    calibration's) routes it as ``reference.step_parts`` says."""
    import jax
    on_chip = jax.device_put(params, jax.devices()[0])
    want = reference.step_parts(on_chip, ids, config, at, wrong=wrong, choice=choice)
    del on_chip
    stepped = jax.tree_util.tree_map(lambda p, g: p + adamw_first_step(g),
                                     params, want["grads"])
    stepped = jax.device_put(stepped, jax.devices()[0])
    want["ce_after"] = reference.step_parts(stepped, ids, config, at, wrong=wrong,
                                            gradients=False, choice=choice)["ce"]
    del stepped
    want["peak_bytes"] = int((jax.devices()[0].memory_stats() or {})
                             .get("peak_bytes_in_use", 0))
    return want


def build_engine(cell, config, params):
    """-> (engine, its ``LlamaConfig``, seconds of ``initialize`` and
    placement): the host parameters placed by the engine on the cell's chips."""
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


def first_step(engine, ids, at) -> dict:
    """The timed program on the first batch: the forward pass's logits at
    ``at`` (a sequence at a time: the float32 logits of four are 2.7 GB),
    then the fused step's first call: its ``loss``, its ``grads`` (out of
    AdamW's first moment), the parameters ``before`` and ``after`` it, its
    router's and its latent's ``stats``, the seconds it took; then the loss
    of a second step on the same batch (``loss_after``). numpy, float32."""
    import jax

    def host(tree):
        return jax.tree_util.tree_map(np.asarray, tree)

    logits = np.stack([np.asarray(engine.eval_batch(ids[row:row + 1])[0, at[row]],
                                  np.float32) for row in range(at.shape[0])])
    before = host(engine.params)
    t0 = time.monotonic()
    loss = float(engine.train_batch(iter([(ids, ids)])))
    jax.block_until_ready(engine.params)
    seconds = time.monotonic() - t0
    grads = host(jax.tree_util.tree_map(
        lambda m: np.asarray(m, np.float32) / (1 - ADAM_B1),
        first_moment(engine.opt_state)))
    got = {"logits": logits, "loss": loss, "grads": grads, "before": before,
           "after": host(engine.params), "stats": engine.moe_stats(),
           "latent": engine.mla_stats(), "seconds": seconds}
    got["loss_after"] = float(engine.train_batch(iter([(ids, ids)])))
    return got


NAMED_LEAVES = ("kv_a_proj_with_mqa", "kv_b_proj", "shared_expert")


def readings(got: dict, want: dict) -> dict:
    """Every distance ``correct`` is decided on, between the program's first
    step (``first_step``) and the reference's (``reference_pass``)."""
    import jax
    d = got["logits"] - want["logits"]
    err = (np.linalg.norm(d, axis=-1) / np.linalg.norm(want["logits"], axis=-1)).ravel()

    def norm(x) -> float:
        return float(np.sqrt(np.vdot(x, x)))

    grad_err, update_err = {}, {}
    for (path, g), w, old, new in zip(
            jax.tree_util.tree_flatten_with_path(got["grads"])[0],
            *(jax.tree_util.tree_leaves(tree)
              for tree in (want["grads"], got["before"], got["after"]))):
        name = jax.tree_util.keystr(path)
        if np.any(w) or np.any(g):      # the selection bias has none
            # a leaf only ONE side gives a gradient (a reference made wrong
            # by dropping its norm) is as far off as can be
            grad_err[name] = norm(g - w) / norm(w) if np.any(w) else float("inf")
        # leaf by leaf: a small leaf left unwritten (a ``kv_a_layernorm`` is
        # 512 of 669M values) is lost in a norm pooled over all of them. A
        # leaf with no gradient has to stand as it was
        update = adamw_first_step(g)
        update_err[name] = (norm(new - (old + update)) / norm(update) if np.any(update)
                            else 0.0 if np.array_equal(new, old) else float("inf"))
    moe_layers = {n.split("']['")[1] for n in grad_err if "block_sparse_moe" in n}
    router = {n: e for n, e in grad_err.items() if "['gate']" in n}
    routed = {n: e for n, e in grad_err.items() if n not in router
              and ("block_sparse_moe" in n
                   or ("ffn_norm" in n and n.split("']['")[1] in moe_layers))}
    dense = {n: e for n, e in grad_err.items() if n not in routed and n not in router}
    counts = (np.asarray(got["stats"]["expert_counts"], np.int64),
              np.asarray(want["counts"], np.int64))
    latent = got["latent"] or {}
    return {"logit_median": float(np.quantile(err, 0.5)),
            "logit_p90": float(np.quantile(err, 0.9)), "logit_worst": float(err.max()),
            "grad_worst": max(dense.items(), key=lambda kv: kv[1]),
            "grad_routed_worst": max(routed.items(), key=lambda kv: kv[1]),
            "grad_router_median": float(np.median(list(router.values()))),
            "grad_router_worst": max(router.items(), key=lambda kv: kv[1]),
            "grad_named": {leaf: max(e for n, e in grad_err.items() if leaf in n)
                           for leaf in NAMED_LEAVES},
            "grad_err": grad_err,
            "update_worst": max(update_err.items(), key=lambda kv: kv[1]),
            "loss_err": abs(got["loss"] - want["ce"]) / abs(want["ce"]),
            "loss_after_err": (abs(got["loss_after"] - want["ce_after"])
                               / abs(want["ce_after"])),
            "descends": bool(got["loss_after"] < got["loss"]),
            "counts": [c.tolist() for c in counts],
            "assigned": [int(c.sum()) for c in counts],
            "moved": int(np.abs(counts[0] - counts[1]).sum()) // 2,
            "rows_held": [int(np.sum(got["stats"]["rows_held"])), int(want["rows_held"])],
            "share_fallback": int(np.sum(got["stats"]["share_fallback"])),
            "latent_rms": [float(latent.get("latent_rms", np.nan)), want["latent_rms"]],
            "k_rope_rms": [float(latent.get("k_rope_rms", np.nan)), want["k_rope_rms"]]}


def verdicts(r: dict, assigned: int, experts: int, held: int,
             slack: float = 1.0) -> dict:
    """Each part of ``correct`` that the readings decide, by the limits
    above: what ``run`` reports and what the calibration holds every wrong
    reference to. NaN fails (no comparison with it holds)."""
    rows = r["rows_held"]
    counts = np.asarray(r["counts"][0])
    return {
        "loss": bool(r["loss_err"] <= LOSS_RTOL
                     and r["loss_after_err"] <= LOSS_AFTER_RTOL and r["descends"]),
        "logits": bool(r["logit_median"] <= slack * LOGIT_MEDIAN_RTOL
                       and r["logit_p90"] <= slack * LOGIT_P90_RTOL),
        "grads": bool(r["grad_worst"][1] <= slack * GRAD_RTOL
                      and r["grad_routed_worst"][1] <= slack * GRAD_ROUTED_RTOL
                      and r["grad_router_median"] <= slack * GRAD_ROUTER_RTOL
                      and r["update_worst"][1] <= UPDATE_RTOL),
        "routing": bool(r["assigned"] == [assigned, assigned]
                        and counts.shape == (experts, )
                        and rows[0] == int(counts[:held].sum())
                        and r["moved"] <= slack * COUNT_MOVED_SHARE * assigned
                        and abs(rows[0] - rows[1]) <= slack * ROWS_HELD_RTOL * max(rows[1], 1)
                        and r["share_fallback"] == 0),
        "latent": bool(all(abs(got - want) <= slack * LATENT_RTOL * want
                           for got, want in (r["latent_rms"], r["k_rope_rms"])))}


def run(*, cell, config, seed, seconds, trace, rehearse, t_start, device,
        compiles, out_dir, log) -> dict:
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.observability import get_registry

    tr = cell["traffic"]
    rows, seq = int(tr["global_batch"]), int(tr["seq_len"])
    layer_cfg, params, t_init = host_parameters(config, seed)
    n_params = kimi_cost.param_count(config)
    batches = gen.token_batches(seed, rows, seq, layer_cfg.vocab_size)
    first = next(batches)
    at = logit_positions(rows, seq)

    # correctness, all on the first batch: the reference before the engine
    # exists (the docstring says why)
    t0 = time.monotonic()
    want = reference_pass(params, first, config, at)
    t_reference = time.monotonic() - t0
    jax.clear_caches()      # the reference's programs hold nothing more

    engine, cfg, t_place = build_engine(cell, config, params)
    del params
    top_k = cfg.num_experts_per_tok
    moe_layers = sum(spec.ffn == "moe" for spec in cfg.layer_specs)
    assigned = rows * seq * top_k * moe_layers
    log(f"training: depth {cfg.num_hidden_layers} "
        f"({'/'.join(s.operator + '+' + s.ffn for s in cfg.layer_specs)}; "
        f"{n_params / 1e9:.3f}B parameters, {kimi_cost.bytes_at_rest(config) / 1e9:.2f} GB "
        f"at rest, {cfg.experts_held_} of {cfg.num_local_experts} experts held, "
        f"top-{top_k}, vocabulary {cfg.vocab_size}), mesh "
        f"{dict(engine.mesh_ctx.mesh.shape)}, batch {rows} x {seq}; host init "
        f"{t_init:.1f} s, reference {t_reference:.1f} s (peak "
        f"{want['peak_bytes'] / 1e9:.2f} GB), initialize+place {t_place:.1f} s")

    def step() -> float:
        batch = jnp.asarray(next(batches))
        return float(engine.train_batch(iter([(batch, batch)])))

    def rows_held() -> int:
        return int(np.sum(engine.moe_stats()["rows_held"]))

    ids = jax.device_put(jnp.asarray(first),
                         engine.zero_plan.batch_sharding((first, ))[0])
    t0 = time.monotonic()
    got = first_step(engine, ids, at)
    t_program = time.monotonic() - t0 - got["seconds"]
    t0 = time.monotonic()
    r = readings(got, want)
    del want["grads"], got["grads"], got["before"], got["after"]
    gc.collect()    # 8 GB of host arrays: freed now, not inside the window
    t_check = t_reference + t_program + time.monotonic() - t0
    losses = [got["loss"], got["loss_after"]]
    for _ in range(int(tr["warmup_steps"])):
        losses.append(step())
    jax.block_until_ready(engine.params)
    slack = REHEARSAL_SLACK if rehearse else 1.0
    ok = verdicts(r, assigned, cfg.num_local_experts, cfg.experts_held_, slack)
    said = {name: "ok" if good else "FAILED" for name, good in ok.items()}
    held = r["rows_held"]
    log(f"correctness: loss {got['loss']:.5f} at initialisation and "
        f"{got['loss_after']:.5f} after one step on the same batch, float32 reference "
        f"{want['ce']:.5f} and {want['ce_after']:.5f} (relative difference "
        f"{r['loss_err']:.1e}, {r['loss_after_err']:.1e}; limits {LOSS_RTOL:g}, "
        f"{LOSS_AFTER_RTOL:g}; must descend): {said['loss']}; logits at {at.size} "
        f"positions of {rows} sequences, relative distance median "
        f"{r['logit_median']:.3e} (limit {slack * LOGIT_MEDIAN_RTOL:g}), 90th percentile "
        f"{r['logit_p90']:.3e} (limit {slack * LOGIT_P90_RTOL:g}), worst "
        f"{r['logit_worst']:.2e}: {said['logits']}; the step's gradients, relative "
        f"distance of the worst leaf outside the expert blocks {r['grad_worst'][1]:.3e} "
        f"at {r['grad_worst'][0]} (limit {slack * GRAD_RTOL:g}), inside them "
        f"{r['grad_routed_worst'][1]:.3e} at {r['grad_routed_worst'][0]} (limit "
        f"{slack * GRAD_ROUTED_RTOL:g}), of the routers' kernels the median layer "
        f"{r['grad_router_median']:.3e} (limit {slack * GRAD_ROUTER_RTOL:g}; worst "
        f"{r['grad_router_worst'][1]:.3e}), by name "
        + ", ".join(f"{leaf} {e:.3e}" for leaf, e in r["grad_named"].items())
        + f", the parameters' change against AdamW's on those gradients, the worst "
        f"leaf {r['update_worst'][1]:.1e} at {r['update_worst'][0]} (limit "
        f"{UPDATE_RTOL:g}): {said['grads']}; expert counts "
        f"sum {r['assigned'][0]} of {assigned} over {len(r['counts'][0])} experts, "
        f"{r['moved']} assignments moved against the reference "
        f"({r['moved'] / assigned:.2e} of all, limit {slack * COUNT_MOVED_SHARE:g}), "
        f"rows held {held[0]} against the reference's {held[1]} "
        f"({100.0 * held[0] / assigned:.2f}% of all; {r['share_fallback']} layers took "
        f"the pass over all rows): {said['routing']}; latent rms {r['latent_rms'][0]:.5f} "
        f"against {r['latent_rms'][1]:.5f}, rope key rms {r['k_rope_rms'][0]:.5f} against "
        f"{r['k_rope_rms'][1]:.5f} (limit {slack * LATENT_RTOL:g}): {said['latent']}; first step "
        f"{got['seconds']:.1f} s")

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
    reg = get_registry()
    fallbacks = reg.get("ds_moe_share_fallback_total")
    counts = np.asarray(r["counts"][0])
    e2e = {"setup_s": t_open - t_start,
           "train_tok_s": tokens / (t_close - t_open)}
    notes = {"setup": setup, "host_init_s": t_init, "initialize_s": t_place,
             "check_s": t_check, "check_reference_s": t_reference,
             "check_program_s": t_program, "first_step_s": got["seconds"],
             "reference_peak_bytes": want["peak_bytes"],
             "steps": len(step_s), "step_s_median": float(np.median(step_s)),
             "step_s_longest": sorted(step_s)[-3:],
             "loss_first_two": losses[:2],
             "loss_reference": [want["ce"], want["ce_after"]],
             "logit_rel_err_median": r["logit_median"],
             "logit_rel_err_p90": r["logit_p90"], "logit_rel_err_worst": r["logit_worst"],
             "grad_rel_err": r["grad_err"], "update_rel_err_worst_leaf": r["update_worst"],
             "assignments_moved": r["moved"], "rows_held_first_batch": held,
             "rows_held_pct_first_batch": 100.0 * held[0] / assigned,
             "rows_held_pct_last_step": 100.0 * held_last / assigned,
             "rows_held_pct_traced_steps": [100.0 * h / assigned for h in held_samples],
             "busiest_expert_over_mean_first_batch": float(counts.max() / counts.mean()),
             "share_fallback_layers": (fallbacks.value if fallbacks is not None
                                       else None),
             "latent_rms": r["latent_rms"], "k_rope_rms": r["k_rope_rms"],
             "model_layers": {m.labels["kind"]: m.value
                              for m in reg.series("ds_model_layers")},
             "verdicts": ok, "expert_counts": r["counts"][0],
             "loss_last": losses[-1], "step_programs": programs,
             "n_params": n_params,
             "bytes_in_use": [int((d.memory_stats() or {}).get("bytes_in_use", 0))
                              for d in jax.devices()[:cell["chips"]]]}
    correct = all(ok.values()) and finite and programs == 1
    mean_held = float(np.mean(held_samples)) if held_samples else float(held[0])
    return {"correct": correct, "attempted": len(step_s),
            "failed": 0 if finite else 1, "end_to_end": e2e, "notes": notes,
            "setup": setup, "trace_steps": min(n_trace, len(step_s)),
            "tokens_per_step": rows * seq, "moe_load_samples": load_samples,
            "moe_rows_held_samples": held_samples,
            # the mean rows held a layer and step: what a weights' gradient
            # call of the grouped matmul multiplied (moe_cost.call_flops)
            "moe_rows_per_step": mean_held / moe_layers,
            "train_flops_per_token": kimi_cost.train_flops_per_token(config, seq),
            "chips": cell["chips"]}
