"""Runner: optimizer steps of an SDAR-MoE decoder under block-diffusion
training, back to back through ``deepspeed_tpu.initialize``, on one chip
that holds a share of the experts and of the vocabulary.

The training runner's flow (``train_steps_lfm2_moe.py``) for a fifth
architecture: the published keys go through ``SdarMoePolicy.config_from_hf``
with the router at its published width, and the deployment's share (the
file's ``num_experts`` held, the first of the chips that share a layer) is
set on the result; the plain reference is ``reference/sdar_moe.py``, the
FLOP count ``sdar_cost.py``. Seeded fp32 parameters made on the host and
placed by the engine; bf16, AdamW, chunked cross-entropy, recomputation as
the file says. Fresh seeded token ids every step (rows 0 .. mask id - 1 of
the vocabulary slice), noised by the PROGRAM (``runtime/data_pipeline/
block_diffusion.py``: the engine's iterator, seeded by ``--seed`` and the
step), no gradient accumulation, the loss read each step.

``train_tok_s`` counts DATA tokens (``rows * L`` a step): each is two
positions through the layers and one through the head.

The reference runs FIRST, on the host-made parameters put on the chip for it
alone (the Granite runner's order: 10.3 GB of training state and a float32
gradient pass over 16,384 positions never share the chip), on the first
batch as the program's noiser made it. Everything it gives goes to the host
as numpy and the chip is emptied; only then is the engine built. A fifth
copy of the training runner's window loop (ROADMAP D12): what the LFM2 and
LFM2 runner's ``first_moment`` and AdamW constants are imported.
"""

import gc
import time

import numpy as np

from benchmark import sdar_cost, traffic as gen
from benchmark.lfm2_cost import router_width
from benchmark.reference import sdar_moe as reference
from benchmark.runners.train_steps_lfm2_moe import ADAM_B1, ADAM_EPS, first_moment

# AdamW's rate. The other training cells step at 1e-4; here the first step of
# AdamW (every parameter moves by the rate, whatever its gradient) RAISED the
# loss of the batch it was taken on at one seed of two (10.653 -> 11.435,
# program and reference alike; my chip runs, PR 37): 645M coherent moves of
# 1e-4 outweigh the first-order gain. 1e-5 is also what continued training of
# a trained checkpoint, which this cell stands for, runs at.
LR = 1e-5

# ``correct`` is decided on what the timed program gave at the timed sizes:
# the first call of the fused step on the first batch of 2 x 8,192 data
# tokens (its loss, its gradients as AdamW's first moment holds them after
# one step from zero, ``mu / (1 - b1)``, the parameters it wrote, its
# router's counts, its masked-token count) and the forward pass of the same
# batch, against ``reference.step_parts`` on the same fp32 masters, the same
# ids and the same noise. Each limit lies between what this program reads and
# what a wrong one would: the readings are ``calibrate_sdar_moe.py``'s on the
# chip at these sizes (seeds 2147480801 and 37, PR 37:
# ``readings/sdar_moe_calibration.jsonl``; PERF.md section 6 has the table),
# where the sound program against a reference made wrong stands for a wrong
# program against the sound reference. The precision below the configuration's
# bf16 is fp8 (every matmul's operands at three mantissa bits); a reference at
# bf16 operands reads as the sound one does (median 1.0e-2 and 1.2e-2, worst
# leaf 1.2e-1 and 2.3e-1) and is required of nothing. The readings are large
# for six layers: the masked positions, where the logits are read and the loss
# lives, hold the one fresh mask row (norm 0.9 where the other rows' is 45),
# so their stream is their attention context and every rounding of it counts.
#
# (a) The loss at initialisation and after one optimizer step on the same
# batch. The loss is a 1/t-weighted sum, so a few heavy tokens carry much of
# it (weights up to 1,000), but both sides weigh the same tokens alike: read
# 1.6e-4 and 2.2e-4 of the loss at initialisation, 2.3e-4 and 5.1e-4 after
# the step. Unit weights read 1.1, a causal mask 1.4e-3 and 8e-3, labels
# shifted by one 1.8e-3 and 2.2e-3, the leak 1.5e-3 after its step on one
# seed (at initialisation every target is as likely as any other: it is the
# logits and the gradients that tell a shift and the leak). The second loss
# must be lower than the first, and the reference's second loss is taken
# after ITS OWN AdamW step.
LOSS_RTOL = 1e-3
# The loss after the step is the first one less a hundredth, and that
# hundredth rides on the batch's heaviest tokens (a token of weight 500 is
# two thirds of the gradient's squared norm): program and reference read
# 2.8e-5 to 9.8e-4 apart over nine seeds (my chip runs, PR 37), so the second
# loss has three times that; a causal mask reads 8e-3, unit weights 1.0.
LOSS_AFTER_RTOL = 3e-3
# (b) The logits (bf16 compute, float32 out) at LOGIT_POSITIONS masked
# positions, spread evenly over the masked positions of each sequence (so
# over all depths of clean context, from block 0 to block 2,047), relative
# L2 over the vocabulary position by position, by their median and 90th
# percentile: routing is a discontinuity (LFM2's runner says why two order
# statistics and no margin filter). The median reads 1.11e-2 and 1.18e-2; with
# the leak (a noisy query that sees its own clean block) 2.8e-2 and 3.9e-2, in
# fp8 2.0e-1 and 2.1e-1, without the renormalisation 4.1e-1, with clean
# queries that see noisy keys 5.2e-1, under a causal mask 1.4. The 90th
# percentile reads 1.04e-1 and 1.07e-1 (a tenth of the positions feel a
# flipped assignment); the leak 2.3e-1 and 3.1e-1, fp8 4.3e-1 and 5.2e-1.
# Both limits leave the sound readings 1.9 times of room (a fresh seed reads
# higher, and one false ``correct`` refuses a PR) and lie under the leak's
# least by 1.27 and 1.16, under fp8's by 9 and 2.1.
LOGIT_POSITIONS = 256
# Over the seven seeds of the cell's own runs since (my chip runs, PR 37) the
# median read 9.7e-3 to 1.27e-2 and the 90th percentile 1.5e-2 to 1.44e-1
# (it has two values: a tenth of the positions feel a flip, or they do not):
# the median's limit keeps 1.7 times of room, the percentile's was raised to
# twice its largest reading and still lies 1.4 under fp8's least; the leak is
# the median's to tell (1.27 and 1.8 over its limit).
LOGIT_MEDIAN_RTOL = 2.2e-2
LOGIT_P90_RTOL = 3e-1
# The step's gradients against ``jax.grad`` of the reference, relative L2
# leaf by leaf, by kind. Outside the expert blocks (embedding, head, norms,
# attention) the worst leaf reads 1.51e-1 and 2.48e-1 (the embedding, whose
# gradient is mostly the mask row's; the attention's matrices 7e-2 to 2.3e-1);
# in fp8 6.4e-1 and 6.5e-1, with the leak 3.2e-1 and 4.5e-1 (the logits tell
# it), without the renormalisation 2.7, with clean queries that see noisy
# keys 8.9e-1, with unit weights, shifted labels or a causal mask 1.8 to 11.
# Inside them, the norm the router reads and the held w1 / w3 / w2: 2.2e-1
# and 3.3e-1 at 7e-4 of the assignments moved (fp8 8.5e-1 and 8.6e-1; 1.4 and
# more the other ways). The routers' own kernels are sums of terms of either
# sign and one layer's can read over 1 on a sound step (1.16 at one seed of
# nine before the mask row was drawn small), so they are judged by the median
# layer: 1.5e-1 and 2.5e-1 (fp8 6.4e-1 and 8.7e-1; a kernel with no gradient
# at all reads 1). Each limit 1.8 to 2 times over the reading and 1.4 to 1.7
# under fp8's least.
# BUT a seventh of the cell's own runs since (one seed of seven) read 7.1e-1,
# 7.3e-1 and 5.1e-1 with every leaf from the first layer's attention on
# between 4e-1 and 7.6e-1, its logits and losses as sound as the others':
# under 1/t weights the gradient is a few heavy tokens' (a masked token of
# t = 0.002 carries two thirds of its squared norm), and where such a
# token's assignment flips between bf16 and float32 its whole path differs.
# No statistic over the leaves cures that, so these limits are backstops
# just under what NO gradient reads (1.0): they tell labels shifted by one
# (1.8, 2.1 and 1.5), unit weights (9), a causal mask (11) and a router that
# does not renormalise (2.7, 5.6, 4.9); the precision and the leak are the
# logits' to tell, and the routing's.
GRAD_RTOL = 9.5e-1
GRAD_ROUTED_RTOL = 9.5e-1
GRAD_ROUTER_RTOL = 9e-1
# The parameters the step wrote against AdamW's first step from zero moments
# on those gradients (``-lr g / (|g| + eps)``, no decay), float32 on both
# sides: 4.3e-5 (the last places of ``p + update`` weigh ten times what they
# do at the other cells' rate of 1e-4); a rule without the bias correction or
# an ascent reads 1 to 2.
UPDATE_RTOL = 1e-3
# (c) The per-expert assignment counts of the first batch, over the router's
# 128 experts, against the reference's: both sum to positions * top_k *
# layers = 1,572,864 (nothing dropped), the assignments that moved between
# experts stay under COUNT_MOVED_SHARE of all (read: 7.1e-4 and 7.7e-4; fp8
# 6.3e-3 and 9.2e-3, clean queries that see noisy keys 4.0e-2, no
# renormalisation 2.1e-2, a causal mask 0.36), the rows sent to the experts
# held agree within ROWS_HELD_RTOL (read: 1e-4; fp8 1.5e-3 and 4.8e-3), no
# layer took the pass over all rows, and the masked tokens the program
# counted are the batch's.
COUNT_MOVED_SHARE = 2.5e-3
ROWS_HELD_RTOL = 5e-3
# A rehearsal (tests only: widths of 64 on a CPU, 128 data tokens) checks the
# flow and not the chip: its sums are short, so it is held to this many times
# the limits of the logits' distances, the gradients, the assignments moved
# and the rows held, and to the others as they are.
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
    from deepspeed_tpu.module_inject.replace_policy import SdarMoePolicy
    cfg = SdarMoePolicy().config_from_hf({**config, "num_experts": router_width(config)})
    return dataclasses.replace(
        cfg, moe_experts_held=int(config["num_experts"]), moe_share_index=0,
        diffusion_t_min=float(config["t_min"]),
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
    # says why: a table born at 0.02 leaves every position's stream the same
    # attention average, and the router then sends whole batches one way)
    table = params["model"]["embed_tokens"]
    born = table["embedding"].std()
    rows = table["embedding"] * np.float32(float(config["embedding_std"]) / born)
    # but for the mask id's row, which an adapted checkpoint adds fresh
    mask = cfg.diffusion_mask_id_
    rows[mask] = table["embedding"][mask] * np.float32(
        float(config["mask_row_std"]) / born)
    table["embedding"] = rows
    return cfg, params, time.monotonic() - t0


def noiser(cfg, seed: int):
    """The program's own noising, as the engine builds it from its config's
    ``seed``: step ``n``'s draw is the same here and there."""
    from deepspeed_tpu.runtime.data_pipeline import BlockDiffusionNoiser
    return BlockDiffusionNoiser(cfg.diffusion_block_length, cfg.diffusion_mask_id_,
                                cfg.diffusion_t_min, seed=seed)


def logit_positions(batch) -> np.ndarray:
    """[rows, n]: LOGIT_POSITIONS masked positions in all, evenly spread
    over each sequence's masked positions."""
    rows = batch.weights.shape[0]
    n = LOGIT_POSITIONS // rows
    at = []
    for w in batch.weights:
        masked = np.flatnonzero(w > 0)
        at.append(masked[np.linspace(0, masked.size - 1, min(n, masked.size)).astype(int)])
    n = min(a.size for a in at)
    return np.stack([a[:n] for a in at])


def reference_pass(params, batch, config: dict, at, wrong=frozenset()) -> dict:
    """The reference alone on the chip: ``reference.step_parts`` on the host
    parameters, then its loss after AdamW's first step on its own gradients
    (``ce_after``). Everything it returns is on the host."""
    import jax
    on_chip = jax.device_put(params, jax.devices()[0])
    want = reference.step_parts(on_chip, batch, config, at, wrong=wrong)
    del on_chip
    stepped = jax.tree_util.tree_map(lambda p, g: p + adamw_first_step(g),
                                     params, want["grads"])
    stepped = jax.device_put(stepped, jax.devices()[0])
    want["ce_after"] = reference.step_parts(stepped, batch, config, at, wrong=wrong,
                                            gradients=False)["ce"]
    del stepped
    want["peak_bytes"] = int((jax.devices()[0].memory_stats() or {})
                             .get("peak_bytes_in_use", 0))
    return want


def build_engine(cell, config, params, seed: int):
    """-> (engine, its ``LlamaConfig``, seconds of ``initialize`` and
    placement): the host parameters placed by the engine on the cell's
    chips; the config's ``seed`` is the noise's."""
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.comm import reset_mesh_context
    from deepspeed_tpu.comm.mesh import MeshContext, set_mesh_context
    from deepspeed_tpu.models.llama import LlamaForCausalLM

    cfg = model_config(config)
    ds_config = {"train_batch_size": int(cell["traffic"]["global_batch"]),
                 "optimizer": {"type": "AdamW", "params": {"lr": LR}},
                 "bf16": {"enabled": True}, "steps_per_print": 0, "seed": seed,
                 **config["ds_config"]}
    # the engine adopts a mesh that exists: the cell's chips and no more
    reset_mesh_context()
    set_mesh_context(MeshContext.create(devices=jax.devices()[:cell["chips"]]))
    t0 = time.monotonic()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(cfg), model_parameters=params, config=ds_config)
    jax.block_until_ready(engine.params)
    return engine, cfg, time.monotonic() - t0


def first_step(engine, batch, at) -> dict:
    """The timed program on the first batch: the forward pass's logits at
    ``at``, then the fused step's first call: its ``loss``, its ``grads``
    (out of AdamW's first moment), the parameters ``before`` and ``after``
    it, its router's and its objective's ``stats``, the seconds it took;
    then the loss of a second step on the same batch (``loss_after``).
    numpy, float32."""
    import jax

    def host(tree):
        return jax.tree_util.tree_map(np.asarray, tree)

    logits = engine.eval_batch(batch.input_ids, positions=batch.positions)
    logits = np.stack([np.asarray(logits[row, at[row]], np.float32)
                       for row in range(at.shape[0])])
    before = host(engine.params)
    t0 = time.monotonic()
    loss = float(engine.train_batch(iter([batch])))
    jax.block_until_ready(engine.params)
    seconds = time.monotonic() - t0
    grads = host(jax.tree_util.tree_map(
        lambda m: np.asarray(m, np.float32) / (1 - ADAM_B1),
        first_moment(engine.opt_state)))
    got = {"logits": logits, "loss": loss, "grads": grads, "before": before,
           "after": host(engine.params), "stats": engine.moe_stats(),
           "diffusion": engine.diffusion_stats(), "seconds": seconds}
    got["loss_after"] = float(engine.train_batch(iter([batch])))
    return got


def readings(got: dict, want: dict) -> dict:
    """Every distance ``correct`` is decided on, between the program's first
    step (``first_step``) and the reference's (``reference_pass``)."""
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
        if np.any(w) or np.any(g):      # a leaf no row reached has none
            grad_err[jax.tree_util.keystr(path)] = norm(g - w) / norm(w)
        update = adamw_first_step(g)
        off_sq += norm(new - (old + update))**2
        update_sq += norm(update)**2
    router = {n: e for n, e in grad_err.items() if "['gate']" in n}
    routed = {n: e for n, e in grad_err.items() if n not in router
              and ("block_sparse_moe" in n or "post_attention_layernorm" in n)}
    dense = {n: e for n, e in grad_err.items() if n not in routed and n not in router}
    counts = (np.asarray(got["stats"]["expert_counts"], np.int64),
              np.asarray(want["counts"], np.int64))
    return {"logit_median": float(np.quantile(err, 0.5)),
            "logit_p90": float(np.quantile(err, 0.9)), "logit_worst": float(err.max()),
            "grad_worst": max(dense.items(), key=lambda kv: kv[1]),
            "grad_routed_worst": max(routed.items(), key=lambda kv: kv[1]),
            "grad_router_median": float(np.median(list(router.values()))),
            "grad_router_worst": max(router.items(), key=lambda kv: kv[1]),
            "grad_err": grad_err, "update_err": float(np.sqrt(off_sq / update_sq)),
            "loss_err": abs(got["loss"] - want["ce"]) / abs(want["ce"]),
            "loss_after_err": (abs(got["loss_after"] - want["ce_after"])
                               / abs(want["ce_after"])),
            "descends": bool(got["loss_after"] < got["loss"]),
            "counts": [c.tolist() for c in counts],
            "assigned": [int(c.sum()) for c in counts],
            "moved": int(np.abs(counts[0] - counts[1]).sum()) // 2,
            "rows_held": [int(np.sum(got["stats"]["rows_held"])), int(want["rows_held"])],
            "share_fallback": int(np.sum(got["stats"]["share_fallback"])),
            "masked_tokens": [int(got["diffusion"]["masked_tokens"]),
                              int(want["masked_tokens"])]}


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
                      and r["update_err"] <= UPDATE_RTOL),
        "routing": bool(r["assigned"] == [assigned, assigned]
                        and counts.shape == (experts, )
                        and rows[0] == int(counts[:held].sum())
                        and r["moved"] <= slack * COUNT_MOVED_SHARE * assigned
                        and abs(rows[0] - rows[1]) <= slack * ROWS_HELD_RTOL * max(rows[1], 1)
                        and r["share_fallback"] == 0),
        "masked": bool(r["masked_tokens"][0] == r["masked_tokens"][1])}


def run(*, cell, config, seed, seconds, trace, rehearse, t_start, device,
        compiles, out_dir, log) -> dict:
    import jax
    from deepspeed_tpu.observability import get_registry

    tr = cell["traffic"]
    rows, seq = int(tr["global_batch"]), int(tr["seq_len"])
    layer_cfg, params, t_init = host_parameters(config, seed)
    n_params = sdar_cost.param_count(config)
    # data ids from the slice's rows below the mask id
    batches = gen.token_batches(seed, rows, seq, layer_cfg.diffusion_mask_id_)
    first = noiser(layer_cfg, seed)(next(batches), 0)
    at = logit_positions(first)

    # correctness, all on the first batch: the reference before the engine
    # exists (the docstring says why)
    t0 = time.monotonic()
    want = reference_pass(params, first, config, at)
    t_reference = time.monotonic() - t0
    jax.clear_caches()      # the reference's programs hold nothing more

    engine, cfg, t_place = build_engine(cell, config, params, seed)
    del params
    top_k, layers = cfg.num_experts_per_tok, cfg.num_hidden_layers
    assigned = rows * 2 * seq * top_k * layers
    log(f"training: depth {layers} (attention+moe; {n_params / 1e9:.3f}B parameters, "
        f"{cfg.experts_held_} of {cfg.num_local_experts} experts held, top-{top_k}, "
        f"vocabulary {cfg.vocab_size}, blocks of {cfg.diffusion_block_length}), mesh "
        f"{dict(engine.mesh_ctx.mesh.shape)}, batch {rows} x {seq} data tokens "
        f"({rows} x {2 * seq} positions); host init {t_init:.1f} s, reference "
        f"{t_reference:.1f} s (peak {want['peak_bytes'] / 1e9:.2f} GB), "
        f"initialize+place {t_place:.1f} s")

    def step() -> float:
        # raw ids: the engine's iterator noises them (ds.train.noise)
        return float(engine.train_batch(iter([next(batches)])))

    def rows_held() -> int:
        return int(np.sum(engine.moe_stats()["rows_held"]))

    t0 = time.monotonic()
    got = first_step(engine, first, at)
    t_program = time.monotonic() - t0 - got["seconds"]
    t0 = time.monotonic()
    r = readings(got, want)
    del want["grads"], got["grads"], got["before"], got["after"]
    gc.collect()    # 10 GB of host arrays: freed now, not inside the window
    t_check = t_reference + t_program + time.monotonic() - t0
    losses = [got["loss"], got["loss_after"]]
    for _ in range(int(tr["warmup_steps"])):
        losses.append(step())
    jax.block_until_ready(engine.params)
    slack = REHEARSAL_SLACK if rehearse else 1.0
    ok = verdicts(r, assigned, cfg.num_local_experts, cfg.experts_held_, slack)
    said = {name: "ok" if good else "FAILED" for name, good in ok.items()}
    held, masked = r["rows_held"], r["masked_tokens"]
    log(f"correctness: loss {got['loss']:.5f} at initialisation and "
        f"{got['loss_after']:.5f} after one step on the same batch, float32 reference "
        f"{want['ce']:.5f} and {want['ce_after']:.5f} (relative difference "
        f"{r['loss_err']:.1e}, {r['loss_after_err']:.1e}; limits {LOSS_RTOL:g}, "
        f"{LOSS_AFTER_RTOL:g}; must descend): {said['loss']}; logits at {at.size} masked positions of {rows} "
        f"sequences, relative distance median {r['logit_median']:.3e} (limit "
        f"{slack * LOGIT_MEDIAN_RTOL:g}), 90th percentile {r['logit_p90']:.3e} (limit "
        f"{slack * LOGIT_P90_RTOL:g}), worst {r['logit_worst']:.2e}: {said['logits']}; "
        f"the step's gradients, relative distance of the worst leaf outside the "
        f"expert blocks {r['grad_worst'][1]:.3e} at {r['grad_worst'][0]} (limit "
        f"{slack * GRAD_RTOL:g}), inside them {r['grad_routed_worst'][1]:.3e} at "
        f"{r['grad_routed_worst'][0]} (limit {slack * GRAD_ROUTED_RTOL:g}), of the "
        f"routers' kernels the median layer {r['grad_router_median']:.3e} (limit "
        f"{slack * GRAD_ROUTER_RTOL:g}; worst {r['grad_router_worst'][1]:.3e}), the "
        f"parameters' change against AdamW's on those gradients {r['update_err']:.1e} "
        f"(limit {UPDATE_RTOL:g}): {said['grads']}; expert counts sum "
        f"{r['assigned'][0]} of {assigned} over {len(r['counts'][0])} experts, "
        f"{r['moved']} assignments moved against the reference "
        f"({r['moved'] / assigned:.2e} of all, limit {slack * COUNT_MOVED_SHARE:g}), "
        f"rows held {held[0]} against the reference's {held[1]} "
        f"({100.0 * held[0] / assigned:.2f}% of all; {r['share_fallback']} layers took "
        f"the pass over all rows): {said['routing']}; masked tokens {masked[0]} "
        f"against {masked[1]} of {rows * seq}: {said['masked']}; first step "
        f"{got['seconds']:.1f} s")

    # ---- the measured window ----
    gauge = get_registry().get("ds_moe_expert_load_max_over_mean")
    t_open = time.monotonic()
    setup = compiles.snapshot()
    step_s, load_samples, held_samples, masked_samples = [], [], [], []
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
            # the step has ended (its loss was read): no read waits
            held_samples.append(rows_held())
            masked_samples.append(engine.diffusion_stats()["masked_tokens"])
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
    mask_rate = reg.get("ds_diffusion_mask_rate")
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
             "grad_rel_err": r["grad_err"], "update_rel_err": r["update_err"],
             "assignments_moved": r["moved"], "rows_held_first_batch": held,
             "rows_held_pct_first_batch": 100.0 * held[0] / assigned,
             "rows_held_pct_last_step": 100.0 * held_last / assigned,
             "share_fallback_layers": (fallbacks.value if fallbacks is not None
                                       else None),
             "masked_tokens_first_batch": masked,
             "mask_rate_gauge": mask_rate.value if mask_rate is not None else None,
             "diffusion_stats_first_batch": got["diffusion"],
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
            # data tokens: what train_tok_s and the FLOPs a token count
            "tokens_per_step": rows * seq, "moe_load_samples": load_samples,
            "moe_rows_held_samples": held_samples,
            "diffusion_masked_samples": masked_samples,
            # the mean rows held a layer and step: what a weights' gradient
            # call of the grouped matmul multiplied (moe_cost.call_flops)
            "moe_rows_per_step": mean_held / layers,
            "train_flops_per_token": sdar_cost.train_flops_per_token(config, seq),
            "chips": cell["chips"]}
