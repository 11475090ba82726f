"""Runner: optimizer steps of Ling-3.0-flash (``bailing_hybrid`` blocks: five
Kimi Delta Attention layers to one of head-gated latent attention, a leading
dense layer, then a group-limited sigmoid router over 512 experts beside an
ungated shared expert) back to back through ``deepspeed_tpu.initialize``, on
one chip that holds a share of the experts and of the vocabulary.

The training runner's flow (``train_steps_kimi_vl.py``) for an eighth
architecture: the published keys go through ``BailingHybridPolicy.
config_from_hf`` with the router at its published width, and the deployment's
share (the file's ``num_experts`` held, the first of the chips that share a
layer) is set on the result; the plain reference is ``reference/
ling3_flash.py``, the FLOP count ``ling3_cost.py``. Seeded fp32 parameters
made on the host (the embedding rows at the file's ``embedding_std``, the
selection bias ``N(0, expert_bias_std)`` and held constant) and placed by the
engine; bf16, AdamW, chunked cross-entropy, recomputation as the file says.
Fresh seeded token ids every step out of the vocabulary slice, one document a
sequence, no gradient accumulation, the loss read each step.

The reference runs FIRST, on the host-made parameters put on the chip for it
alone (9.2 GB of training state and a float32 gradient pass over 16,384
positions do not share the chip); what it gives goes to the host as numpy and
the chip is emptied; only then is the engine built. Another copy of the
training runner's window loop (ROADMAP D12); the Kimi-VL runner's
``adamw_first_step``, ``logit_positions`` and ``build_engine``'s form and the
LFM2 runner's ``first_moment`` and ``seed_selection_bias`` are imported or
followed.
"""

import gc
import time

import numpy as np

from benchmark import ling3_cost, traffic as gen
from benchmark.reference import ling3_flash as reference
from benchmark.runners.train_steps_kimi_vl import LR, adamw_first_step
from benchmark.runners.train_steps_lfm2_moe import (ADAM_B1, first_moment,
                                                    seed_selection_bias)

# ``correct`` is decided on what the timed program gave at the timed sizes:
# the first call of the fused step on the first batch of 1 x 16,384 tokens (its
# loss, its gradients as AdamW's first moment holds them after one step from
# zero, the parameters it wrote, its router's expert and group counts, its
# linear-attention layers' statistics) and the forward pass of the same batch,
# against ``reference.step_parts`` on the same fp32 masters and ids. Each limit
# lies between what this program reads and what a wrong one would: the readings
# are ``calibrate_ling3_flash.py``'s on the chip at these sizes (seeds
# 2147480901 and 41, PR 48: ``readings/ling3_flash_calibration.jsonl``; PERF.md
# section 6 has the table), a third seed's (2147483001) from the first run, a
# fourth's (2147483408, the final tree): the sound program against a reference
# made wrong stands for a wrong program against the sound one. Below the
# configuration's bf16 is fp8 (every matmul's operands at three mantissa bits):
# it fails the logits, the gradients and the counts moved and passes the
# losses, the rows held and the layers' statistics. A reference at bf16
# operands reads as the sound one does and is required of nothing. The
# subtlest wrong model is the state carried in bf16: the logits and the
# gradients outside the expert blocks tell it, nothing else does.
#
# (a) The loss at initialisation and after one optimizer step on the same
# batch: read 1.2e-5 to 3.5e-5 and 2.7e-7 to 2.6e-5 of the loss; the harness's
# limit for every training cell leaves that thirty times over. No delta term
# reads 1.4e-2 after the step, the unbounded gate 6.7e-3, one decay a head
# 4.1e-3 (fp8 6.9e-4: a loss near ln 19,648 hardly sees the precision). The
# second loss must be lower than the first.
LOSS_RTOL = 1e-3
LOSS_AFTER_RTOL = 1e-3
# (b) The logits at LOGIT_POSITIONS positions, three quarters of them in the
# sequence's last quarter (a state that has decayed and been rewritten
# thousands of times is where a wrong recurrence shows), relative L2 over the
# vocabulary position by position, by their median and 90th percentile. The
# median reads 1.94e-2 to 2.12e-2 on three seeds: the plain top 8 without
# groups 4.0e-2 and 4.4e-2, the state in bf16 4.18e-2 and 4.34e-2, no head gate
# 9.4e-2, fp8 1.59e-1 and 1.61e-1, one decay a head 6.2e-1, the unbounded gate
# 8.1e-1, no delta term 8.7e-1, no convolution on v 1.03, no L2 norm on k NaN
# (its state overflows float32); the limit 1.41 times over the reading and
# 1.33 under the least of those. The 90th percentile reads 2.13e-2 to 2.57e-2:
# the state in bf16 4.69e-2 to 4.91e-2, without groups 1.05e-1 and 1.20e-1;
# the limit 1.32 over the reading, 1.38 under the least.
LOGIT_POSITIONS = 256
LOGIT_MEDIAN_RTOL = 3.0e-2
LOGIT_P90_RTOL = 3.4e-2
# (c) The step's gradients against ``jax.grad`` of the reference, relative L2
# leaf by leaf, by kind. Outside the expert blocks (embedding, head, norms,
# the mixers, the dense FFN) the worst leaf reads 1.37e-1 to 1.57e-1, always
# the LAST KDA layer's ``f_proj`` (its ``dt_bias`` 1.3e-1 to 1.5e-1, ``A_log``
# 6.8e-2 to 9.0e-2: the gate's gradient is a sum of differences along the
# sequence, and a bf16 reference reads 1.45e-1 to 1.52e-1 there too; ``b_proj``
# 6.0e-2, the taps 5.8e-2, ``g_proj`` 5.1e-2, ``o_norm`` 5.6e-2, the MLA gate
# 5.1e-2): the state in bf16 2.45e-1 to 2.69e-1, without groups 3.3e-1, fp8
# 7.5e-1, one decay a head 4.5, the unbounded gate 5.1, no delta term 18; no
# convolution on v and no head gate leave a leaf with no gradient on one side
# (inf). The limit 1.27 over the reading and 1.22 under the least: the
# tightest of the cell, held by the state in bf16, which the logits tell as
# well. Inside the blocks (the norm the router reads, the held w1 / w3 / w2,
# the shared expert) 2.38e-1 to 2.76e-1: fp8 6.4e-1 and 6.6e-1, without groups
# 6.2e-1, no head gate 5.0e-1. The routers' own kernels by their median layer
# 3.03e-1 to 3.25e-1: fp8 7.2e-1 and 7.6e-1, without groups 7.7e-1. (What the
# routed leaves' distance is made of, flipped near-ties, the Kimi-VL runner
# says.) The parameters written against AdamW's first step on those
# gradients, leaf by leaf, float32's rounding of the sum taken out
# (``beyond_rounding``, which says why); a leaf not written reads 0.96 or more.
GRAD_RTOL = 2.0e-1
GRAD_ROUTED_RTOL = 4.2e-1
GRAD_ROUTER_RTOL = 5e-1
UPDATE_RTOL = 1e-3
# (d) The per-expert counts over the router's 512 experts and the per-group
# counts over its 8 groups against the reference's: both sums exact (tokens *
# top_k * expert layers = 655,360; tokens * topk_group * expert layers =
# 327,680). The assignments that moved between the experts' counts read
# 2.40e-3 to 2.65e-3 of all (fp8 8.4e-3 and 8.7e-3, without groups 9.6e-3 and
# 1.07e-2, no head gate 2.1e-2); the tokens that moved between the groups'
# 2.6e-4 to 3.2e-4 (fp8 1.13e-3 and 1.26e-3, no head gate 2.8e-3; the plain
# top 8 keeps the same groups' COUNTS: 3.5e-4); the rows sent to the experts
# held agree within 3e-4 to 3.3e-3 (without groups 6e-3 and 2.5e-2, one decay
# a head 2.1e-2, the unbounded gate 6.8e-2); no layer took the pass over all
# rows.
COUNT_MOVED_SHARE = 5e-3
GROUP_MOVED_SHARE = 8e-4
ROWS_HELD_RTOL = 1.5e-2
# (e) ``kda_stats`` against the reference's: the largest |S| at the chunk ends
# within 0.1% to 0.9% (no delta term 11,814 for 5.5, one decay a head 4.6, no
# convolution on v 3.2; the state in bf16 0.4% and 17%), the mean decay
# ``exp(g)`` within 6e-8 to 2.4e-7 (the unbounded gate 1.9e-1, one decay a
# head 4.5e-3) and the mean ``beta`` within 1.4e-6 to 2.8e-5.
STATE_ABSMAX_FACTOR = 1.1
KDA_MEAN_RTOL = 2e-3
# A rehearsal (tests only: widths of 64 on a CPU, 96 tokens) checks the flow
# and not the chip: its sums are short (a loss over 95 positions reads 1.3e-3
# off, 7 of 384 assignments move), so it is held to this many times the limits
# of the losses, the logits' distances, the gradients, the counts moved and
# the rows held, and to the others as they are.
REHEARSAL_SLACK = 8.0
# the reference's gradient pass compares the loss of ALL positions; a cell
# whose first set-up would pass its limit may compare the first LOSS_POSITIONS
# instead (0 = all): stated here, with the reading, if it is ever set
LOSS_POSITIONS = 0

NAMED_LEAVES = ("f_proj", "A_log", "dt_bias", "b_proj", "conv_weight", "g_proj",
                "o_norm", "gate_proj']['kernel")


def model_config(config: dict):
    """``LlamaConfig`` of the file: the published keys through the policy,
    the router at its published width, this chip's share and the training
    recipe's keys set beside it."""
    import dataclasses
    from deepspeed_tpu.module_inject.replace_policy import BailingHybridPolicy
    cfg = BailingHybridPolicy().config_from_hf(
        {**config, "num_experts": ling3_cost.router_width(config)})
    return dataclasses.replace(
        cfg, moe_experts_held=int(config["num_experts"]), moe_share_index=0,
        kda_chunk_size=int(config["kda_chunk_size"]),
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
    table = params["model"]["embed_tokens"]
    table["embedding"] = table["embedding"] * np.float32(
        float(config["embedding_std"]) / table["embedding"].std())
    params = seed_selection_bias(params, seed, float(config["expert_bias_std"]))
    return cfg, params, time.monotonic() - t0


def logit_positions(rows: int, seq: int) -> np.ndarray:
    """[rows, n]: LOGIT_POSITIONS positions in all, a quarter spread over each
    sequence's first three quarters and the rest over its last."""
    n = min(max(LOGIT_POSITIONS // rows, 1), seq - 1)
    early = np.linspace(0, 3 * (seq - 2) // 4, n // 4, endpoint=False)
    late = np.linspace(3 * (seq - 2) // 4, seq - 2, n - n // 4)
    at = np.unique(np.concatenate([early, late]).astype(int))
    return np.stack([at] * rows)


def reference_pass(params, ids, config: dict, at, wrong=frozenset()) -> dict:
    """The reference alone on the chip: ``reference.step_parts`` on the host
    parameters, then its loss after AdamW's first step on its own gradients
    (``ce_after``). Everything it returns is on the host."""
    import jax
    on_chip = jax.device_put(params, jax.devices()[0])
    want = reference.step_parts(on_chip, ids, config, at, wrong=wrong,
                                loss_positions=LOSS_POSITIONS)
    del on_chip
    stepped = jax.tree_util.tree_map(lambda p, g: p + adamw_first_step(g),
                                     params, want["grads"])
    stepped = jax.device_put(stepped, jax.devices()[0])
    # through the same compiled program (its gradients dropped): the cell's
    # first, compiling set-up read 351 s of 360 with a forward-only program too
    want["ce_after"] = reference.step_parts(stepped, ids, config, at, wrong=wrong,
                                            gradients=False, one_program=True,
                                            loss_positions=LOSS_POSITIONS)["ce"]
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
    reset_mesh_context()
    set_mesh_context(MeshContext.create(devices=jax.devices()[:cell["chips"]]))
    t0 = time.monotonic()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(cfg), model_parameters=params, config=ds_config)
    jax.block_until_ready(engine.params)
    return engine, cfg, time.monotonic() - t0


def first_step(engine, ids, at) -> dict:
    """The timed program on the first batch: the forward pass's logits at
    ``at`` (a sequence at a time), then the fused step's first call: its
    ``loss``, its ``grads`` (out of AdamW's first moment), the parameters
    ``before`` and ``after`` it, its router's ``stats`` and its linear
    layers' (``kda``), the seconds it took; then the loss of a second step on
    the same batch (``loss_after``). numpy, float32."""
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
           "kda": engine.kda_stats(), "seconds": seconds}
    got["loss_after"] = float(engine.train_batch(iter([(ids, ids)])))
    return got


def beyond_rounding(new: np.ndarray, expected: np.ndarray) -> np.ndarray:
    """``|new - expected|`` less one step of float32 at that size, not below
    zero: what lies between a master as written and ``old + update`` beyond
    the rounding of the sum. A ``dt_bias`` of -4 to -7 moves in steps of
    4.8e-7 and AdamW's first step is 1e-5, so ONE of its 4,096 elements
    rounded the other way (the engine's update and ``adamw_first_step``'s
    differ in their last bit) reads 7.5e-4 of the update's norm and two read
    1.05e-3: the plain distance read 2.6e-4 to 9.9e-4 over twenty-seven runs
    on the chip, a ``dt_bias`` above 9e-4 in five of them; with the step
    taken off every run reads 9.1e-7 to 9.2e-7. A leaf not written still
    reads 0.96 (its update against one step), a learning rate 1% off 1e-2 on
    every matrix (steps of 2e-9 there). The plain distance is in the notes."""
    d = np.abs(new - expected)
    d -= np.spacing(np.maximum(np.abs(new), np.abs(expected)))
    return np.maximum(d, 0, out=d)


def readings(got: dict, want: dict) -> dict:
    """Every distance ``correct`` is decided on, between the program's first
    step (``first_step``) and the reference's (``reference_pass``)."""
    import jax
    d = got["logits"] - want["logits"]
    err = (np.linalg.norm(d, axis=-1) / np.linalg.norm(want["logits"], axis=-1)).ravel()

    def norm(x) -> float:
        return float(np.sqrt(np.vdot(x, x)))

    grad_err, update_err, update_raw = {}, {}, {}
    for (path, g), w, old, new in zip(
            jax.tree_util.tree_flatten_with_path(got["grads"])[0],
            *(jax.tree_util.tree_leaves(tree)
              for tree in (want["grads"], got["before"], got["after"]))):
        name = jax.tree_util.keystr(path)
        if np.any(w) or np.any(g):      # the selection bias has none
            grad_err[name] = norm(g - w) / norm(w) if np.any(w) else float("inf")
        update = adamw_first_step(g)
        if np.any(update):
            expected = old + update
            update_err[name] = norm(beyond_rounding(new, expected)) / norm(update)
            update_raw[name] = norm(new - expected) / norm(update)
        else:
            update_err[name] = 0.0 if np.array_equal(new, old) else float("inf")
    moe_layers = {n.split("']['")[1] for n in grad_err if "block_sparse_moe" in n}
    router = {n: e for n, e in grad_err.items() if "['gate']" in n}
    routed = {n: e for n, e in grad_err.items() if n not in router
              and ("block_sparse_moe" in n
                   or ("ffn_norm" in n and n.split("']['")[1] in moe_layers))}
    dense = {n: e for n, e in grad_err.items() if n not in routed and n not in router}
    counts = (np.asarray(got["stats"]["expert_counts"], np.int64),
              np.asarray(want["counts"], np.int64))
    groups = (np.asarray(got["stats"].get("group_counts", ()), np.int64),
              np.asarray(want["group_counts"], np.int64))
    kda = got["kda"] or {}
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
            "update_with_rounding_worst": max(update_raw.items(), key=lambda kv: kv[1]),
            "loss_err": abs(got["loss"] - want["ce"]) / abs(want["ce"]),
            "loss_after_err": (abs(got["loss_after"] - want["ce_after"])
                               / abs(want["ce_after"])),
            "descends": bool(got["loss_after"] < got["loss"]),
            "counts": [c.tolist() for c in counts],
            "assigned": [int(c.sum()) for c in counts],
            "moved": int(np.abs(counts[0] - counts[1]).sum()) // 2,
            "group_counts": [c.tolist() for c in groups],
            "groups_kept": [int(c.sum()) for c in groups],
            "groups_moved": (int(np.abs(groups[0] - groups[1]).sum()) // 2
                             if groups[0].shape == groups[1].shape else -1),
            "rows_held": [int(np.sum(got["stats"]["rows_held"])), int(want["rows_held"])],
            "share_fallback": int(np.sum(got["stats"]["share_fallback"])),
            "state_absmax": [float(kda.get("state_absmax", np.nan)), want["state_absmax"]],
            "decay_mean": [float(kda.get("decay_mean", np.nan)), want["decay_mean"]],
            "beta_mean": [float(kda.get("beta_mean", np.nan)), want["beta_mean"]]}


def verdicts(r: dict, assigned: int, kept: int, experts: int, held: int,
             slack: float = 1.0) -> dict:
    """Each part of ``correct`` that the readings decide, by the limits
    above: what ``run`` reports and what the calibration holds every wrong
    reference to. NaN fails (no comparison with it holds)."""
    rows = r["rows_held"]
    counts = np.asarray(r["counts"][0])
    top = r["state_absmax"]
    return {
        "loss": bool(r["loss_err"] <= slack * LOSS_RTOL
                     and r["loss_after_err"] <= slack * LOSS_AFTER_RTOL and r["descends"]),
        "logits": bool(r["logit_median"] <= slack * LOGIT_MEDIAN_RTOL
                       and r["logit_p90"] <= slack * LOGIT_P90_RTOL),
        "grads": bool(r["grad_worst"][1] <= slack * GRAD_RTOL
                      and r["grad_routed_worst"][1] <= slack * GRAD_ROUTED_RTOL
                      and r["grad_router_median"] <= slack * GRAD_ROUTER_RTOL
                      and r["update_worst"][1] <= UPDATE_RTOL),
        "routing": bool(r["assigned"] == [assigned, assigned]
                        and r["groups_kept"] == [kept, kept]
                        and counts.shape == (experts, )
                        and rows[0] == int(counts[:held].sum())
                        and r["moved"] <= slack * COUNT_MOVED_SHARE * assigned
                        and 0 <= r["groups_moved"] <= slack * GROUP_MOVED_SHARE * kept
                        and abs(rows[0] - rows[1]) <= slack * ROWS_HELD_RTOL * max(rows[1], 1)
                        and r["share_fallback"] == 0),
        "kda": bool(top[1] / STATE_ABSMAX_FACTOR <= top[0] <= top[1] * STATE_ABSMAX_FACTOR
                    and all(abs(got - want) <= KDA_MEAN_RTOL * want
                            for got, want in (r["decay_mean"], r["beta_mean"])))}


def run(*, cell, config, seed, seconds, trace, rehearse, t_start, device,
        compiles, out_dir, log) -> dict:
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.observability import get_registry

    tr = cell["traffic"]
    rows, seq = int(tr["global_batch"]), int(tr["seq_len"])
    layer_cfg, params, t_init = host_parameters(config, seed)
    n_params = ling3_cost.param_count(config)
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
    kept = rows * seq * cfg.moe_topk_group * moe_layers
    log(f"training: depth {cfg.num_hidden_layers} "
        f"({'/'.join(s.operator + '+' + s.ffn for s in cfg.layer_specs)}; "
        f"{n_params / 1e9:.3f}B parameters, {ling3_cost.bytes_at_rest(config) / 1e9:.2f} GB "
        f"at rest, {cfg.experts_held_} of {cfg.num_local_experts} experts held, "
        f"top-{top_k} in {cfg.moe_topk_group} of {cfg.moe_n_group} groups, vocabulary "
        f"{cfg.vocab_size}), mesh {dict(engine.mesh_ctx.mesh.shape)}, batch {rows} x "
        f"{seq}; host init {t_init:.1f} s, reference {t_reference:.1f} s (peak "
        f"{want['peak_bytes'] / 1e9:.2f} GB), initialize+place {t_place:.1f} s")

    def step() -> float:
        batch = jnp.asarray(next(batches))
        return float(engine.train_batch(iter([(batch, batch)])))

    def stats_now():
        stats = engine.moe_stats()
        return int(np.sum(stats["rows_held"])), int(np.sum(stats["share_fallback"]))

    ids = jax.device_put(jnp.asarray(first),
                         engine.zero_plan.batch_sharding((first, ))[0])
    t0 = time.monotonic()
    got = first_step(engine, ids, at)
    t_program = time.monotonic() - t0 - got["seconds"]
    t0 = time.monotonic()
    r = readings(got, want)
    del want["grads"], got["grads"], got["before"], got["after"]
    gc.collect()    # 9 GB of host arrays: freed now, not inside the window
    t_check = t_reference + t_program + time.monotonic() - t0
    losses = [got["loss"], got["loss_after"]]
    for _ in range(int(tr["warmup_steps"])):
        losses.append(step())
    jax.block_until_ready(engine.params)
    slack = REHEARSAL_SLACK if rehearse else 1.0
    ok = verdicts(r, assigned, kept, cfg.num_local_experts, cfg.experts_held_, slack)
    said = {name: "ok" if good else "FAILED" for name, good in ok.items()}
    held = r["rows_held"]
    log(f"correctness: loss {got['loss']:.5f} at initialisation and "
        f"{got['loss_after']:.5f} after one step on the same batch, float32 reference "
        f"{want['ce']:.5f} and {want['ce_after']:.5f} (relative difference "
        f"{r['loss_err']:.1e}, {r['loss_after_err']:.1e}; limits {LOSS_RTOL:g}, "
        f"{LOSS_AFTER_RTOL:g}; must descend): {said['loss']}; logits at {at.size} "
        f"positions, relative distance median {r['logit_median']:.3e} (limit "
        f"{slack * LOGIT_MEDIAN_RTOL:g}), 90th percentile {r['logit_p90']:.3e} (limit "
        f"{slack * LOGIT_P90_RTOL:g}), worst {r['logit_worst']:.2e}: {said['logits']}; "
        f"the step's gradients, relative distance of the worst leaf outside the expert "
        f"blocks {r['grad_worst'][1]:.3e} at {r['grad_worst'][0]} (limit "
        f"{slack * GRAD_RTOL:g}), inside them {r['grad_routed_worst'][1]:.3e} at "
        f"{r['grad_routed_worst'][0]} (limit {slack * GRAD_ROUTED_RTOL:g}), of the "
        f"routers' kernels the median layer {r['grad_router_median']:.3e} (limit "
        f"{slack * GRAD_ROUTER_RTOL:g}; worst {r['grad_router_worst'][1]:.3e}), by name "
        + ", ".join(f"{leaf.split(chr(39))[0]} {e:.3e}" for leaf, e in r["grad_named"].items())
        + f", the parameters' change against AdamW's on those gradients, the worst "
        f"leaf {r['update_worst'][1]:.1e} at {r['update_worst'][0]} (limit "
        f"{UPDATE_RTOL:g}; float32's rounding of the sum counted too, "
        f"{r['update_with_rounding_worst'][1]:.1e} at "
        f"{r['update_with_rounding_worst'][0]}): {said['grads']}; expert counts sum {r['assigned'][0]} of "
        f"{assigned} over {len(r['counts'][0])} experts, {r['moved']} assignments moved "
        f"against the reference ({r['moved'] / assigned:.2e} of all, limit "
        f"{slack * COUNT_MOVED_SHARE:g}), groups kept {r['group_counts'][0]} against "
        f"{r['group_counts'][1]} ({r['groups_moved']} moved, limit "
        f"{slack * GROUP_MOVED_SHARE:g} of {kept}), rows held {held[0]} against the "
        f"reference's {held[1]} ({100.0 * held[0] / assigned:.2f}% of all; "
        f"{r['share_fallback']} layers took the pass over all rows): {said['routing']}; "
        f"largest |S| {r['state_absmax'][0]:.4f} against {r['state_absmax'][1]:.4f} "
        f"(within x{STATE_ABSMAX_FACTOR:g}), mean decay {r['decay_mean'][0]:.5f} against "
        f"{r['decay_mean'][1]:.5f}, mean beta {r['beta_mean'][0]:.5f} against "
        f"{r['beta_mean'][1]:.5f} (limit {KDA_MEAN_RTOL:g}): {said['kda']}; first step "
        f"{got['seconds']:.1f} s")

    # ---- the measured window ----
    gauge = get_registry().get("ds_moe_expert_load_max_over_mean")
    t_open = time.monotonic()
    setup = compiles.snapshot()
    step_s, load_samples, held_samples, fallbacks_in_window = [], [], [], 0
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
            rows_now, fell_back = stats_now()
            held_samples.append(rows_now)
            fallbacks_in_window += fell_back
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
    held_last, _ = stats_now()         # of the window's last step, after it

    programs = int(engine._train_step_fused._cache_size())
    finite = bool(np.isfinite(losses).all())
    tokens = len(step_s) * rows * seq
    reg = get_registry()
    fallbacks = reg.get("ds_moe_share_fallback_total")
    # every step's fallbacks, the untraced ones' too: the program's counter
    fell_back_total = int(fallbacks.value) if fallbacks is not None else fallbacks_in_window
    counts = np.asarray(r["counts"][0])
    kda_now = engine.kda_stats() or {}
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
             "update_rel_err_with_rounding_worst_leaf": r["update_with_rounding_worst"],
             "assignments_moved": r["moved"], "groups_moved": r["groups_moved"],
             "group_counts": r["group_counts"], "rows_held_first_batch": held,
             "rows_held_pct_first_batch": 100.0 * held[0] / assigned,
             "rows_held_pct_last_step": 100.0 * held_last / assigned,
             "rows_held_pct_traced_steps": [100.0 * h / assigned for h in held_samples],
             "busiest_expert_over_mean_first_batch": float(counts.max() / counts.mean()),
             "share_fallback_layers": fell_back_total,
             "kda_stats_first_batch": {k: r[k] for k in ("state_absmax", "decay_mean",
                                                         "beta_mean")},
             "kda_stats_last_step": {k: float(v) for k, v in kda_now.items()},
             "remat_kept_bytes": {m.labels.get("key", ""): m.value
                                  for m in reg.series("ds_remat_kept_bytes")},
             "model_layers": {m.labels["kind"]: m.value
                              for m in reg.series("ds_model_layers")},
             "verdicts": ok, "expert_counts": r["counts"][0],
             "loss_last": losses[-1], "step_programs": programs,
             "n_params": n_params,
             "bytes_in_use": [int((d.memory_stats() or {}).get("bytes_in_use", 0))
                              for d in jax.devices()[:cell["chips"]]]}
    # a fallback inside the window (or before it) fails the run: the eight
    # held experts all lie in one routing group, so the share's static rows
    # are twice what it gets when EVERY token keeps that group (8,192 here,
    # four times the even share: twice the even share was outrun by one layer
    # of the first batch on the driver's seed 2093992587); a rehearsal's 96
    # tokens over 2 experts of 16 outrun that now and then: there it is only
    # reported
    correct = (all(ok.values()) and finite and programs == 1
               and (rehearse or fell_back_total == 0))
    mean_held = float(np.mean(held_samples)) if held_samples else float(held[0])
    return {"correct": correct, "attempted": len(step_s),
            "failed": 0 if finite else 1, "end_to_end": e2e, "notes": notes,
            "setup": setup, "trace_steps": min(n_trace, len(step_s)),
            "tokens_per_step": rows * seq, "moe_load_samples": load_samples,
            "moe_rows_held_samples": held_samples,
            "moe_rows_per_step": mean_held / moe_layers,
            "train_flops_per_token": ling3_cost.train_flops_per_token(config, seq),
            "chips": cell["chips"]}
