"""Runner: optimizer steps of Qwen3-Next (``qwen3_next`` blocks: three Gated
DeltaNet layers to one of gated softmax attention at head 256, every FFN a
softmax router over 512 experts beside a gated shared expert) back to back
through ``deepspeed_tpu.initialize``, on one chip that holds a share of the
experts and of the vocabulary.

The training runner's flow (``train_steps_ling3_flash.py``) for a tenth
architecture: the published keys go through ``Qwen3NextPolicy.
config_from_hf`` with the router at its published width, and the deployment's
share (the file's ``num_experts`` held, the first of the chips that share a
layer) is set on the result; the plain reference is ``reference/
qwen3_next.py``, the FLOP count ``qwen3next_cost.py``. Seeded fp32 parameters
made on the host (the embedding rows at the file's ``embedding_std``) and
placed by the engine; bf16, AdamW, chunked cross-entropy, recomputation as the
file says. Fresh seeded token ids every step out of the vocabulary slice, one
document a sequence, no gradient accumulation, the loss read each step.

The reference runs FIRST, on the host-made parameters put on the chip for it
alone (7.5 GB of training state and a float32 gradient pass over 32,768
positions do not share the chip); what it gives goes to the host as numpy and
the chip is emptied; only then is the engine built. Another copy of the
training runner's window loop (ROADMAP D12); the Kimi-VL runner's
``adamw_first_step``, the LFM2 runner's ``first_moment`` and the Ling-3.0
runner's ``logit_positions`` and ``beyond_rounding`` are imported.
"""

import gc
import time

import numpy as np

from benchmark import qwen3next_cost, traffic as gen
from benchmark.reference import qwen3_next as reference
from benchmark.runners.train_steps_kimi_vl import LR, adamw_first_step
from benchmark.runners.train_steps_lfm2_moe import ADAM_B1, first_moment
from benchmark.runners.train_steps_ling3_flash import beyond_rounding, logit_positions

# ``correct`` is decided on what the timed program gave at the timed sizes:
# the first call of the fused step on the first batch of 1 x 32,768 tokens (its
# loss, its gradients as AdamW's first moment holds them after one step from
# zero, the parameters it wrote, its router's expert counts, its
# linear-attention layers' statistics and its attention layer's mean gate) and
# the forward pass of the same batch, against ``reference.step_parts`` on the
# same fp32 masters and ids. Each limit lies between what this program reads
# and what a wrong one would: the readings are ``calibrate_qwen3_next.py``'s on
# the chip at these sizes (seeds 2147480901 and 41, PR 54:
# ``readings/qwen3_next_calibration.jsonl``; PERF.md section 6 has the table):
# the sound program against a reference made wrong stands for a wrong program
# against the sound one. Below the configuration's bf16 is fp8 (every matmul's
# operands at three mantissa bits): it fails the logits, the gradients and the
# counts moved and passes the losses, the rows held and the layers'
# statistics. A reference at bf16 operands reads as the sound one does (it is
# the configuration's OWN precision) and is required of nothing.
# **The state carried in bf16 is NOT told apart, and no limit here pretends
# to**: it reads 1.08 times the sound reference on the logits (1.114e-2
# against 1.035e-2) and on the worst gradient leaf (3.48e-2 against 3.21e-2),
# the second seed 1.126e-2 against 1.048e-2 and 3.96e-2 against 3.27e-2. At this gate
# (mean decay 0.826 a token: a state forgets in some six tokens) the rounding
# of a carried state does not pile up as it does under Ling-3.0's bounded gate
# (mean decay 0.994, where the same wrong model read twice the sound one); a
# limit threaded through an 8% gap would refuse sound runs on the driver's
# seeds. What holds the kernels' float32 state instead: the interpreted
# kernels against the recurrence to 4e-7 (``tests/unit/ops/test_gdn.py``).
#
# (a) The loss at initialisation and after one optimizer step on the same
# batch: read 5.4e-6 and 5.2e-6 of the loss (the second seed 8.8e-7 and 4.1e-6); the
# harness's limit for every training cell leaves that two hundred times over.
# A sigmoid for the SiLU reads 1.6e-3 after the step, ``1 + w`` 1.7e-3,
# ``beta`` doubled 7.3e-4 (fp8 7.8e-5: a loss near ln 18,992 hardly sees the
# precision). The second loss must be lower than the first.
LOSS_RTOL = 1e-3
LOSS_AFTER_RTOL = 1e-3
# (b) The logits at 256 positions (``logit_positions``), three quarters of
# them in the sequence's last quarter, relative L2 over the vocabulary
# position by position, by their median and 90th percentile. The median reads
# 1.035e-2 and 1.048e-2 on the two seeds: no attention gate 5.7e-2, the top 10 not
# renormalised 7.1e-2, fp8 9.3e-2, ``beta`` doubled 0.24, the shared expert
# ungated 0.49, ``1 + w`` 0.57, the key heads tiled 0.63, a sigmoid for the
# SiLU 0.71, no softplus NaN (its decay passes 1 and the state leaves
# float32); the limit 2.4 times over the reading and 2.3 under the least of
# those. (The rotary embedding over all 256 lanes reads 1.29e-2: only 64
# positions' worth of phase differs at theta 1e7, and the gradients tell it.)
# The 90th percentile reads 1.225e-2 and 1.282e-2: no attention gate 6.1e-2,
# fp8 1.0e-1; the limit 2.4 over, 2.0 under.
LOGIT_MEDIAN_RTOL = 2.5e-2
LOGIT_P90_RTOL = 3.0e-2
# (c) The step's gradients against ``jax.grad`` of the reference, relative L2
# leaf by leaf, by kind. Outside the expert blocks (embedding, head, norms,
# the mixers) the worst leaf reads 3.21e-2, layer 0's ``A_log`` (``dt_bias``
# 3.12e-2, ``in_proj_ba`` 2.62e-2, ``conv_weight`` 2.46e-2, ``in_proj_qkvz``
# 2.36e-2, ``norm_weight`` 2.14e-2, the doubled ``q_proj`` 1.79e-2; the second seed 3.27e-2 at layer 2's
# ``dt_bias``):
# not renormalised 0.18, fp8 0.42, ``beta`` doubled 0.65, the shared expert
# ungated 0.90, rope over all lanes 1.02 (``k_proj``), no attention gate 1.11
# (``q_proj``: half of it has no gradient), the others above 1.2. The limit
# 2.5 over the reading and 2.2 under the least. Inside the blocks (the norm
# the router reads, the held w1 / w3 / w2, the shared expert and its gate)
# 0.115 and 0.113: no attention gate 0.25, fp8 0.33, the shared expert
# ungated inf (its gate has no gradient on one side); the limit 1.7 over, 1.65
# under fp8. The routers' own kernels by their median layer 0.111
# and 0.115: fp8 0.34, not renormalised 5.5. (What the routed leaves'
# distance is made of, flipped near-ties, the Kimi-VL runner says.) The
# parameters written against AdamW's first step on those gradients, leaf by
# leaf, float32's rounding of the sum taken out (``beyond_rounding``): read
# 6.6e-6; a leaf not written reads 0.96 or more.
GRAD_RTOL = 8e-2
GRAD_ROUTED_RTOL = 2.0e-1
GRAD_ROUTER_RTOL = 2.0e-1
UPDATE_RTOL = 1e-3
# (d) The per-expert counts over the router's 512 experts against the
# reference's: the sum exact (tokens * top_k * layers = 1,310,720). The
# assignments that moved between the experts' counts read 1.18e-3 and
# 1.20e-3 of all: fp8 3.6e-3, the key heads tiled 8.0e-3, no attention gate
# 1.1e-2 (not renormalised 2.3e-3: the choice is the same top 10, the logits
# and gradients tell it); the rows sent to the experts held agree within
# 1.0e-3 (82,385 against 82,303; the second seed 82,878 against 82,861): ``1 + w`` 8.3e-3, a sigmoid for
# the SiLU 5.1e-2 (fp8 2.3e-3 passes); no layer took the pass over all rows.
COUNT_MOVED_SHARE = 2.5e-3
ROWS_HELD_RTOL = 6e-3
# (e) ``gdn_stats`` against the reference's: the largest |S| at the chunk
# ends within 1e-4 (``1 + w`` 4.11 for 3.55, a sigmoid for the SiLU 4.56, the
# key heads tiled 5.15, ``beta`` doubled 6.60), the mean decay ``exp(g)``
# within 6.7e-6, the mean ``beta`` within 2.2e-5 and the attention layer's
# mean ``sigmoid(gate)`` within 3.0e-6 (``beta`` doubled 0.5, a sigmoid for
# the SiLU 4.6e-3, ``1 + w`` 1.8e-3; fp8 1.9e-4 passes; the second seed's largest |S| 3.509
# against 3.488, 0.6%, the means within 2.0e-5).
STATE_ABSMAX_FACTOR = 1.1
GDN_MEAN_RTOL = 5e-4
# A rehearsal (tests only: widths of 64 on a CPU, 96 tokens) checks the flow
# and not the chip: its sums are short, so it is held to this many times the
# limits of the losses, the logits' distances, the gradients, the counts moved
# and the rows held, and to the others as they are (but the means' limit,
# which 96 tokens of bf16 read 3e-4 from: REHEARSAL_MEAN_RTOL).
REHEARSAL_SLACK = 8.0
REHEARSAL_MEAN_RTOL = 4e-3

NAMED_LEAVES = ("in_proj_qkvz", "in_proj_ba", "A_log", "dt_bias", "conv_weight",
                "norm_weight", "q_proj", "shared_expert_gate")


def model_config(config: dict):
    """``LlamaConfig`` of the file: the published keys through the policy,
    the router at its published width, this chip's share and the training
    recipe's keys set beside it."""
    import dataclasses
    from deepspeed_tpu.module_inject.replace_policy import Qwen3NextPolicy
    cfg = Qwen3NextPolicy().config_from_hf(
        {**config, "num_experts": qwen3next_cost.router_width(config)})
    return dataclasses.replace(
        cfg, moe_experts_held=int(config["num_experts"]), moe_share_index=0,
        gdn_chunk_size=int(config["gdn_chunk_size"]),
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
    return cfg, params, time.monotonic() - t0


def reference_pass(params, ids, config: dict, at, wrong=frozenset()) -> dict:
    """The reference alone on the chip: ``reference.step_parts`` on the host
    parameters, then its loss after AdamW's first step on its own gradients
    (``ce_after``). Everything it returns is on the host."""
    import jax
    on_chip = jax.device_put(params, jax.devices()[0])
    want = reference.step_parts(on_chip, ids, config, at, wrong=wrong)
    del on_chip
    stepped = jax.tree_util.tree_map(lambda p, g: p + adamw_first_step(g),
                                     params, want["grads"])
    stepped = jax.device_put(stepped, jax.devices()[0])
    # through the same compiled program (its gradients dropped): a
    # forward-only program is one more compilation inside the set-up
    want["ce_after"] = reference.step_parts(stepped, ids, config, at, wrong=wrong,
                                            gradients=False, one_program=True)["ce"]
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
    ``before`` and ``after`` it, its router's ``stats``, its linear layers'
    (``gdn``) and its attention layer's (``attn``), the seconds it took; then the loss of a second step on
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
           "gdn": engine.gdn_stats(), "attn": engine.attn_stats(), "seconds": seconds}
    got["loss_after"] = float(engine.train_batch(iter([(ids, ids)])))
    return got


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
        if np.any(w) or np.any(g):
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
    gdn, attn = got["gdn"] or {}, got["attn"] or {}
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
            "rows_held": [int(np.sum(got["stats"]["rows_held"])), int(want["rows_held"])],
            "share_fallback": int(np.sum(got["stats"]["share_fallback"])),
            "state_absmax": [float(gdn.get("state_absmax", np.nan)), want["state_absmax"]],
            "decay_mean": [float(gdn.get("decay_mean", np.nan)), want["decay_mean"]],
            "beta_mean": [float(gdn.get("beta_mean", np.nan)), want["beta_mean"]],
            "gate_mean": [float(attn.get("gate_mean", np.nan)), want["gate_mean"]]}


def verdicts(r: dict, assigned: int, experts: int, held: int,
             slack: float = 1.0) -> dict:
    """Each part of ``correct`` that the readings decide, by the limits
    above: what ``run`` reports and what the calibration holds every wrong
    reference to. NaN fails (no comparison with it holds)."""
    rows = r["rows_held"]
    counts = np.asarray(r["counts"][0])
    top = r["state_absmax"]
    means = GDN_MEAN_RTOL if slack == 1.0 else REHEARSAL_MEAN_RTOL
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
                        and counts.shape == (experts, )
                        and rows[0] == int(counts[:held].sum())
                        and r["moved"] <= slack * COUNT_MOVED_SHARE * assigned
                        and abs(rows[0] - rows[1]) <= slack * ROWS_HELD_RTOL * max(rows[1], 1)
                        and r["share_fallback"] == 0),
        "gdn": bool(top[1] / STATE_ABSMAX_FACTOR <= top[0] <= top[1] * STATE_ABSMAX_FACTOR
                    and all(abs(got - want) <= means * want
                            for got, want in (r["decay_mean"], r["beta_mean"],
                                              r["gate_mean"])))}


def run(*, cell, config, seed, seconds, trace, rehearse, t_start, device,
        compiles, out_dir, log) -> dict:
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.observability import get_registry

    tr = cell["traffic"]
    rows, seq = int(tr["global_batch"]), int(tr["seq_len"])
    layer_cfg, params, t_init = host_parameters(config, seed)
    n_params = qwen3next_cost.param_count(config)
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
        f"{n_params / 1e9:.3f}B parameters, {qwen3next_cost.bytes_at_rest(config) / 1e9:.2f} "
        f"GB at rest, {cfg.experts_held_} of {cfg.num_local_experts} experts held, "
        f"top-{top_k}, vocabulary "
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
    gc.collect()    # 7.5 GB of host arrays: freed now, not inside the window
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
        f"{slack * COUNT_MOVED_SHARE:g}), rows held {held[0]} against the "
        f"reference's {held[1]} ({100.0 * held[0] / assigned:.2f}% of all; "
        f"{r['share_fallback']} layers took the pass over all rows): {said['routing']}; "
        f"largest |S| {r['state_absmax'][0]:.4f} against {r['state_absmax'][1]:.4f} "
        f"(within x{STATE_ABSMAX_FACTOR:g}), mean decay {r['decay_mean'][0]:.5f} against "
        f"{r['decay_mean'][1]:.5f}, mean beta {r['beta_mean'][0]:.5f} against "
        f"{r['beta_mean'][1]:.5f}, the attention gate's mean {r['gate_mean'][0]:.5f} against "
        f"{r['gate_mean'][1]:.5f} (limit "
        f"{REHEARSAL_MEAN_RTOL if rehearse else GDN_MEAN_RTOL:g}): {said['gdn']}; first step "
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
    gdn_now = engine.gdn_stats() or {}
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
             "assignments_moved": r["moved"], "rows_held_first_batch": held,
             "rows_held_pct_first_batch": 100.0 * held[0] / assigned,
             "rows_held_pct_last_step": 100.0 * held_last / assigned,
             "rows_held_pct_traced_steps": [100.0 * h / assigned for h in held_samples],
             "busiest_expert_over_mean_first_batch": float(counts.max() / counts.mean()),
             "share_fallback_layers": fell_back_total,
             "gdn_stats_first_batch": {k: r[k] for k in ("state_absmax", "decay_mean",
                                                         "beta_mean", "gate_mean")},
             "gdn_stats_last_step": {k: float(v) for k, v in gdn_now.items()},
             "remat_kept_bytes": {m.labels.get("key", ""): m.value
                                  for m in reg.series("ds_remat_kept_bytes")},
             "model_layers": {m.labels["kind"]: m.value
                              for m in reg.series("ds_model_layers")},
             "verdicts": ok, "expert_counts": r["counts"][0],
             "loss_last": losses[-1], "step_programs": programs,
             "n_params": n_params,
             "bytes_in_use": [int((d.memory_stats() or {}).get("bytes_in_use", 0))
                              for d in jax.devices()[:cell["chips"]]]}
    # a fallback inside the window (or before it) fails the run: the share's
    # static rows are twice its even share of the assignments; a rehearsal's
    # 96 tokens over 2 experts of 16 outrun that now and then: there it is
    # only reported
    correct = (all(ok.values()) and finite and programs == 1
               and (rehearse or fell_back_total == 0))
    mean_held = float(np.mean(held_samples)) if held_samples else float(held[0])
    return {"correct": correct, "attempted": len(step_s),
            "failed": 0 if finite else 1, "end_to_end": e2e, "notes": notes,
            "setup": setup, "trace_steps": min(n_trace, len(step_s)),
            "tokens_per_step": rows * seq, "moe_load_samples": load_samples,
            "moe_rows_held_samples": held_samples,
            "moe_rows_per_step": mean_held / moe_layers,
            # the experts' part by the rows they held in fact, not the expectation
            "train_flops_per_token": qwen3next_cost.train_flops_per_token(
                config, seq, mean_held / moe_layers / (rows * seq)),
            "chips": cell["chips"]}
