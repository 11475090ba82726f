"""Runner: optimizer steps of Keye-VL-2.0-30B-A3B's language model (Qwen3-MoE's
blocks whose every attention layer is a learned sparse attention: a 16-head,
64-wide indexer with one key a token scores every earlier token and each
query attends its top 2,048) back to back through ``deepspeed_tpu.
initialize``, on one chip that holds a share of the experts and of the
vocabulary.

The training runner's flow (``train_steps_kimi_vl.py``) for a seventh
architecture: the published keys go through ``KeyeVL2Policy.config_from_hf``
with the router at its published width, and the deployment's share (the
file's ``num_experts`` held, the first of the chips that share a layer) is
set on the result; the plain reference is ``reference/keye_vl2.py``, the FLOP
count ``keye_cost.py``. Seeded fp32 parameters made on the host (the
embedding rows at the file's ``embedding_std``) and placed by the engine;
bf16, AdamW, chunked cross-entropy, recomputation as the file says. Fresh
seeded token ids every step out of the vocabulary slice, no gradient
accumulation, the loss read each step.

The reference runs FIRST, on the host-made parameters put on the chip for it
alone (the Granite, SDAR and Kimi-VL runners' order: 8 GB of training state
and a float32 gradient pass over 32,768 positions do not share the chip).
What it gives goes to the host as numpy and the chip is emptied; only then is
the engine built. A seventh copy of the training runner's window loop
(ROADMAP D12); the LFM2 runner's ``first_moment`` and AdamW constants and the
Kimi-VL runner's ``adamw_first_step`` and ``logit_positions`` are imported.
"""

import gc
import time

import numpy as np

from benchmark import dsa_cost, keye_cost, traffic as gen
from benchmark.lfm2_cost import router_width
from benchmark.reference import keye_vl2 as reference
from benchmark.runners.train_steps_kimi_vl import LR, adamw_first_step, logit_positions
from benchmark.runners.train_steps_lfm2_moe import ADAM_B1, first_moment

# ``correct`` is decided on what the timed program gave at the timed sizes:
# the first call of the fused step on the first batch of 1 x 32,768 tokens (its
# loss, its gradients as AdamW's first moment holds them after one step from
# zero, the parameters it wrote, its router's counts, what its sparse
# attention chose) and the forward pass of the same batch, against
# ``reference.step_parts`` on the same fp32 masters and ids. Each limit lies
# between what this program reads and what a wrong one would: the readings are
# ``calibrate_keye_vl2.py``'s on the chip at these sizes (seeds 2147480901 and
# 41, PR 45: ``readings/keye_vl2_calibration.jsonl``; PERF.md section 6 has
# the table), and a third seed's (2147483777) from the cell's first run,
# where the sound program against a reference made wrong stands for a wrong
# program against the sound reference. The precision below the
# configuration's bf16 is fp8 (every matmul's operands, the indexer's too, at
# three mantissa bits): it fails the logits, the gradients, the assignments
# moved and the choice's statistics and passes the losses and the rows held.
# A reference at bf16 operands reads as the sound one does (median 1.50e-2,
# worst leaf 0.18 and 0.21) and is required of nothing.
#
# (a) The loss at initialisation and after one optimizer step on the same
# batch (the reference's second loss after ITS OWN AdamW step): read 3.4e-6
# to 2.2e-5 and 6.7e-6 to 1.3e-5 of the loss; the harness's limit for every
# training cell leaves that forty times over (a loss near ln 18,992 hardly
# sees a wrong model: the worst of them reads 1.1e-4). The second loss must
# be lower than the first.
LOSS_RTOL = 1e-3
LOSS_AFTER_RTOL = 1e-3
# (b) The logits (bf16 compute, float32 out) at LOGIT_POSITIONS positions
# spread evenly over the sequence (240 of the 256 lie past position 2,048,
# where the choice is a choice), relative L2 over the vocabulary position by
# position, by their median and 90th percentile (the LFM2 runner says why two
# order statistics: routing, and here the choice too, are discontinuities).
# The median reads 1.49e-2 to 1.52e-2 on three seeds: fp8 5.57e-2 and 5.60e-2,
# no ReLU 8.2e-2, the top 1,024 9.4e-2, the choice ignored 1.05e-1, the choice
# without the causal limit 1.32e-1, no head weights 1.41e-1; the limit 2.0
# over the reading and 1.86 under the least of those. The 90th percentile
# reads 4.16e-2 to 4.97e-2 (a position whose row lost a few percent of its
# keys to flipped near-ties): fp8 7.8e-2 and 8.1e-2, the others 1.08e-1 and
# more; the median tells each of them, so this limit only has to hold a tail
# no median shows, 1.4 over the reading.
LOGIT_POSITIONS = 256
LOGIT_MEDIAN_RTOL = 3.0e-2
LOGIT_P90_RTOL = 7.0e-2
# The step's gradients against ``jax.grad`` of the reference, relative L2 leaf
# by leaf, by kind. Outside the expert blocks (embedding, head, norms,
# attention's q/k/v/o and its two head norms) the worst leaf reads 1.81e-1 to
# 1.97e-1, always a deep layer's ``q_norm`` / ``k_norm`` (``q_proj`` and
# ``k_proj`` 1.74e-1 beside them, ``v_proj`` and ``o_proj`` 4e-2): what the
# near-ties at the threshold cost. In the deepest layer 2.4% of a query's
# chosen keys differ from the float32 reference's (the overlap of (d)), and
# rows that random ids leave uncorrelated then read ``sqrt(2 f)`` = 0.22; a
# bf16 REFERENCE reads the same (1.78e-1 and 2.08e-1), so it is the
# precision's and not the kernels'. fp8 4.07e-1 and 4.21e-1, the top 1,024
# 7.5e-1, no ReLU 7.9e-1, the others 0.98 and more; the limit 1.5 over the
# reading, 1.35 under fp8's least. Inside the blocks (the norm the router
# reads, the held w1 / w3 / w2) 1.21e-1 to 1.24e-1: fp8 2.20e-1 and 2.24e-1,
# no ReLU 2.8e-1. The routers' own kernels by their median layer 1.14e-1: fp8
# 2.02e-1 and 2.05e-1, no ReLU 2.8e-1; each limit 1.36 to 1.38 over the
# reading and 1.29 to 1.30 under fp8's. The indexer's five leaves a layer
# have to be EXACTLY zero on both sides (the choice passes no gradient).
GRAD_RTOL = 3.0e-1
GRAD_ROUTED_RTOL = 1.7e-1
GRAD_ROUTER_RTOL = 1.55e-1
# The parameters the step wrote against AdamW's first step from zero moments
# on those gradients (``-lr g / (|g| + eps)``, no decay), float32 on both
# sides, leaf by leaf and held by the worst leaf: 2.6e-4 on two seeds and
# 5.3e-4 on the third, always a norm's weights (the Kimi-VL runner says why:
# at 1.0 the last place of ``p + update`` is 0.6% of a 1e-5 update). A leaf
# not written reads 1, a rule without the bias correction or an ascent 1 to
# 2; a leaf with no gradient (the indexer's) has to stand as it was.
UPDATE_RTOL = 2e-3
# (c) The per-expert assignment counts of the first batch, over the router's
# 128 experts, against the reference's: both sum to tokens * top_k * layers =
# 1,572,864 (nothing dropped); the assignments that moved between the
# experts' COUNTS (half the summed differences: a net figure) read 5.94e-4 to
# 5.99e-4 of all: no ReLU 1.25e-3, the choice ignored 1.46e-3, the top 1,024
# 1.48e-3, no head weights 1.77e-3, fp8 2.77e-3 and 2.80e-3, the choice
# without the causal limit 1.5e-2; the limit 1.5 over the reading, 1.39 under
# the least. The rows sent to the experts held agree within 2.1e-4 to 3.2e-4
# (the choice without the causal limit 7.9e-3, which this limit is there
# for; fp8 9.4e-4 and 1.2e-3: not told here); no layer took the pass over all
# rows.
COUNT_MOVED_SHARE = 9e-4
ROWS_HELD_RTOL = 2.5e-3
# (d) The choice. ``chosen_pairs`` of every layer, on both sides, EXACTLY
# ``sum_t min(t + 1, topk)`` = 65,012,736 (ties go to the lower position, so
# a row takes exactly topk; 7 rows of 32,768 held such a tie in a kernel
# probe on the chip): the choice ignored reads 536,887,296, the top 1,024
# 33,030,656, the choice without the causal limit 33,586,039.
# ``kth_score_mean`` (the mean over rows and layers of a row's smallest
# chosen score, 0.400 to 0.416 by seed) within KTH_RTOL of the reference's:
# read 1.0e-4 to 2.6e-4; fp8 9.4e-4 and 1.4e-3, the top 1,024 0.73, no ReLU
# 0.84, no head weights 164; the limit 2.3 over the reading, 1.57 under fp8's
# least. The overlap ``|S_t & S_t_ref| / |S_t|`` at CHOICE_QUERIES queries
# spread over the sequence, in every layer, by the layer's mean: 0.996 in
# the first layer falling to 0.976-0.981 in the sixth (bf16's rounding of
# the stream and of the indexer's operands flips the near-ties at the
# threshold, more of them the deeper the layer; the worst single query
# 0.886): fp8 0.951 to 0.927, no ReLU 0.70 to 0.68, the top 1,024 and the
# choice without the causal limit 0.5, no head weights 0.3; in what is NOT
# shared the limit is 1.9 over the reading and 1.6 under fp8's.
CHOICE_QUERIES = 64
KTH_RTOL = 6e-4
OVERLAP_MIN = 0.955
# A rehearsal (tests only: widths of 64 on a CPU, 128 tokens, the top 32)
# checks the flow and not the chip: its sums are short, so it is held to this
# many times the limits of the logits' distances, the gradients, the
# assignments moved, the rows held and the choice's statistics, and to the
# others as they are; but for the smallest chosen score's mean, which lies
# near zero there (the top 32 of at most 128 scores of either sign), where a
# relative band means nothing: within REHEARSAL_KTH_ATOL.
REHEARSAL_SLACK = 4.0
REHEARSAL_KTH_ATOL = 5e-3


def model_config(config: dict):
    """``LlamaConfig`` of the file: the published keys through the policy,
    the router at its published width, this chip's share and the training
    recipe's keys set beside it."""
    import dataclasses
    from deepspeed_tpu.module_inject.replace_policy import policy_for
    cfg = policy_for(config["model_type"]).config_from_hf(
        {**config, "num_experts": router_width(config)})
    return dataclasses.replace(
        cfg, moe_experts_held=int(config["num_experts"]), moe_share_index=0,
        ce_chunk_size=int(config["ce_chunk_size"]), remat=bool(config["remat"]),
        remat_policy=config.get("remat_policy"))


def host_parameters(config: dict, seed: int):
    """-> (the ``LlamaConfig``, its seeded fp32 parameters as numpy on the
    host, seconds). The indexer's LayerNorm bias is born zero and stays so:
    nothing trains it here."""
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


def choice_queries(rows: int, seq: int) -> np.ndarray:
    """[rows, n]: CHOICE_QUERIES queries in all, evenly spread over each
    sequence."""
    n = min(max(CHOICE_QUERIES // rows, 1), seq)
    return np.stack([np.linspace(0, seq - 1, n).astype(int)] * rows)


def reference_pass(params, ids, config: dict, at, sample, wrong=frozenset()) -> dict:
    """The reference alone on the chip: ``reference.step_parts`` on the host
    parameters, then its loss after AdamW's first step on its own gradients
    (``ce_after``). Everything it returns is on the host."""
    import jax
    on_chip = jax.device_put(params, jax.devices()[0])
    want = reference.step_parts(on_chip, ids, config, at, sample, wrong=wrong)
    del on_chip
    stepped = jax.tree_util.tree_map(lambda p, g: p + adamw_first_step(g),
                                     params, want["grads"])
    stepped = jax.device_put(stepped, jax.devices()[0])
    want["ce_after"] = reference.step_parts(stepped, ids, config, at, sample,
                                            wrong=wrong, gradients=False)["ce"]
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


def forward_parts(engine, cfg, ids, at, sample):
    """-> (logits [rows, n, vocab] float32 at ``at``, choice [rows, layers,
    m, T] bool at ``sample``): ONE forward pass of the model as the step runs
    it (the same module, kernels and bf16 parameters), a sequence at a time
    (the float32 logits of one are 2.5 GB), with its ``dsa_choice``
    collection open: the indexer's operands and each row's smallest chosen
    score, from which ``ops.dsa_attention.chosen_keys`` rebuilds the keys a
    query chose."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    from deepspeed_tpu.ops.dsa_attention import chosen_keys
    model = LlamaForCausalLM(cfg)

    @jax.jit
    def one(params, ids, at, sample):
        compute = jax.tree_util.tree_map(lambda p: p.astype(cfg.dtype), params)
        logits, mods = model.apply({"params": compute}, ids, mutable=["dsa_choice"])
        out = []
        for i in range(cfg.num_hidden_layers):
            sown = mods["dsa_choice"]["model"][f"layers_{i}"]["self_attn"]
            qi, ki, w, kth = (sown[name][0] for name in ("qi", "ki", "w", "kth"))
            out.append(chosen_keys(qi[:, sample], ki, w[:, sample], kth[:, sample],
                                   sample[None], cfg.dsa_topk)[0])
        return logits[0, at].astype(jnp.float32), jnp.stack(out)

    parts = [one(engine.params, ids[row:row + 1], jnp.asarray(at[row]),
                 jnp.asarray(sample[row])) for row in range(at.shape[0])]
    return tuple(np.stack([np.asarray(p[i]) for p in parts]) for i in (0, 1))


def first_step(engine, cfg, ids, at, sample) -> dict:
    """The timed program on the first batch: the forward pass's logits at
    ``at`` and its sparse attention's ``choice`` at ``sample``
    (``forward_parts``), then the fused step's first call: its ``loss``, its
    ``grads`` (out of AdamW's first moment), the parameters ``before`` and
    ``after`` it, its router's ``stats`` and its sparse attention's
    (``dsa``), the seconds it took; then the loss of a second step on the
    same batch (``loss_after``). numpy, float32."""
    import jax

    def host(tree):
        return jax.tree_util.tree_map(np.asarray, tree)

    logits, choice = forward_parts(engine, cfg, ids, at, sample)
    before = host(engine.params)
    t0 = time.monotonic()
    loss = float(engine.train_batch(iter([(ids, ids)])))
    jax.block_until_ready(engine.params)
    seconds = time.monotonic() - t0
    grads = host(jax.tree_util.tree_map(
        lambda m: np.asarray(m, np.float32) / (1 - ADAM_B1),
        first_moment(engine.opt_state)))
    got = {"logits": logits, "choice": choice, "loss": loss, "grads": grads,
           "before": before, "after": host(engine.params),
           "stats": engine.moe_stats(), "dsa": engine.dsa_stats(),
           "seconds": seconds}
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

    grad_err, update_err, indexer = {}, {}, {}
    for (path, g), w, old, new in zip(
            jax.tree_util.tree_flatten_with_path(got["grads"])[0],
            *(jax.tree_util.tree_leaves(tree)
              for tree in (want["grads"], got["before"], got["after"]))):
        name = jax.tree_util.keystr(path)
        if "indexer" in name:       # no gradient, either side; stands as it was
            indexer[name] = bool(not np.any(g) and not np.any(w)
                                 and np.array_equal(new, old))
            continue
        # a leaf only ONE side gives a gradient is as far off as can be
        grad_err[name] = norm(g - w) / norm(w) if np.any(w) else float("inf")
        update = adamw_first_step(g)
        update_err[name] = (norm(new - (old + update)) / norm(update) if np.any(update)
                            else 0.0 if np.array_equal(new, old) else float("inf"))
    router = {n: e for n, e in grad_err.items() if "['gate']" in n}
    routed = {n: e for n, e in grad_err.items() if n not in router
              and ("block_sparse_moe" in n or "post_attention_layernorm" in n)}
    dense = {n: e for n, e in grad_err.items() if n not in routed and n not in router}
    counts = (np.asarray(got["stats"]["expert_counts"], np.int64),
              np.asarray(want["counts"], np.int64))
    # the choice: the overlap a (row, layer, query), then each layer's mean
    both = (got["choice"] & want["choice"]).sum(-1)
    overlap = (both / np.maximum(got["choice"].sum(-1), 1)).mean(axis=(0, 2))
    dsa = got["dsa"] or {}
    return {"logit_median": float(np.quantile(err, 0.5)),
            "logit_p90": float(np.quantile(err, 0.9)), "logit_worst": float(err.max()),
            "grad_worst": max(dense.items(), key=lambda kv: kv[1]),
            "grad_routed_worst": max(routed.items(), key=lambda kv: kv[1]),
            "grad_router_median": float(np.median(list(router.values()))),
            "grad_router_worst": max(router.items(), key=lambda kv: kv[1]),
            "grad_err": grad_err,
            "indexer_untouched": bool(indexer) and all(indexer.values()),
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
            "chosen_pairs": [list(dsa.get("chosen_pairs_by_layer", ())),
                             np.asarray(want["chosen_pairs"]).ravel().tolist()],
            "chosen_share": float(dsa.get("chosen_share", np.nan)),
            "kth_score_mean": [float(dsa.get("kth_score_mean", np.nan)),
                               float(want["kth_score_mean"])],
            "overlap_by_layer": overlap.tolist(),
            "overlap_min_query": float((both / np.maximum(got["choice"].sum(-1), 1)).min())}


def verdicts(r: dict, assigned: int, experts: int, held: int, pairs_a_layer: int,
             slack: float = 1.0) -> dict:
    """Each part of ``correct`` that the readings decide, by the limits
    above: what ``run`` reports and what the calibration holds every wrong
    reference to. NaN fails (no comparison with it holds)."""
    rows = r["rows_held"]
    counts = np.asarray(r["counts"][0])
    kth = r["kth_score_mean"]
    kth_limit = KTH_RTOL * abs(kth[1]) if slack == 1.0 else REHEARSAL_KTH_ATOL
    return {
        "loss": bool(r["loss_err"] <= LOSS_RTOL
                     and r["loss_after_err"] <= LOSS_AFTER_RTOL and r["descends"]),
        "logits": bool(r["logit_median"] <= slack * LOGIT_MEDIAN_RTOL
                       and r["logit_p90"] <= slack * LOGIT_P90_RTOL),
        "grads": bool(r["grad_worst"][1] <= slack * GRAD_RTOL
                      and r["grad_routed_worst"][1] <= slack * GRAD_ROUTED_RTOL
                      and r["grad_router_median"] <= slack * GRAD_ROUTER_RTOL
                      and r["update_worst"][1] <= UPDATE_RTOL
                      and r["indexer_untouched"]),
        "routing": bool(r["assigned"] == [assigned, assigned]
                        and counts.shape == (experts, )
                        and rows[0] == int(counts[:held].sum())
                        and r["moved"] <= slack * COUNT_MOVED_SHARE * assigned
                        and abs(rows[0] - rows[1]) <= slack * ROWS_HELD_RTOL * max(rows[1], 1)
                        and r["share_fallback"] == 0),
        "choice": bool(all(n == pairs_a_layer for side in r["chosen_pairs"] for n in side)
                       and abs(kth[0] - kth[1]) <= kth_limit
                       and min(r["overlap_by_layer"]) >= 1.0 - slack * (1.0 - OVERLAP_MIN))}


def run(*, cell, config, seed, seconds, trace, rehearse, t_start, device,
        compiles, out_dir, log) -> dict:
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.observability import get_registry

    tr = cell["traffic"]
    rows, seq = int(tr["global_batch"]), int(tr["seq_len"])
    layer_cfg, params, t_init = host_parameters(config, seed)
    n_params = keye_cost.param_count(config)
    batches = gen.token_batches(seed, rows, seq, layer_cfg.vocab_size)
    first = next(batches)
    at, sample = logit_positions(rows, seq), choice_queries(rows, seq)

    # correctness, all on the first batch: the reference before the engine
    # exists (the docstring says why)
    t0 = time.monotonic()
    want = reference_pass(params, first, config, at, sample)
    t_reference = time.monotonic() - t0
    jax.clear_caches()      # the reference's programs hold nothing more

    engine, cfg, t_place = build_engine(cell, config, params)
    del params
    top_k, layers = cfg.num_experts_per_tok, cfg.num_hidden_layers
    assigned = rows * seq * top_k * layers
    pairs_a_layer = rows * dsa_cost.chosen_pairs(seq, cfg.dsa_topk)
    log(f"training: depth {layers} (sparse attention top-{cfg.dsa_topk} by a "
        f"{cfg.dsa_index_heads} x {cfg.dsa_index_head_dim} indexer + moe; "
        f"{n_params / 1e9:.3f}B parameters, {keye_cost.bytes_at_rest(config) / 1e9:.2f} GB "
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
    got = first_step(engine, cfg, ids, at, sample)
    t_program = time.monotonic() - t0 - got["seconds"]
    t0 = time.monotonic()
    r = readings(got, want)
    del want["grads"], got["grads"], got["before"], got["after"]
    del want["choice"], got["choice"]
    gc.collect()    # 8 GB of host arrays: freed now, not inside the window
    t_check = t_reference + t_program + time.monotonic() - t0
    losses = [got["loss"], got["loss_after"]]
    for _ in range(int(tr["warmup_steps"])):
        losses.append(step())
    jax.block_until_ready(engine.params)
    slack = REHEARSAL_SLACK if rehearse else 1.0
    ok = verdicts(r, assigned, cfg.num_local_experts, cfg.experts_held_,
                  pairs_a_layer, slack)
    said = {name: "ok" if good else "FAILED" for name, good in ok.items()}
    held = r["rows_held"]
    log(f"correctness: loss {got['loss']:.5f} at initialisation and "
        f"{got['loss_after']:.5f} after one step on the same batch, float32 reference "
        f"{want['ce']:.5f} and {want['ce_after']:.5f} (relative difference "
        f"{r['loss_err']:.1e}, {r['loss_after_err']:.1e}; limits {LOSS_RTOL:g}, "
        f"{LOSS_AFTER_RTOL:g}; must descend): {said['loss']}; logits at {at.size} "
        f"positions ({int((at >= cfg.dsa_topk).sum())} past {cfg.dsa_topk}), relative "
        f"distance median {r['logit_median']:.3e} (limit {slack * LOGIT_MEDIAN_RTOL:g}), "
        f"90th percentile {r['logit_p90']:.3e} (limit {slack * LOGIT_P90_RTOL:g}), worst "
        f"{r['logit_worst']:.2e}: {said['logits']}; the step's gradients, relative "
        f"distance of the worst leaf outside the expert blocks {r['grad_worst'][1]:.3e} "
        f"at {r['grad_worst'][0]} (limit {slack * GRAD_RTOL:g}), inside them "
        f"{r['grad_routed_worst'][1]:.3e} at {r['grad_routed_worst'][0]} (limit "
        f"{slack * GRAD_ROUTED_RTOL:g}), of the routers' kernels the median layer "
        f"{r['grad_router_median']:.3e} (limit {slack * GRAD_ROUTER_RTOL:g}; worst "
        f"{r['grad_router_worst'][1]:.3e}), the indexer's leaves without gradient and "
        f"unwritten on both sides: {r['indexer_untouched']}, the parameters' change "
        f"against AdamW's on those gradients, the worst leaf {r['update_worst'][1]:.1e} "
        f"at {r['update_worst'][0]} (limit {UPDATE_RTOL:g}): {said['grads']}; expert "
        f"counts sum {r['assigned'][0]} of {assigned} over {len(r['counts'][0])} experts, "
        f"{r['moved']} assignments moved against the reference "
        f"({r['moved'] / assigned:.2e} of all, limit {slack * COUNT_MOVED_SHARE:g}), "
        f"rows held {held[0]} against the reference's {held[1]} "
        f"({100.0 * held[0] / assigned:.2f}% of all; {r['share_fallback']} layers took "
        f"the pass over all rows): {said['routing']}; pairs chosen a layer "
        f"{r['chosen_pairs'][0]} against the reference's {r['chosen_pairs'][1]} (each "
        f"has to be {pairs_a_layer}; {100.0 * r['chosen_share']:.2f}% of the causal "
        f"pairs), smallest chosen score's mean {r['kth_score_mean'][0]:.5f} against "
        f"{r['kth_score_mean'][1]:.5f} (limit {KTH_RTOL:g} of it"
        f"{f'; rehearsed: {REHEARSAL_KTH_ATOL:g} absolute' if rehearse else ''}), overlap of the "
        f"choice at {sample.size} queries by layer "
        + ", ".join(f"{o:.4f}" for o in r["overlap_by_layer"])
        + f" (each at least {1.0 - slack * (1.0 - OVERLAP_MIN):g}; the worst query "
        f"{r['overlap_min_query']:.3f}): {said['choice']}; first step "
        f"{got['seconds']:.1f} s")

    # ---- the measured window ----
    gauge = get_registry().get("ds_moe_expert_load_max_over_mean")
    t_open = time.monotonic()
    setup = compiles.snapshot()
    step_s, load_samples, held_samples, chosen_samples = [], [], [], []
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
            chosen_samples.append(engine.dsa_stats()["chosen_share"])
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
             "chosen_pairs_first_batch": r["chosen_pairs"],
             "kth_score_mean": r["kth_score_mean"],
             "choice_overlap_by_layer": r["overlap_by_layer"],
             "choice_overlap_worst_query": r["overlap_min_query"],
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
            "dsa_chosen_share_samples": chosen_samples,
            # the mean rows held a layer and step: what a weights' gradient
            # call of the grouped matmul multiplied (moe_cost.call_flops)
            "moe_rows_per_step": mean_held / layers,
            "train_flops_per_token": keye_cost.train_flops_per_token(config, seq),
            "chips": cell["chips"]}
