"""Runner: optimizer steps of Ouro (``model_type: ouro``, a looped language
model: a stack of layers of four norms applied ``total_ut_steps`` times over
the same weights, one final norm and an exit gate after every pass, the head
read once a pass under the exit distribution's loss) back to back through
``deepspeed_tpu.initialize``, on one chip that holds the first layers of the
published stack and the whole vocabulary.

The training runner's flow (``train_steps_qwen3_next.py``) for an eleventh
architecture: the published keys go through ``OuroPolicy.config_from_hf`` and
the training recipe's keys (the entropy weight, the scanned stack, the chunked
loss, recomputation) are set on the result; the plain reference is
``reference/ouro.py``, the FLOP count ``ouro_cost.py``. Seeded fp32 parameters
made on the host (the embedding rows at the file's ``embedding_std``) and
placed by the engine; bf16, AdamW, chunked cross-entropy, recomputation as the
file says. Fresh seeded token ids every step out of the whole vocabulary, one
document a sequence, no gradient accumulation, the loss read each step.

The reference runs FIRST, on the host-made parameters put on the chip for it
alone (7.35 GB of training state and a float32 gradient pass over 32
applications of a layer at 16,384 positions do not share the chip); what it
gives goes to the host as numpy and the chip is emptied; only then is the
engine built. Another copy of the training runner's window loop (ROADMAP
D12); the Kimi-VL runner's ``adamw_first_step``, the LFM2 runner's
``first_moment`` and the Ling-3.0 runner's ``logit_positions`` and
``beyond_rounding`` are imported.
"""

import gc
import time

import numpy as np

from benchmark import ouro_cost, traffic as gen
from benchmark.reference import ouro as reference
from benchmark.runners.train_steps_kimi_vl import LR, adamw_first_step
from benchmark.runners.train_steps_lfm2_moe import ADAM_B1, first_moment
from benchmark.runners.train_steps_ling3_flash import beyond_rounding, logit_positions

# ``correct`` is decided on what the timed program gave at the timed sizes: the
# first call of the fused step on the first batch of 1 x 16,384 tokens (its
# loss, its gradients as AdamW's first moment holds them after one step from
# zero, the parameters it wrote, what it sowed of its exits) and the forward
# pass of the same batch (the logits of the first AND the last pass), against
# ``reference.step_parts`` on the same fp32 masters and ids. Each limit lies
# between what this program reads and what a wrong one would: the readings are
# ``calibrate_ouro.py``'s on the chip at these sizes (seeds 2147480901 and 41,
# PR 58: ``readings/ouro_calibration.jsonl``; PERF.md section 6 has the table): the
# sound program against a reference made wrong stands for a wrong program
# against the sound one. Below the configuration's bf16 is fp8 (every matmul's
# operands at three mantissa bits): it fails the logits, the exits and the
# gradients and passes the losses. A reference at bf16 operands is the
# configuration's OWN precision and is required of nothing (it reads as the
# sound one does). Every wrong model of ``reference.WRONG`` is told apart.
#
# Thirty seeds were read in all (the calibration's two and twenty-eight
# runs of the cell, PR 58; the runs' rows are ``readings/ouro_cell_runs.jsonl``,
# kept from their ``notes`` by ``calibrate_ouro.py --keep``): "read" below is
# the calibration's pair, "the largest" the largest of the thirty, what a
# fresh seed may read, and every limit has both on its lower side.
#
# (a) The loss ``L`` at initialisation and after one optimizer step on the same
# batch, a limit each. At initialisation: read 2.3e-6 and 1.1e-5 of the loss
# (the largest 2.4e-5); three passes read 3.4e-4 | 4.4e-4, the pre-norms alone
# 6.2e-4, ``beta`` 0 4.2e-3, ``p`` without the survival product 0.51 (fp8
# 4.5e-5 | 2.0e-5 is not told by it: a loss near ln 49,152 hardly sees the
# precision); the limit 4.2 times over the largest reading and 3.4 under the
# nearest wrong model, so NOT the harness's 1e-3 of the other training cells,
# which three passes and the pre-norms alone would both pass. After the step:
# read 8.5e-6 and 3.9e-5 (the largest 2.0e-4); three passes read 4.6e-3 |
# 3.9e-3, ``p`` without the survival product 4.6e-3, ``beta`` 0 7.4e-3, the
# pre-norms alone 1.4e-2 (fp8 3.4e-5 | 5.6e-4); the limit 5 over, 3.9 under.
# **The second loss need NOT be lower than the first, here alone of the
# training cells**: on seed 2147480777 the float32 reference itself rises,
# 11.24669 -> 11.24812, and the program with it, 11.24659 -> 11.24845 (the
# twenty-nine other seeds fall, by 5e-3 to 8e-2). On uniform random ids the
# gradient is noise, AdamW's first step moves every one of 612M parameters by
# 1e-5 whatever its gradient's size, and through four passes over the same
# weights the second-order term of that step can pass the first-order one.
# Whether it falls is reported (``descends``) and decides nothing; a step taken
# the wrong way reads 2.0 on the parameters written, (d).
LOSS_RTOL = 1e-4
LOSS_AFTER_RTOL = 1e-3
# (b) The logits of pass 1 AND of pass T at 256 positions
# (``logit_positions``), relative L2 over the vocabulary position by position,
# by the median and the 90th percentile of each pass, one limit for both. The
# last pass has 32 applications of bf16 behind it and reads twice the first:
# median 1.151e-2 | 2.231e-2 (the second seed 1.114e-2 | 2.387e-2; the largest
# 1.180e-2 | 2.707e-2), 90th percentile 1.200e-2 | 2.316e-2 (1.163e-2 |
# 2.561e-2; 1.231e-2 | 2.812e-2). fp8 reads 0.130 | 0.294 and 0.137 | 0.317
# (the second seed 0.125 | 0.314), three passes 0.77 on the last, the pre-norms
# alone 1.19 | 1.13, the final norm after the last pass alone 0.76 | 1.29. The
# median's limit 2.2 times over the largest reading and 2.1 under fp8's FIRST
# pass; the 90th percentile's 2.3 over, 2.0 under.
LOGIT_MEDIAN_RTOL = 6.0e-2
LOGIT_P90_RTOL = 6.5e-2
# (c) What the program sowed of its exits against the reference's. Each pass's
# mean CE, relative: read 1.2e-5 and 8.5e-6 (the largest 5.1e-5); fp8 2.7e-4 |
# 3.3e-4, the pre-norms alone 3.0e-4, the final norm after the last pass alone
# 0.73; the limit 2.3 times over, 2.25 under. Each pass's mean exit mass,
# absolute: read 6.8e-4 and 7.3e-4 (the largest 2.03e-3; a reference at bf16
# operands, the configuration's own precision, 1.2e-3); fp8 9.7e-3 | 8.5e-3,
# three passes 4.7e-2, no survival product 0.60; the limit 2.2 over, 1.9
# under. The mean entropy, absolute: read 1.1e-3 and 1.0e-3 (the largest
# 2.1e-3); fp8 3.0e-2 | 1.8e-2, no survival product 7.9e-2; the limit 2.9
# over, 3.0 under.
PASS_CE_RTOL = 1.2e-4
EXIT_MASS_ATOL = 4.5e-3
EXIT_ENTROPY_ATOL = 6e-3
# (d) The step's gradients against the reference's, relative L2 leaf by leaf (a
# layer's leaf is the sum over its four applications; the scanned stack's
# leaves are told layer by layer): the worst leaf reads 2.856e-2 (layer 4's
# ``k_proj``; the second seed 2.843e-2; the largest 4.705e-2, always a
# ``q_proj`` or ``k_proj``): fp8 0.327 | 0.339, three passes 0.43, no survival
# product 1.0, ``beta`` 0 1.5; the limit 2.3 over, 3.0 under. The exit gate's
# two leaves by name (a gate without one of them fails). Its kernel is held to
# the same limit: read 9.34e-3 and 7.06e-3, fifteen seeds at 7e-3 to 1.0e-2,
# nine at 1.4e-2 to 2.1e-2 and one at 3.75e-2: three passes 0.33 | 0.41,
# ``beta`` 0 4.7; 2.9 over, 3.0 under, and fp8's 0.114 | 0.104 is not told by
# it. The bias, one number summed over 49,149 positions' gates, is NOT read
# over its own size: the reference's value is the entropy term's
# +1.7e-2 less what the passes' mean CEs differ by, and crosses zero with the
# seed (read 1.87e-2, 4.8e-3, 8.1e-4, -3.9e-3), while the program's error stays
# where it is (4e-6 to 2.3e-4), so that ratio read 2.2e-4 to 9.5e-2 over
# twenty-five seeds with no wrong step behind it. It is read over the kernel's
# gradient a lane, ``|w_kernel| / sqrt(2,048)`` (3.9e-3 to 1.3e-2, never near
# zero): the gate reads ``h . w + b`` off a stream normed to RMS 1 a lane, so
# the bias is the weight of one more lane whose input is 1. So read: 3.7e-3
# and 3.4e-4 (the largest 3.68e-2, then 2.8e-2 and 1.7e-2; the first twenty-
# three runs' readings restated from the raw values the chip gave for each seed,
# ``readings/ouro_gate_raw.jsonl``); three passes 0.53 | 0.61, no survival
# product 1.7, the pre-norms alone 2.0, ``beta`` 0 7.9, the gate without
# its bias 1.66 and on NOTHING else: that one leaf is what tells it,
# since the bias is seeded 0 (fp8 7.7e-4 | 6.9e-2 is not told by it); the limit
# 4.1 over, 3.5 under. The parameters written against AdamW's first step on
# those gradients, leaf by leaf, float32's rounding of the sum taken out
# (``beyond_rounding``): read 6.5e-6 (the largest 6.7e-6); a leaf not written
# reads 0.96 or more, a step taken the wrong way 2.0.
GRAD_RTOL = 1.1e-1
GRAD_GATE_BIAS_LANES = 1.5e-1
UPDATE_RTOL = 1e-3
# A rehearsal (tests only: widths of 64 on a CPU, 96 tokens) checks the flow
# and not the chip: its sums are short, so it is held to this many times the
# limits but the update's.
REHEARSAL_SLACK = 8.0

GATE = "early_exit_gate"


def model_config(config: dict):
    """``LlamaConfig`` of the file: the published keys through the policy, the
    training recipe's keys set beside them."""
    import dataclasses
    from deepspeed_tpu.module_inject.replace_policy import OuroPolicy
    cfg = OuroPolicy().config_from_hf(config)
    return dataclasses.replace(
        cfg, exit_entropy_weight=config["exit_entropy_weight"],
        scan_layers=bool(config["scan_layers"]),
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


def unrolled(tree: dict) -> dict:
    """A host tree of the scanned stack's form (ONE ``layers/layer`` with a
    leading axis a layer) as the unrolled model's (``layers_<i>``, views of the
    same arrays); an unrolled tree as it is. The reference takes this form: a
    slice of a stacked leaf inside its program would scatter each of the 32
    applications' gradients into an array of the whole stack's size."""
    import jax
    model = tree["model"]
    if "layers" not in model:
        return tree
    stack = model["layers"]["layer"]
    depth = jax.tree_util.tree_leaves(stack)[0].shape[0]
    rest = {name: sub for name, sub in model.items() if name != "layers"}
    return {**tree, "model": {**rest, **{
        f"layers_{i}": jax.tree_util.tree_map(lambda a: a[i], stack) for i in range(depth)}}}


def reference_pass(params, ids, config: dict, at, wrong=frozenset()) -> dict:
    """The reference alone on the chip: ``reference.step_parts`` on the host
    parameters (``unrolled``), then its loss after AdamW's first step on its
    own gradients (``ce_after``). Everything it returns is on the host, its
    gradients in the unrolled form."""
    import jax
    params = unrolled(params)
    on_chip = jax.device_put(params, jax.devices()[0])
    want = reference.step_parts(on_chip, ids, config, at, wrong=wrong)
    del on_chip
    stepped = jax.tree_util.tree_map(lambda p, g: p + adamw_first_step(g),
                                     params, want["grads"])
    stepped = jax.device_put(stepped, jax.devices()[0])
    # through the same compiled program (its gradients dropped): a
    # forward-only program is one more compilation inside the set-up
    want["ce_after"] = reference.step_parts(stepped, ids, config, at, wrong=wrong,
                                            gradients=False)["ce"]
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
    """The timed program on the first batch: the forward pass's logits of the
    first and the last pass at ``at`` (a sequence at a time; ``[rows, 2, n,
    vocab]``), then the fused step's first call: its ``loss``, its ``grads``
    (out of AdamW's first moment), the parameters ``before`` and ``after`` it,
    what it sowed of its exits (``loop``), the seconds it took; then the loss
    of a second step on the same batch (``loss_after``). numpy, float32."""
    import jax
    import jax.numpy as jnp

    def host(tree):
        return jax.tree_util.tree_map(np.asarray, tree)

    logits = np.stack([
        np.asarray(engine.eval_batch(ids[row:row + 1], logits_to_keep=jnp.asarray(at[row]),
                                     all_passes=True)[0], np.float32)[[0, -1]]
        for row in range(at.shape[0])])
    before = host(engine.params)
    t0 = time.monotonic()
    loss = float(engine.train_batch(iter([(ids, ids)])))
    jax.block_until_ready(engine.params)
    seconds = time.monotonic() - t0
    grads = host(jax.tree_util.tree_map(
        lambda m: np.asarray(m, np.float32) / (1 - ADAM_B1),
        first_moment(engine.opt_state)))
    got = {"logits": logits, "loss": loss, "grads": grads, "before": before,
           "after": host(engine.params), "loop": engine.sown_stats("loop"),
           "seconds": seconds}
    got["loss_after"] = float(engine.train_batch(iter([(ids, ids)])))
    return got


def gate_errors(got: dict, want: dict) -> dict:
    """The exit gate's two leaves, program against reference: its ``kernel`` by
    relative L2 as any leaf, its ``bias`` by the error over the kernel's
    gradient a lane (``|w_kernel| / sqrt(hidden)``: (d) above says why not over
    itself). Nothing where a side lacks a leaf."""
    if not {"kernel", "bias"} <= set(got) & set(want):
        return {}
    g, w = (np.ravel(t["kernel"]).astype(np.float64) for t in (got, want))
    size = float(np.linalg.norm(w)) or float("nan")     # no gradient at all: fails
    off = abs(float(np.ravel(got["bias"])[0]) - float(np.ravel(want["bias"])[0]))
    return {"kernel": float(np.linalg.norm(g - w)) / size,
            "bias": off / (size / np.sqrt(w.size))}


def readings(got: dict, want: dict) -> dict:
    """Every distance ``correct`` is decided on, between the program's first
    step (``first_step``) and the reference's (``reference_pass``)."""
    import jax
    d = got["logits"] - want["logits"]
    err = np.linalg.norm(d, axis=-1) / np.linalg.norm(want["logits"], axis=-1)
    err = err.transpose(1, 0, 2).reshape(2, -1)             # [first | last, positions]

    def norm(x) -> float:
        return float(np.sqrt(np.vdot(x, x)))

    grad_err, update_err, update_raw = {}, {}, {}
    # the program's trees layer by layer, as the reference's gradients are
    for (path, g), w, old, new in zip(
            jax.tree_util.tree_flatten_with_path(unrolled(got["grads"]))[0],
            *(jax.tree_util.tree_leaves(tree)
              for tree in (want["grads"], unrolled(got["before"]), unrolled(got["after"])))):
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
    stack = {n: e for n, e in grad_err.items() if GATE not in n}
    gate = gate_errors(got["grads"]["model"].get(GATE, {}), want["grads"]["model"].get(GATE, {}))
    loop = got["loop"] or {}
    passes = len(np.ravel(loop.get("ce", [])))

    def padded(values):     # a reference of fewer passes fails by its shape
        out = np.full(max(passes, 1), np.nan)
        values = np.ravel(values)[:len(out)]
        out[:len(values)] = values
        return out

    return {"logit_median": [float(np.quantile(e, 0.5)) for e in err],
            "logit_p90": [float(np.quantile(e, 0.9)) for e in err],
            "logit_worst": [float(e.max()) for e in err],
            "grad_worst": max(stack.items(), key=lambda kv: kv[1]),
            "grad_gate": gate,
            "grad_err": grad_err,
            "update_worst": max(update_err.items(), key=lambda kv: kv[1]),
            "update_with_rounding_worst": max(update_raw.items(), key=lambda kv: kv[1]),
            "loss_err": abs(got["loss"] - want["ce"]) / abs(want["ce"]),
            "loss_after_err": (abs(got["loss_after"] - want["ce_after"])
                               / abs(want["ce_after"])),
            "descends": bool(got["loss_after"] < got["loss"]),
            "ce_pass": [np.ravel(loop.get("ce", [np.nan])).tolist(),
                        padded(want["ce_pass"]).tolist()],
            "exit_mass": [np.ravel(loop.get("exit_mass", [np.nan])).tolist(),
                          padded(want["exit_mass"]).tolist()],
            "exit_entropy": [float(loop.get("exit_entropy", np.nan)),
                             float(want["exit_entropy"])]}


def verdicts(r: dict, passes: int, slack: float = 1.0) -> dict:
    """Each part of ``correct`` that the readings decide, by the limits above:
    what ``run`` reports and what the calibration holds every wrong reference
    to. NaN fails (no comparison with it holds)."""
    ce, mass = (np.asarray(r[k], np.float64) for k in ("ce_pass", "exit_mass"))
    entropy = r["exit_entropy"]
    return {
        "loss": bool(r["loss_err"] <= slack * LOSS_RTOL
                     and r["loss_after_err"] <= slack * LOSS_AFTER_RTOL),
        "logits": bool(all(m <= slack * LOGIT_MEDIAN_RTOL for m in r["logit_median"])
                       and all(p <= slack * LOGIT_P90_RTOL for p in r["logit_p90"])),
        "exits": bool(ce.shape == (2, passes) and mass.shape == (2, passes)
                      and np.all(np.abs(ce[0] - ce[1]) <= slack * PASS_CE_RTOL * ce[1])
                      and np.all(np.abs(mass[0] - mass[1]) <= slack * EXIT_MASS_ATOL)
                      and abs(entropy[0] - entropy[1]) <= slack * EXIT_ENTROPY_ATOL),
        "grads": bool(r["grad_worst"][1] <= slack * GRAD_RTOL
                      and set(r["grad_gate"]) == {"kernel", "bias"}
                      and r["grad_gate"]["kernel"] <= slack * GRAD_RTOL
                      and r["grad_gate"]["bias"] <= slack * GRAD_GATE_BIAS_LANES
                      and r["update_worst"][1] <= UPDATE_RTOL)}


def run(*, cell, config, seed, seconds, trace, rehearse, t_start, device,
        compiles, out_dir, log) -> dict:
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.observability import get_registry

    tr = cell["traffic"]
    rows, seq = int(tr["global_batch"]), int(tr["seq_len"])
    # the configuration first: a program that cannot run it fails here, at once
    layer_cfg, params, t_init = host_parameters(config, seed)
    n_params = ouro_cost.param_count(config)
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
    passes = cfg.total_ut_steps
    log(f"training: depth {cfg.num_hidden_layers} run {passes} times "
        f"({'one scanned body' if cfg.scan_layers else 'unrolled'}; {n_params / 1e9:.3f}B "
        f"parameters, {ouro_cost.bytes_at_rest(config) / 1e9:.2f} GB at rest, vocabulary "
        f"{cfg.vocab_size}, beta {cfg.exit_entropy_weight}), mesh "
        f"{dict(engine.mesh_ctx.mesh.shape)}, batch {rows} x {seq}; host init "
        f"{t_init:.1f} s, reference {t_reference:.1f} s (peak "
        f"{want['peak_bytes'] / 1e9:.2f} GB), initialize+place {t_place:.1f} s")

    def step() -> float:
        batch = jnp.asarray(next(batches))
        return float(engine.train_batch(iter([(batch, batch)])))

    ids = jax.device_put(jnp.asarray(first),
                         engine.zero_plan.batch_sharding((first, ))[0])
    t0 = time.monotonic()
    got = first_step(engine, ids, at)
    t_program = time.monotonic() - t0 - got["seconds"]
    t0 = time.monotonic()
    r = readings(got, want)
    del want["grads"], got["grads"], got["before"], got["after"]
    gc.collect()    # 7.35 GB of host arrays: freed now, not inside the window
    t_check = t_reference + t_program + time.monotonic() - t0
    losses = [got["loss"], got["loss_after"]]
    for _ in range(int(tr["warmup_steps"])):
        losses.append(step())
    jax.block_until_ready(engine.params)
    slack = REHEARSAL_SLACK if rehearse else 1.0
    ok = verdicts(r, passes, slack)
    said = {name: "ok" if good else "FAILED" for name, good in ok.items()}
    fmt = lambda values, spec: "/".join(format(v, spec) for v in values)    # noqa: E731
    log(f"correctness: loss {got['loss']:.5f} at initialisation and "
        f"{got['loss_after']:.5f} after one step on the same batch, float32 reference "
        f"{want['ce']:.5f} and {want['ce_after']:.5f} (relative difference "
        f"{r['loss_err']:.1e}, {r['loss_after_err']:.1e}; limits {slack * LOSS_RTOL:g}, "
        f"{slack * LOSS_AFTER_RTOL:g}; it {'falls' if r['descends'] else 'RISES'}, the "
        f"reference's {'falls' if want['ce_after'] < want['ce'] else 'rises'}: decides "
        f"nothing): {said['loss']}; logits of pass 1 | "
        f"pass {passes} at {at.size} positions, relative distance median "
        f"{fmt(r['logit_median'], '.3e')} (limit {slack * LOGIT_MEDIAN_RTOL:g}), 90th "
        f"percentile {fmt(r['logit_p90'], '.3e')} (limit {slack * LOGIT_P90_RTOL:g}), worst "
        f"{fmt(r['logit_worst'], '.2e')}: {said['logits']}; mean CE by pass "
        f"{fmt(r['ce_pass'][0], '.5f')} against {fmt(r['ce_pass'][1], '.5f')} (relative "
        f"limit {slack * PASS_CE_RTOL:g}), mean exit mass {fmt(r['exit_mass'][0], '.5f')} "
        f"against {fmt(r['exit_mass'][1], '.5f')} (limit {slack * EXIT_MASS_ATOL:g}), mean "
        f"entropy {r['exit_entropy'][0]:.5f} against {r['exit_entropy'][1]:.5f} (limit "
        f"{slack * EXIT_ENTROPY_ATOL:g}): {said['exits']}; the step's gradients, relative "
        f"distance of the worst leaf {r['grad_worst'][1]:.3e} at {r['grad_worst'][0]} "
        f"(limit {slack * GRAD_RTOL:g}), the exit gate's "
        + ", ".join(f"{leaf} {e:.3e}" for leaf, e in r["grad_gate"].items())
        + f" (the kernel's limit {slack * GRAD_RTOL:g}; the bias in the kernel's lanes, limit "
        f"{slack * GRAD_GATE_BIAS_LANES:g}), "
        f"the parameters' change against AdamW's on "
        f"those gradients, the worst leaf {r['update_worst'][1]:.1e} at "
        f"{r['update_worst'][0]} (limit {UPDATE_RTOL:g}; float32's rounding of the sum "
        f"counted too, {r['update_with_rounding_worst'][1]:.1e} at "
        f"{r['update_with_rounding_worst'][0]}): {said['grads']}; first step "
        f"{got['seconds']:.1f} s")

    # ---- the measured window ----
    t_open = time.monotonic()
    setup = compiles.snapshot()
    step_s, mass_samples = [], []
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
            # the step has ended (its loss was read): the read does not wait
            mass_samples.append(np.ravel(engine.sown_stats("loop")["exit_mass"]).tolist())
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
    reg = get_registry()
    loop_now = engine.sown_stats("loop") or {}
    e2e = {"setup_s": t_open - t_start,
           "train_tok_s": tokens / (t_close - t_open)}
    notes = {"seed": seed, "setup": setup, "host_init_s": t_init, "initialize_s": t_place,
             "check_s": t_check, "check_reference_s": t_reference,
             "check_program_s": t_program, "first_step_s": got["seconds"],
             "reference_peak_bytes": want["peak_bytes"],
             "steps": len(step_s), "step_s_median": float(np.median(step_s)),
             "step_s_longest": sorted(step_s)[-3:],
             "loss_first_two": losses[:2],
             "loss_reference": [want["ce"], want["ce_after"]],
             "logit_rel_err_median": r["logit_median"], "logit_rel_err_p90": r["logit_p90"],
             "logit_rel_err_worst": r["logit_worst"],
             "grad_rel_err": r["grad_err"], "grad_gate": r["grad_gate"],
             "update_rel_err_worst_leaf": r["update_worst"],
             "update_rel_err_with_rounding_worst_leaf": r["update_with_rounding_worst"],
             "loop_first_batch": {k: r[k] for k in ("ce_pass", "exit_mass", "exit_entropy")},
             "loop_last_step": {k: np.ravel(v).tolist() for k, v in loop_now.items()},
             "scan_layers": bool(cfg.scan_layers),
             "remat_kept_bytes": {m.labels.get("key", ""): m.value
                                  for m in reg.series("ds_remat_kept_bytes")},
             "verdicts": ok, "loss_last": losses[-1], "step_programs": programs,
             "n_params": n_params,
             "bytes_in_use": [int((d.memory_stats() or {}).get("bytes_in_use", 0))
                              for d in jax.devices()[:cell["chips"]]]}
    correct = all(ok.values()) and finite and programs == 1
    return {"correct": correct, "attempted": len(step_s),
            "failed": 0 if finite else 1, "end_to_end": e2e, "notes": notes,
            "setup": setup, "trace_steps": min(n_trace, len(step_s)),
            "tokens_per_step": rows * seq, "loop_exit_mass_samples": mass_samples,
            "train_flops_per_token": ouro_cost.train_flops_per_token(config, seq),
            "chips": cell["chips"]}
