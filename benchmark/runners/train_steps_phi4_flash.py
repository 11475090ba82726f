"""Runner: optimizer steps of Phi-4-mini-flash's blocks (SambaY with
differential attention: Mamba-1 layers, differential attention under a window,
causal and as cross-attention to one layer's keys and values, a Gated Memory
Unit over one scan's output) back to back through ``deepspeed_tpu.initialize``,
on one chip that holds a share of the vocabulary.

The training runner's flow (``train_steps_ling3_flash.py``) for a ninth
architecture: the file's keys go through ``Phi4FlashPolicy.config_from_hf``
(the kept layers' kinds with their published offset); the plain reference is
``reference/phi4_flash.py``, the FLOP count ``phi4flash_cost.py``. Seeded fp32
parameters made on the host and placed by the engine; bf16, AdamW, chunked
cross-entropy, recomputation as the file says, the six layers unrolled (their
kinds differ). Fresh seeded token ids every step out of the vocabulary slice,
one document a sequence, no gradient accumulation, every step's loss read
before the next step is sent, as in the other training cells.

The reference runs FIRST, on the host-made parameters put on the chip for it
alone (8.4 GB of training state and a float32 gradient pass over 16,384
positions do not share the chip); what it gives goes to the host as numpy and
the chip is emptied; only then is the engine built. Another copy of the
training runner's window loop (ROADMAP D12); the Kimi-VL runner's
``adamw_first_step``, the Ling-3.0 runner's ``beyond_rounding`` and
``build_engine``'s form and the LFM2 runner's ``first_moment`` are imported or
followed.
"""

import gc
import time

import numpy as np

from benchmark import phi4flash_cost, traffic as gen
from benchmark.reference import phi4_flash as reference
from benchmark.runners.train_steps_kimi_vl import LR, adamw_first_step
from benchmark.runners.train_steps_lfm2_moe import ADAM_B1, first_moment
from benchmark.runners.train_steps_ling3_flash import beyond_rounding

# ``correct`` is decided on what the timed program gave at the timed sizes:
# the first call of the fused step on the first batch of 1 x 16,384 tokens (its
# loss, its gradients as AdamW's first moment holds them after one step from
# zero, the parameters it wrote, its scans' and its attention layers'
# statistics) and the forward pass of the same batch, against
# ``reference.step_parts`` on the same fp32 masters and ids. Each limit lies
# between what this program reads and what a wrong one would: the readings are
# ``calibrate_phi4_flash.py``'s on the chip at these sizes (seeds 2147480901:
# every variant, and 41: sound, bf16, fp8 and the state in bf16, PR 52:
# ``readings/phi4_flash_calibration.jsonl``; PERF.md section 6 has the table):
# the sound program against a reference made wrong stands for a wrong program
# against the sound one. Below the configuration's bf16 is fp8 (every matmul's
# operands at three mantissa bits): it fails the second loss, the logits and
# the gradients and passes the scans' and the lambdas' statistics. A reference
# at bf16 operands reads as the sound one does and is required of nothing. The
# subtlest wrong model is the state carried in bf16: the logits' 90th
# percentile and the gradients tell it, nothing else does.
#
# (a) The loss at initialisation and after one optimizer step on the same
# batch: read 1.9e-5 to 3.1e-5 and 1.7e-5 to 2.5e-5 of the loss on two seeds;
# the harness's limit for every training cell leaves that thirty times over. A
# loss near ln 25,008 hardly sees the mixers: the ``D`` term dropped reads
# 1.7e-3 and 8.9e-3, no window 5.9e-4 and 1.3e-3, ``1 - lambda_init`` dropped
# 1.3e-3 and 3.2e-3, fp8 5.0e-5 to 1.8e-4 and 1.1e-3 to 1.3e-3; one decay a
# channel (1.9e-4), the state in bf16 (9.2e-5), the memory after its gate
# (1.5e-4) and the cross layer's own keys (1.7e-4) pass it. The second loss
# must be lower than the first.
LOSS_RTOL = 1e-3
LOSS_AFTER_RTOL = 1e-3
# (b) The logits at LOGIT_POSITIONS positions, three quarters of them past the
# sequence's middle (position 8,192 on: where the 512 window and the causal
# mask differ most, and where a state has decayed and been rewritten thousands
# of times), relative L2 over the vocabulary position by position, by their
# median and 90th percentile. The median reads 2.20e-2 to 2.37e-2 on eight
# seeds (a bf16 reference 2.254e-2 to 2.279e-2 where the float32 one read
# 2.276e-2 to 2.300e-2: it is the rounding of six bf16 layers): the cross layer's own keys 9.8e-2, the window in the full layer too
# 2.2e-1, one decay a channel 2.2e-1, fp8 2.4e-1 to 2.5e-1, the memory after
# its gate 3.1e-1, the GMU on the first scan 4.1e-1, no subtraction 4.8e-1, no
# window 5.1e-1, no ``subln`` 5.3e-1, ``1 - lambda_init`` dropped 1.05, no ``D``
# 1.40, no softplus NaN. The state in bf16 depends on the seed (how large the
# states grow), as in the Granite cell: 8.2e-2 on one and 2.604e-2 on the
# other, which the median's limit (14% over the largest reading) does not
# tell; the 90th percentile does: it reads 2.36e-2 to 2.56e-2 on eight seeds,
# the state in bf16 3.490e-2 and 1.6e-1, every other wrong way 1.1e-1 or more;
# its limit 17% over the largest reading and 14% under the least of those.
LOGIT_POSITIONS = 256
LOGIT_MEDIAN_RTOL = 2.7e-2
LOGIT_P90_RTOL = 3.0e-2
# (c) The step's gradients against ``jax.grad`` of the reference, relative L2
# leaf by leaf, by kind. The leaves that are sums of terms of one sign pattern
# over all tokens (every matrix, the taps, the norms, the embedding) read 3.2e-2
# to 5.3e-2, the worst the second Mamba layer's ``dt_proj`` kernel (4.13e-2 to
# 4.33e-2) or an ``x_proj`` kernel (4.25e-2, 4.62e-2, 4.67e-2, 5.26e-2) on eight
# seeds (a bf16 reference 4.24e-2; by name:
# ``x_proj`` 3.6e-2, the taps 3.4e-2, ``subln`` 3.7e-2, the full layer's
# ``k_proj`` 3.8e-2 and ``v_proj`` 3.2e-2, which collect from two layers, the
# GMU's ``in_proj`` 3.3e-2): the state in bf16 1.40e-1 and 2.7e-1, fp8 4.5e-1,
# the window in the full layer 9.9e-1, the cross layer's own keys 1.0, no
# window 1.24, one decay a channel 1.34, the GMU on the first scan 1.38, the
# memory after its gate 1.60, ``1 - lambda_init`` dropped 1.82, no subtraction
# 4.4, no ``D`` 8.3, no ``subln`` inf (a leaf without a gradient on one side).
# The limit is near the geometric mean of 4.27e-2 and 1.40e-1: 1.43 over the
# largest reading of eight seeds, 1.86 under the least wrong one. The scans'
# own leaves (``A_log``, ``D``, ``dt_proj``'s bias: 81,920 or 5,120 values
# each) read 3.2e-2 to 4.2e-2 on eight seeds, as the matrices do: the cross
# layer's own keys 1.75e-1 (``D``), the state in bf16 3.1e-1 and 5.5e-1, the
# window in the full layer 3.4e-1, fp8 4.0e-1, every other wrong way 0.62 or
# more; the limit 2.4 over the reading and 1.75 under the least wrong one.
# WHICH LEAVES A SUM THAT CANCELS TAKES OUT OF THE RELATIVE DISTANCE is decided
# by the reference's own gradient, not by a leaf's name. (i) A bias whose
# gradient is a zero of the mathematics: a constant added to every key moves
# every score of a row alike, the softmax does not see it, and ``k_proj``'s
# bias collects the per-token gradients of the keys, which sum to zero: both
# sides hold rounding and nothing else (read: a relative distance of 2e4 to
# 3e4). The rule: a bias whose reference gradient is under ZERO_GRAD_SHARE of
# its own kernel's a unit of input (``|dW| / sqrt(fan in)``: what the same
# per-token gradients sum to against inputs of unit size) is left out, and
# reported with that share (``grad_zero``). (ii) A layer's four lambda
# vectors: their gradients are one SCALAR, ``dL/d lambda``, times fixed
# vectors, and that scalar is a sum over all tokens and pairs of terms of
# either sign: where it lands near zero, rounding moves it by its own size
# (on eight seeds the worst of a run's three layers read 3.7e-2, 4.6e-2,
# 4.7e-2, 4.7e-2, 1.9e-1, 2.5e-1, 3.5e-1 and 2.9, all four vectors of a layer
# alike to three digits, as one scalar would; the bf16 reference alike). The
# reference says how far the terms cancel (``lambda_terms``: ``|dL/d lambda|``
# and the sum of its terms' magnitudes, a term a token and pair), and the
# vectors are held on EVERY seed to the matrices' limit plus what rounding can
# do to a sum of that size: a relative distance of at most ``GRAD_RTOL +
# LAMBDA_TERMS_RTOL * (sum of magnitudes) / |sum|``, which is ``|difference|
# <= GRAD_RTOL * |sum| + LAMBDA_TERMS_RTOL * (sum of magnitudes)``. A sum that
# does not cancel is held as any matrix is; one that cancels to nothing is held
# to the terms' rounding. Read on the chip (the difference as a share of the
# sum of magnitudes, layer by layer; ``|sum|`` over that sum was 4.4e-5 to 7.4e-3
# in every layer read, so these sums ALL cancel and the second term is what
# holds them): the sound program 5.4e-6 to 1.3e-4 in thirty layers of ten seeds
# (the layer that read a relative distance of 2.9 among them: its sum is 4.4e-5
# of its terms); the worst layer of a wrong model: fp8 2.0e-3, ``1 - lambda_init``
# dropped 6.1e-3, no ``subln`` 7.7e-2, no subtraction inf (its reference has no
# such gradient); the limit is the geometric mean of 1.3e-4 and 2.0e-3. Of the
# biases, ``k_proj``'s read 2.1e-7 to 4.2e-7 of their kernels' and the least of
# the others (a ``q_proj``'s, ``grad_bias_least``) 0.975 to 0.998: the limit 1e-3.
# (iii) A value projection's bias, and the bias of the norm before it. A
# softmax's rows sum to one, so ``v_proj``'s bias has a gradient of EXACTLY
# ``(1 - lambda) G`` summed over the layers that read the values (``G``: the
# gradient to ``(A1 - lambda A2) V``, summed over the query tokens), and every
# program that runs the two maps as two attention calls (the family's own code;
# here ONE stacked call, whose halves' value gradients are added afterwards)
# makes it as the difference of ``G`` and ``lambda G``, each rounded to bf16 on
# its way. The seed's four vectors put lambda at 0.79 to 0.81 +- 0.11 (250 seeds
# read on the host): within 0.03 of 1 in the windowed or the full layer on one
# seed in fifteen (17 of 250). There the sum is a fiftieth or less of its terms, and
# bf16's rounding of the terms, alike over runs of neighbouring tokens and so
# not averaged out by a sum over tokens, moves it by a tenth of its size and
# more. That is what the driver's seed 929722737 read (lambda 1.0131 in the
# full layer, 0.9553 in the cross layer: 9.75e-2 at that ``v_proj`` bias, its
# sum a hundredth of its terms; everything else passed), and seeds 2147483777
# (0.9614 in the windowed layer: 6.93e-2) and 2147480777 (0.9470 in the full
# layer: 6.39e-2) before it, which the matrices' limit still let pass. The
# norm's bias sums the same per-token gradients through the three projections
# (``W_q dq + W_k dk + W_v dv`` of their bias gradients): the same two terms
# beside one that does not cancel (6.83e-2 on the driver's seed, 1.014e-1 on
# seed 2028953222). The reference says how far the terms cancel
# (``value_terms``: each of the two leaves' gradient and the sum of its terms'
# magnitudes, ``(1 + |lambda|) |G|`` a reader for the one and the same through
# ``W_v`` beside ``|W_q dq| + |W_k dk|`` for the other), and both are held, on
# every seed, as the lambda vectors are: to ``GRAD_RTOL + VALUE_TERMS_RTOL * (sum
# of magnitudes) / |sum|``, VALUE_TERMS_RTOL one ulp of bf16 (2^-8: twice the
# most that one rounding of each term can do). Read on the chip before the seeds
# had a margin (``readings/phi4_flash_lambda_near_one.jsonl``: eleven seeds,
# five of them picked for a lambda near 1), the difference as a share of the
# terms' magnitudes: 9.7e-4 to 1.73e-3 at the fourteen ``v_proj`` biases whose
# sum is under a fourteenth of its terms (lambda 1.0019, a 1,057th: 1.83 times
# its own size and 1.73e-3 of its terms; 0.9905 and 0.9884, a 210th and a 172nd:
# 2.46e-1 and 2.44e-1, 1.17e-3 and 1.42e-3) and 1.08e-3 to 2.85e-3 at the seven
# such biases of a norm; where the sum is a fifth to a tenth of its terms the
# matrices' rounding shows instead (2.3e-2 to 3.6e-2 of the leaf's size) and the
# first term holds it. No other leaf is a plain sum over the tokens of the
# values' gradients. With LAMBDA_MARGIN (below) a sum that one layer reads is
# a twenty-first of its terms at the least, and the second term adds 8e-2.
# The parameters
# written against AdamW's first step on those gradients, leaf by leaf,
# float32's rounding of the sum taken out (``beyond_rounding``): read 6.6e-6
# (3.2e-4 to 3.7e-4 with the rounding counted); a leaf not written reads 0.96
# or more.
GRAD_RTOL = 7.5e-2
GRAD_SMALL_RTOL = 1.0e-1
UPDATE_RTOL = 1e-3
SMALL_LEAVES = ("['A_log']", "['D']", "['dt_proj']['bias']")
LAMBDA_LEAVES = ("['lambda_q1']", "['lambda_k1']", "['lambda_q2']", "['lambda_k2']")
LAMBDA_TERMS_RTOL = 5e-4
VALUE_TERMS_RTOL = 2.0 ** -8
# The cell's seeds give no differential layer a lambda within LAMBDA_MARGIN of
# 1 (``draw_lambdas_again``). The family's draw (four N(0, 0.1) 64-vectors a
# layer: lambda 0.79 to 0.81 +- 0.11) puts some layer of the three within 0.1 of
# 1 on 38% of seeds and within 0.03 on 10% (250 seeds read on the host). There
# the two maps' outputs all but cancel before ``subln`` divides by what is
# left, and what bf16 can say of the layer's gradients depends on the seed: of
# five seeds picked for a lambda within 0.03 of 1, the full layer's ``q_proj``,
# ``k_proj`` and norm read 1.05e-1, 9.8e-2 and 1.01e-1 on one (2028953222:
# lambda 1.0262, the cross layer's 0.9683) and 4.6e-2 or less on the others
# (1.0131, 1.0006, 0.9452; every other verdict ok on all five); at 0.90 and
# 0.91 they read 3.8e-2 as anywhere. What decides it between 1.026 (1.05e-1) and
# 1.027 (5.2e-2, seed 2093992688) was not found, so no rule of the reference's
# own, as (ii) and (iii) are, could be written for it, and every limit here was
# read where lambda is 0.74 to 0.81. The comparison is made where bf16 can
# decide it: which weights the seed gives is the cell's to say, how far the
# program may be from the reference on them is not moved. PERF.md section 7.
LAMBDA_MARGIN = 0.1
ZERO_GRAD_SHARE = 1e-3
# (d) ``selscan_stats`` against the reference's: the largest |h| at the ends
# of the blocks of 128 tokens (the states the kernels keep) within this factor
# either way (read: 1.0008 and 1.0056; one decay a channel 1.20, ``1 -
# lambda_init`` dropped 0.84), the mean ``dt`` within DT_MEAN_RTOL (read: 1.0e-5
# and 1.4e-5; without its softplus NaN); ``diffattn_stats``: each differential
# layer's lambda within LAMBDA_ATOL (read: 0 to the last bit: the engine casts
# the lambda vectors to bf16 and the operator takes them back up, a pair of
# casts that XLA drops on a TPU; one published index off moves ``lambda_init``
# by 2.3e-3 at layer 15 and 1.3e-3 at 17).
STATE_ABSMAX_FACTOR = 1.1
DT_MEAN_RTOL = 2e-2
LAMBDA_ATOL = 1e-3
# A rehearsal (tests only: widths of 64 on a CPU, 96 tokens) checks the flow
# and not the chip: its sums are short, so it is held to this many times the
# limits of the losses, the logits' distances and the gradients, and to the
# others as they are.
REHEARSAL_SLACK = 8.0
# the reference's gradient pass compares the loss of ALL positions; a cell
# whose first set-up would pass its limit may compare the first LOSS_POSITIONS
# instead (0 = all): stated here, with the reading, if it is ever set
LOSS_POSITIONS = 0

NAMED_LEAVES = ("A_log", "['D']", "dt_proj']['bias", "dt_proj']['kernel", "x_proj",
                "conv_weight", "lambda_", "subln", "layers_3']['self_attn']['k_proj']['kernel",
                "layers_3']['self_attn']['v_proj']['kernel", "layers_4']['mamba']['in_proj")


def model_config(config: dict):
    """``LlamaConfig`` of the file: its keys through the policy, the training
    recipe's keys set beside them."""
    import dataclasses
    from deepspeed_tpu.module_inject.replace_policy import Phi4FlashPolicy
    cfg = Phi4FlashPolicy().config_from_hf(config)
    return dataclasses.replace(
        cfg, ce_chunk_size=int(config["ce_chunk_size"]), remat=bool(config["remat"]),
        remat_policy=config.get("remat_policy"))


def lambda_of(attn: dict, published_index: int) -> float:
    """A differential layer's lambda from its four vectors."""
    dot = lambda a, b: float(np.vdot(attn[a].astype(np.float64), attn[b]))  # noqa: E731
    return (np.exp(dot("lambda_q1", "lambda_k1")) - np.exp(dot("lambda_q2", "lambda_k2"))
            + 0.8 - 0.6 * np.exp(-0.3 * published_index))


def draw_lambdas_again(params: dict, cfg, seed: int) -> dict:
    """The four lambda vectors of every differential layer whose lambda the
    seed put within LAMBDA_MARGIN of 1, drawn again (N(0, 0.1), the family's,
    from the seed, the layer and the attempt) until it is not; in place.
    -> ``{layer: attempts}`` of the layers drawn again."""
    again = {}
    for layer, lp in params["model"].items():
        attn = lp.get("self_attn", {}) if isinstance(lp, dict) else {}
        if "lambda_q1" not in attn:
            continue
        index = int(layer.split("_")[1]) + cfg.layer_index_offset
        while abs(1.0 - lambda_of(attn, index)) < LAMBDA_MARGIN:
            again[layer] = again.get(layer, 0) + 1
            rng = np.random.default_rng([seed, index, again[layer]])
            for name in LAMBDA_LEAVES:
                name = name[2:-2]
                attn[name] = rng.normal(0.0, 0.1, attn[name].shape).astype(np.float32)
    return again


def host_parameters(config: dict, seed: int):
    """-> (the ``LlamaConfig``, its seeded fp32 parameters as numpy on the
    host, seconds); ``draw_lambdas_again`` applied."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.llama import init_llama
    t0 = time.monotonic()
    cfg = model_config(config)
    with jax.default_device(jax.devices("cpu")[0]):
        _, params = init_llama(cfg, seed=seed % (2**31 - 1), dtype=jnp.float32)
    params = jax.tree_util.tree_map(np.array, params)
    draw_lambdas_again(params, cfg, seed)
    return cfg, params, time.monotonic() - t0


def logit_positions(rows: int, seq: int) -> np.ndarray:
    """[rows, n]: LOGIT_POSITIONS positions in all, a quarter spread over each
    sequence's first half and the rest over its second."""
    n = min(max(LOGIT_POSITIONS // rows, 1), seq - 1)
    early = np.linspace(0, (seq - 2) // 2, n // 4, endpoint=False)
    late = np.linspace((seq - 2) // 2, seq - 2, n - n // 4)
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
    # through the same compiled program (its gradients dropped): a forward-only
    # program of the cell's size costs more to compile than the backward to run
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
    ``before`` and ``after`` it, what its scans (``selscan``) and its
    attention layers (``diffattn``) sowed, the seconds it took; then the loss
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
           "after": host(engine.params), "selscan": engine.selscan_stats(),
           "diffattn": engine.diffattn_stats(), "seconds": seconds}
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
        grad_err[name] = norm(g - w) / norm(w) if np.any(w) else float("inf")
        update = adamw_first_step(g)
        if np.any(update):
            expected = old + update
            update_err[name] = norm(beyond_rounding(new, expected)) / norm(update)
            update_raw[name] = norm(new - expected) / norm(update)
        else:
            update_err[name] = 0.0 if np.array_equal(new, old) else float("inf")
    want_grads = dict(zip(grad_err, jax.tree_util.tree_leaves(want["grads"])))
    # (i) the biases whose reference gradient is a zero of the mathematics
    zero, kept = {}, {}
    for name, w in want_grads.items():
        kernel = want_grads.get(name[:-len("['bias']")] + "['kernel']")
        if name.endswith("['bias']") and kernel is not None:
            share = norm(w) * np.sqrt(kernel.shape[0]) / norm(kernel)
            if share < ZERO_GRAD_SHARE:
                zero[name] = share
            else:
                kept[name] = share
    # (ii) a layer's lambda vectors: the worst of the four, and how far the
    # terms of their one scalar cancel
    lam = {}
    for name, e in grad_err.items():
        if name.endswith(LAMBDA_LEAVES):
            layer = name.split("']['")[1]
            size, terms = want["lambda_terms"][layer]
            lam[layer] = {"distance": max(e, lam.get(layer, {}).get("distance", 0.0)),
                          "sum_over_terms": size / terms if terms else 0.0}
    # (iii) a value projection's bias and the norm's before it, and how far
    # their readers' terms cancel
    value = {}
    for name, (size, terms) in want["value_terms"].items():
        value[name] = {"distance": grad_err[name],
                       "sum_over_terms": size / terms if terms else 0.0}
    small = {n: e for n, e in grad_err.items() if n.endswith(SMALL_LEAVES)}
    summed = {n: e for n, e in grad_err.items()
              if n not in small and n not in zero and n not in value
              and not n.endswith(LAMBDA_LEAVES)}
    scan, attn = got["selscan"] or {}, got["diffattn"] or {}
    lams = (np.asarray(attn.get("lambda_mean", np.nan), np.float64).ravel(),
            np.asarray(want["diffattn_stats"]["lambda_mean"], np.float64).ravel())
    return {"logit_median": float(np.quantile(err, 0.5)),
            "logit_p90": float(np.quantile(err, 0.9)), "logit_worst": float(err.max()),
            "grad_worst": max(summed.items(), key=lambda kv: kv[1]),
            "grad_small_worst": max(small.items(), key=lambda kv: kv[1]),
            "grad_zero": zero, "grad_bias_least": min(kept.items(), key=lambda kv: kv[1]),
            "grad_lambda": lam, "grad_value": value,
            "grad_named": {leaf: max(e for n, e in grad_err.items() if leaf in n)
                           for leaf in NAMED_LEAVES
                           if any(leaf in n for n in grad_err)},
            "grad_err": grad_err,
            "update_worst": max(update_err.items(), key=lambda kv: kv[1]),
            "update_with_rounding_worst": max(update_raw.items(), key=lambda kv: kv[1]),
            "loss_err": abs(got["loss"] - want["ce"]) / abs(want["ce"]),
            "loss_after_err": (abs(got["loss_after"] - want["ce_after"])
                               / abs(want["ce_after"])),
            "descends": bool(got["loss_after"] < got["loss"]),
            "state_absmax": [float(scan.get("state_absmax", np.nan)),
                             want["selscan_stats"]["state_absmax"]],
            "dt_mean": [float(scan.get("dt_mean", np.nan)),
                        want["selscan_stats"]["dt_mean"]],
            "lambdas": [lams[0].tolist(), lams[1].tolist()],
            "lambda_err": (float(np.abs(lams[0] - lams[1]).max())
                           if lams[0].shape == lams[1].shape else float("inf"))}


def verdicts(r: dict, slack: float = 1.0) -> dict:
    """Each part of ``correct`` that the readings decide, by the limits
    above: what ``run`` reports and what the calibration holds every wrong
    reference to. NaN fails (no comparison with it holds)."""
    top, dt = r["state_absmax"], r["dt_mean"]
    return {
        "loss": bool(r["loss_err"] <= slack * LOSS_RTOL
                     and r["loss_after_err"] <= slack * LOSS_AFTER_RTOL and r["descends"]),
        "logits": bool(r["logit_median"] <= slack * LOGIT_MEDIAN_RTOL
                       and r["logit_p90"] <= slack * LOGIT_P90_RTOL),
        "grads": bool(r["grad_worst"][1] <= slack * GRAD_RTOL
                      and r["grad_small_worst"][1] <= slack * GRAD_SMALL_RTOL
                      and r["update_worst"][1] <= UPDATE_RTOL),
        "lambda_grads": all(
            v["sum_over_terms"] > 0 and v["distance"] <= slack * (
                GRAD_RTOL + LAMBDA_TERMS_RTOL / v["sum_over_terms"])
            for v in r["grad_lambda"].values()),
        "value_grads": all(
            v["sum_over_terms"] > 0 and v["distance"] <= slack * (
                GRAD_RTOL + VALUE_TERMS_RTOL / v["sum_over_terms"])
            for v in r["grad_value"].values()),
        "selscan": bool(top[1] / STATE_ABSMAX_FACTOR <= top[0] <= top[1] * STATE_ABSMAX_FACTOR
                        and abs(dt[0] - dt[1]) <= DT_MEAN_RTOL * dt[1]),
        "diffattn": bool(r["lambda_err"] <= LAMBDA_ATOL)}


def run(*, cell, config, seed, seconds, trace, rehearse, t_start, device,
        compiles, out_dir, log) -> dict:
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.observability import get_registry

    tr = cell["traffic"]
    rows, seq = int(tr["global_batch"]), int(tr["seq_len"])
    layer_cfg, params, t_init = host_parameters(config, seed)
    n_params = phi4flash_cost.param_count(config)
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
    log(f"training: depth {cfg.num_hidden_layers} ({'/'.join(config['layer_types'])}, "
        f"published layers {cfg.layer_index_offset}-"
        f"{cfg.layer_index_offset + cfg.num_hidden_layers - 1}; {n_params / 1e9:.3f}B "
        f"parameters, {phi4flash_cost.bytes_at_rest(config) / 1e9:.2f} GB at rest, "
        f"vocabulary {cfg.vocab_size}), mesh {dict(engine.mesh_ctx.mesh.shape)}, batch "
        f"{rows} x {seq}; host init {t_init:.1f} s, reference {t_reference:.1f} s (peak "
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
    gc.collect()    # 8 GB of host arrays: freed now, not inside the window
    t_check = t_reference + t_program + time.monotonic() - t0
    losses = [got["loss"], got["loss_after"]]
    for _ in range(int(tr["warmup_steps"])):
        losses.append(step())
    jax.block_until_ready(engine.params)
    slack = REHEARSAL_SLACK if rehearse else 1.0
    ok = verdicts(r, slack)
    said = {name: "ok" if good else "FAILED" for name, good in ok.items()}
    log(f"correctness: loss {got['loss']:.5f} at initialisation and "
        f"{got['loss_after']:.5f} after one step on the same batch, float32 reference "
        f"{want['ce']:.5f} and {want['ce_after']:.5f} (relative difference "
        f"{r['loss_err']:.1e}, {r['loss_after_err']:.1e}; limits {LOSS_RTOL:g}, "
        f"{LOSS_AFTER_RTOL:g}; must descend): {said['loss']}; logits at {at.size} "
        f"positions, relative distance median {r['logit_median']:.3e} (limit "
        f"{slack * LOGIT_MEDIAN_RTOL:g}), 90th percentile {r['logit_p90']:.3e} (limit "
        f"{slack * LOGIT_P90_RTOL:g}), worst {r['logit_worst']:.2e}: {said['logits']}; "
        f"the step's gradients, relative distance of the worst summed leaf "
        f"{r['grad_worst'][1]:.3e} at {r['grad_worst'][0]} (limit {slack * GRAD_RTOL:g}), "
        f"of the scans' own leaves {r['grad_small_worst'][1]:.3e} at "
        f"{r['grad_small_worst'][0]} (limit {slack * GRAD_SMALL_RTOL:g}), left out as "
        f"zeros of the mathematics (share of the kernel's) "
        + (", ".join(f"{leaf} {e:.1e}" for leaf, e in r["grad_zero"].items()) or "none")
        + ", by name "
        + ", ".join(f"{leaf} {e:.3e}" for leaf, e in r["grad_named"].items())
        + f", the parameters' change against AdamW's on those gradients, the worst "
        f"leaf {r['update_worst'][1]:.1e} at {r['update_worst'][0]} (limit "
        f"{UPDATE_RTOL:g}; float32's rounding of the sum counted too, "
        f"{r['update_with_rounding_worst'][1]:.1e}): {said['grads']}; a layer's lambda "
        f"vectors (distance, |sum| over the sum of its terms' magnitudes, the limit there) "
        + ", ".join(
            f"{layer} {v['distance']:.3e} {v['sum_over_terms']:.3e} "
            + (f"{slack * (GRAD_RTOL + LAMBDA_TERMS_RTOL / v['sum_over_terms']):.3e}"
               if v["sum_over_terms"] else "none")
            for layer, v in r["grad_lambda"].items())
        + f": {said['lambda_grads']}; the biases that sum the values' gradients over "
        "the tokens (the same three) "
        + ", ".join(
            f"{'.'.join(leaf.split(chr(39))[3:8:2])} {v['distance']:.3e} "
            f"{v['sum_over_terms']:.3e} "
            + (f"{slack * (GRAD_RTOL + VALUE_TERMS_RTOL / v['sum_over_terms']):.3e}"
               if v["sum_over_terms"] else "none")
            for leaf, v in r["grad_value"].items())
        + f": {said['value_grads']}; largest |h| "
        f"{r['state_absmax'][0]:.4f} against {r['state_absmax'][1]:.4f} (within "
        f"x{STATE_ABSMAX_FACTOR:g}), mean dt {r['dt_mean'][0]:.6f} against "
        f"{r['dt_mean'][1]:.6f} (limit {DT_MEAN_RTOL:g}): {said['selscan']}; lambdas "
        f"{[round(x, 5) for x in r['lambdas'][0]]} against "
        f"{[round(x, 5) for x in r['lambdas'][1]]} (limit {LAMBDA_ATOL:g}): "
        f"{said['diffattn']}; first step {got['seconds']:.1f} s")

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

    def gauge(name):
        return reg.get(name).value if reg.get(name) is not None else None

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
             "selscan_stats_first_batch": {k: r[k] for k in ("state_absmax", "dt_mean")},
             "selscan_stats_last_step": {k: float(v) for k, v in
                                         (engine.selscan_stats() or {}).items()},
             "lambdas_first_batch": r["lambdas"],
             "grad_lambda": r["grad_lambda"], "grad_value": r["grad_value"],
             "grad_zero": r["grad_zero"],
             "grad_bias_least": r["grad_bias_least"],
             "gauges": {name: gauge(name) for name in (
                 "ds_selscan_state_absmax", "ds_selscan_dt_mean", "ds_diffattn_lambda_mean",
                 "ds_model_shared_kv_readers", "ds_model_shared_memory_readers")},
             "remat_kept_bytes": {m.labels.get("key", ""): m.value
                                  for m in reg.series("ds_remat_kept_bytes")},
             "model_layers": {m.labels["kind"]: m.value
                              for m in reg.series("ds_model_layers")},
             "verdicts": ok, "loss_last": losses[-1], "step_programs": programs,
             "n_params": n_params,
             "bytes_in_use": [int((d.memory_stats() or {}).get("bytes_in_use", 0))
                              for d in jax.devices()[:cell["chips"]]]}
    correct = all(ok.values()) and finite and programs == 1
    return {"correct": correct, "attempted": len(step_s),
            "failed": 0 if finite else 1, "end_to_end": e2e, "notes": notes,
            "setup": setup, "trace_steps": min(n_trace, len(step_s)),
            "tokens_per_step": rows * seq,
            "train_flops_per_token": phi4flash_cost.train_flops_per_token(config, seq),
            "chips": cell["chips"]}
