"""Plain reference of Ouro (``model_type: ouro``, a looped language model)
under its Stage I training loss: forward pass, loss, its gradients, the exit
distribution's statistics.

Straightforward ``jax.numpy`` in float32 with the matmul precision at
"highest". The loop is a Python loop over ONE parameter tree, an application
of a layer a call (forward, and backward through ``jax.vjp`` of the same
function: reverse mode by hand over the applications, ``sequence_parts``),
attention runs in blocks of queries; no kernel, no scan over layers, no
chunked loss, and nothing imported from the program under test. ``N`` layers, ``T =
total_ut_steps`` passes. ``h^0 = E[ids]``; for ``t = 1..T``: ``x <- h^{t-1}``;
for ``l = 0..N-1``:

    a = x + RMSNorm_{in2,l}(Attn_l(RMSNorm_{in,l}(x)))
    x = a + RMSNorm_{post2,l}(MLP_l(RMSNorm_{post,l}(a)))

then ``h^t = RMSNorm_f(x)`` (the SAME final norm every pass; the normed ``h^t``
is what pass ``t + 1`` starts from), ``logits^t = W_head h^t``, ``g^t = w_g .
h^t + b_g``. ``Attn``: 16 heads of 128 and as many key heads, no bias, no q/k
norm, rotary (half-split pairs, theta 1e6) on all 128 lanes, causal, scores
over sqrt(128). ``MLP``: ``W_down(silu(W_gate u) * W_up u)``. ``RMSNorm(u) = u
/ sqrt(mean u^2 + 1e-6) * w``.

A token's exit distribution: ``lambda_t = sigmoid(g^t)``, ``p_t = lambda_t
prod_{j<t} (1 - lambda_j)`` for ``t < T``, ``p_T = prod_{j<T} (1 - lambda_j)``
(the last gate is not read). The loss over the ``M = B (S - 1)`` shifted
positions:

    L = (1/M) sum_tokens [sum_t p_t CE(logits^t, next id) - beta H(p)],
    H(p) = -sum_t p_t log p_t

``p`` carrying a gradient through both terms; ``beta`` is the file's
``exit_entropy_weight``.

Departures from the published description, each stated: the paper's Stage II
(the gate alone trained on a detached improvement signal) is not built;
dropout none. Each application of a layer and each block of queries, of the
FFN's tokens or of the head's positions is a ``jax.checkpoint``, which changes
no value.

``wrong`` (a set of names) makes it the WRONG model in one stated way, for the
calibration of the cell's limits and nothing else: ``norm_last_only`` (the
final norm after the last pass alone; the other passes hand their stream on
and read the head and the gate unnormed), ``three_passes`` (T - 1 passes: the
third takes what mass is left), ``pre_norms_only`` (no norm on a sublayer's
output), ``no_survival`` (``p_t = lambda_t`` for ``t < T``, the last pass still
``prod (1 - lambda_j)``), ``beta_zero``, ``gate_no_bias``, ``fp8`` (every
matmul's operands rounded to fp8 e4m3's three mantissa bits: the nearest
precision below the bf16 the configuration states). ``bf16`` (the operands
rounded to bf16) is the configuration's OWN precision.

Weights come as the tree the program holds: ``model`` with ``embed_tokens``,
``norm``, ``early_exit_gate`` (``kernel [hidden, 1]``, ``bias [1]``),
``lm_head`` and the layers, either ``layers_<i>`` each or ONE ``layers/layer``
with a leading axis a layer (the program's ``scan_layers``), a layer being
``self_attn`` (``q_proj``, ``k_proj``, ``v_proj``, ``o_proj`` kernels ``[in,
out]``), ``mlp`` (``gate_proj``, ``up_proj``, ``down_proj``) and four norms
under the ``sandwich_norm`` layer's names: ``input_layernorm``,
``post_attention_layernorm`` (HF's ``input_layernorm_2``),
``pre_feedforward_layernorm`` (HF's ``post_attention_layernorm``),
``post_feedforward_layernorm`` (HF's ``post_attention_layernorm_2``).
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.kimi_vl import swiglu
from benchmark.reference.mistral import _rope, rms_norm
from benchmark.reference.qwen3_next import attend
from benchmark.reference.sdar_moe import _mm

WRONG = ("norm_last_only", "three_passes", "pre_norms_only", "no_survival", "beta_zero",
         "gate_no_bias", "fp8")
OWN_PRECISION = "bf16"
SEQ_BLOCKS = 8       # blocks of positions the head's loss and a layer's FFN run in


def layer_weights(model: dict, i: int) -> dict:
    """Layer ``i`` of either form of the tree."""
    if "layers" in model:
        return jax.tree_util.tree_map(lambda a: a[i], model["layers"]["layer"])
    return model[f"layers_{i}"]


def layer(x, lp, cfg: dict, q_block: int = 256, wrong=frozenset()):
    """One application of a layer on ``x [s, hidden]``."""
    eps = float(cfg["rms_norm_eps"])
    H, KV, d = (int(cfg[key]) for key in ("num_attention_heads", "num_key_value_heads",
                                          "head_dim"))
    s = x.shape[0]

    def after(out, name):
        if "pre_norms_only" in wrong:
            return out
        return rms_norm(out, lp[name]["weight"], eps)

    a, h = lp["self_attn"], rms_norm(x, lp["input_layernorm"]["weight"], eps)
    positions = jnp.arange(s)[None]
    q, k = (_rope(_mm(h, a[name]["kernel"], wrong).reshape(1, s, heads, d), positions,
                  float(cfg["rope_theta"]))[0]
            for name, heads in (("q_proj", H), ("k_proj", KV)))
    v = _mm(h, a["v_proj"]["kernel"], wrong).reshape(s, KV, d)
    mixed = _mm(attend(q, k, v, 1.0 / float(np.sqrt(d)), q_block, wrong),
                a["o_proj"]["kernel"], wrong)
    r = x + after(mixed, "post_attention_layernorm")
    u = rms_norm(r, lp["pre_feedforward_layernorm"]["weight"], eps)
    # the FFN a block of tokens at a time (a row's result is its own): a
    # backward pass holds one block's 5,632-wide values, not the sequence's
    blocks = SEQ_BLOCKS if s % SEQ_BLOCKS == 0 else 1
    ffn = jax.lax.map(jax.checkpoint(lambda ub: swiglu(ub, lp["mlp"], wrong)),
                      u.reshape(blocks, s // blocks, -1)).reshape(s, -1)
    return r + after(ffn, "post_feedforward_layernorm")


def passes_of(cfg: dict, wrong=frozenset()) -> int:
    return int(cfg["total_ut_steps"]) - ("three_passes" in wrong)


def exit_distribution(gates, wrong=frozenset()):
    """``gates [T - 1, s]`` -> ``p [T, s]``: the products written out."""
    lam = jax.nn.sigmoid(gates)
    survive = jnp.cumprod(1.0 - lam, axis=0)                    # prod_{j<=t}
    before = jnp.concatenate([jnp.ones_like(lam[:1]), survive[:-1]])
    early = lam if "no_survival" in wrong else lam * before
    return jnp.concatenate([early, survive[-1:]])


def loss_of_streams(hs, head, gate, targets, at, cfg: dict, wrong=frozenset()):
    """What the head reads after each pass ``hs [T, s, hidden]`` -> (the SUM
    over the counted positions of ``sum_t p_t CE_t - beta H(p)``, (the sums of
    ``CE_t`` ``[T]``, of ``p_t`` ``[T]`` and of ``H``, the logits ``[2,
    len(at), vocab]`` of the first and the last pass at positions ``at``))."""
    seq = hs.shape[1]
    counted = (jnp.arange(seq) < seq - 1).astype(jnp.float32)
    blocks = SEQ_BLOCKS if seq % SEQ_BLOCKS == 0 else 1
    gates = jnp.einsum("tsh,h->ts", hs[:-1], gate["kernel"].astype(jnp.float32)[:, 0],
                       precision=jax.lax.Precision.HIGHEST)
    if "gate_no_bias" not in wrong:
        gates = gates + gate["bias"][0]

    @jax.checkpoint
    def block(args):
        xb, tb = args
        lg = _mm(xb, head, wrong)
        gold = jnp.take_along_axis(lg, tb[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(lg, axis=-1) - gold

    split = lambda a: a.reshape(blocks, seq // blocks, *a.shape[1:])     # noqa: E731
    ce = jnp.stack([jax.lax.map(block, (split(x), split(targets))).reshape(seq)
                    for x in hs])                                       # [T, seq]
    p = exit_distribution(gates, wrong)
    entropy = -jnp.sum(p * jnp.log(p), axis=0)
    beta = 0.0 if "beta_zero" in wrong else float(cfg["exit_entropy_weight"])
    loss = jnp.sum((jnp.sum(p * ce, axis=0) - beta * entropy) * counted)
    sums = (jnp.sum(ce * counted, axis=1), jnp.sum(p * counted, axis=1),
            jnp.sum(entropy * counted))
    logits = jnp.stack([_mm(x[at], head, wrong) for x in (hs[0], hs[-1])])
    return loss, (sums, logits)


@functools.lru_cache(maxsize=None)
def _programs(cfg_json: str, wrong: frozenset) -> dict:
    """The compiled pieces one sequence's pass is made of: a layer forward and
    its vector-Jacobian product, the final norm's, the loss of the streams
    with its gradients. One application a call: the chip never holds more
    than one layer's values beside the saved inputs (a single program of 32
    checkpointed applications is scheduled with several layers'
    recomputation at once: 23 GB at 16,384 positions)."""
    cfg = json.loads(cfg_json)
    eps = float(cfg["rms_norm_eps"])
    one = functools.partial(layer, cfg=cfg, wrong=wrong)
    loss = functools.partial(loss_of_streams, cfg=cfg, wrong=wrong)

    def back(fn):
        return jax.jit(lambda ct, *args: jax.vjp(fn, *args)[1](ct))

    norm = lambda x, w: rms_norm(x, w, eps)     # noqa: E731
    return {"layer": jax.jit(one), "layer_back": back(one),
            "norm": jax.jit(norm), "norm_back": back(norm),
            "loss": jax.jit(loss),
            "loss_grad": jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))}


def sequence_parts(params, ids, at, cfg: dict, wrong=frozenset(), gradients: bool = True):
    """One sequence ``ids [seq]`` -> ``loss_of_streams``'s values and, with
    ``gradients``, the gradient of its loss in the tree's own form: the forward
    pass an application of a layer at a time with every application's input
    kept, then reverse mode by hand, the same applications backwards, a layer's
    gradient being the sum over its passes."""
    run = _programs(json.dumps({k: cfg[k] for k in KEYS}), frozenset(wrong))
    m = params["model"]
    depth = int(cfg["num_hidden_layers"])
    layers = [layer_weights(m, i) for i in range(depth)]
    passes = passes_of(cfg, wrong)
    normed = [("norm_last_only" not in wrong or t == passes - 1) for t in range(passes)]
    x = jnp.take(m["embed_tokens"]["embedding"], ids, axis=0).astype(jnp.float32)
    inputs, raw, hs = [], [], []
    for t in range(passes):
        for lp in layers:
            inputs.append(x)
            x = run["layer"](x, lp)
        raw.append(x)
        x = run["norm"](x, m["norm"]["weight"]) if normed[t] else x
        hs.append(x)
    hs = jnp.stack(hs)
    targets = jnp.concatenate([ids[1:], ids[:1]])
    head, gate = m["lm_head"]["kernel"], m["early_exit_gate"]
    if not gradients:
        return run["loss"](hs, head, gate, targets, at), None
    out, (d_hs, d_head, d_gate) = run["loss_grad"](hs, head, gate, targets, at)
    del hs
    d_layers = [None] * depth
    d_norm, ct = jnp.zeros_like(m["norm"]["weight"]), jnp.zeros_like(x)
    for t in reversed(range(passes)):
        ct = ct + d_hs[t]                   # from the head and the gate, and from pass t + 1
        if normed[t]:
            ct, d_w = run["norm_back"](ct, raw.pop(), m["norm"]["weight"])
            d_norm = d_norm + d_w
        for i in reversed(range(depth)):
            ct, d_lp = run["layer_back"](ct, inputs.pop(), layers[i])
            d_layers[i] = d_lp if d_layers[i] is None else jax.tree_util.tree_map(
                jnp.add, d_layers[i], d_lp)
    d_table = jnp.zeros_like(m["embed_tokens"]["embedding"]).at[ids].add(ct)
    rest = {"embed_tokens": {"embedding": d_table}, "lm_head": {"kernel": d_head},
            "norm": {"weight": d_norm}, "early_exit_gate": d_gate}
    if "layers" in m:
        grads = {**rest, "layers": {"layer": jax.tree_util.tree_map(
            lambda *a: jnp.stack(a), *d_layers)}}
    else:
        grads = {**rest, **{f"layers_{i}": d for i, d in enumerate(d_layers)}}
    return out, {"model": {name: grads[name] for name in m}}


KEYS = ("num_hidden_layers", "num_attention_heads", "num_key_value_heads", "head_dim",
        "rms_norm_eps", "rope_theta", "total_ut_steps", "exit_entropy_weight")


def step_parts(params, ids, cfg: dict, at, wrong=frozenset(),
               gradients: bool = True) -> dict:
    """What one training step on ``ids [rows, seq]`` has to reproduce, one
    sequence at a time and each a single compiled pass: ``ce`` (the loss
    ``L``), ``ce_pass`` ``[T]`` (the mean next-token CE of each pass's own
    logits), ``exit_mass`` ``[T]`` (the mean ``p_t``), ``exit_entropy`` (the
    mean ``H(p)``), ``grads`` (``jax.grad`` of ``L``, numpy float32, in the
    tree's own form; None without ``gradients``), ``logits`` ``[rows, 2, n,
    vocab]`` of the first and the last pass at each sequence's positions
    ``at[row]``. A pass without ``gradients`` runs the forward pieces the
    other compiled: no program more."""
    ids = np.asarray(ids)
    rows, seq = ids.shape
    tokens = rows * (seq - 1)
    with jax.default_matmul_precision("highest"):
        loss, sums, grads, logits = 0.0, None, None, []
        for row in range(rows):
            (part, (part_sums, lg)), grad = sequence_parts(
                params, jnp.asarray(ids[row]), jnp.asarray(at[row]), cfg, wrong, gradients)
            loss += float(part)
            part_sums = [np.asarray(s, np.float64) for s in part_sums]
            sums = part_sums if sums is None else [a + b for a, b in zip(sums, part_sums)]
            logits.append(np.asarray(lg))
            if gradients:
                grad = jax.tree_util.tree_map(lambda g: np.asarray(g) / tokens, grad)
                grads = grad if grads is None else jax.tree_util.tree_map(
                    np.add, grads, grad)
    return {"ce": loss / tokens, "ce_pass": sums[0] / tokens, "exit_mass": sums[1] / tokens,
            "exit_entropy": float(sums[2]) / tokens, "grads": grads,
            "logits": np.stack(logits)}
