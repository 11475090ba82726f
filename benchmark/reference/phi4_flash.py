"""Plain reference of Phi-4-mini-flash's blocks (SambaY with differential
attention): forward pass, loss and gradients.

Straightforward ``jax.numpy`` in float32 with the matmul precision at
"highest". No kernels, no stacked attention call, nothing imported from the
program under test; ``jax.grad`` of the loss gives the step's gradients. It
follows the papers (SambaY arXiv:2507.06607, Samba 2406.07522, YOCO
2405.05254, Differential Transformer 2410.05258, Mamba 2312.00752) and the
published ``config.json``; what the config leaves open is the configuration
file's ``assumed``. Per token ``x`` of ``hidden_size``, no position embedding:

    h   = x + mixer_i(LN(x, operator_norm))          LayerNorm with scale and bias
    out = h + W_down(silu(W_gate LN(h)) * W_up LN(h))
    logits = LN(x, norm) embed^T                      (tied, no bias)

``mixer_i`` by the layer's kind (``layer_types[i]``):

- ``mamba`` (Mamba-1): ``x | z = W_in u``; ``x = silu(conv(x) + bias)``
  (depthwise, causal, ``w[j]`` multiplies ``x[t - (L - 1) + j]``, zeros left of
  the sequence); ``delta | B | C = W_x x``; ``dt = softplus(W_dt delta +
  b_dt)``; ``A = -exp(A_log)`` ``[inner, N]``; one token after another in a
  ``lax.scan``, the state ``h`` ``[inner, N]`` float32 and zero before the
  first token:

      h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t) B_t^T,    y_t = h_t C_t + D x_t

  then ``W_out (y * silu(z))``. The layer the ``gmu`` layers read also hands on
  ``M = y`` (before the gate).
- ``sliding_attention`` / ``full_attention`` (differential attention): ``q | k
  | v`` with biases; the heads in adjacent pairs, query pair ``p`` reading
  key/value pair ``g = p // group``; TWO softmax maps a pair, written out:

      A1 = softmax(q_{2p} k_{2g}^T / sqrt(d) + m),  A2 = softmax(q_{2p+1} k_{2g+1}^T / sqrt(d) + m)
      o_p = (1 - l_init) * rms((A1 - l A2) [v_{2g} | v_{2g+1}]) * subln
      l = exp(lq1 . lk1) - exp(lq2 . lk2) + l_init,   l_init = 0.8 - 0.6 exp(-0.3 i_published)

  ``m`` the causal mask, under ``sliding_window`` also ``t - s < window``;
  then ``W_o [o_p] + b_o``. The full layer hands on its ``k`` and ``v``.
- ``cross_attention``: the same with the layer's own ``q`` (``W_q``, bias) and
  lambdas against the handed ``k`` and ``v``, causal.
- ``gmu``: ``W_2 (M * silu(W_1 u))``.

Token-mean cross-entropy with the shift by one. A sliced vocabulary is a
smaller vocabulary: the embedding has that many rows.

Departures, each stated; none changes a value:
- the recurrence runs in blocks of ``SCAN_BLOCK`` tokens, each a
  ``jax.checkpoint``, so that a backward pass holds a block's states and not
  all of them; no chunk enters the arithmetic;
- attention runs in blocks of queries, the FFN and the head in blocks of the
  sequence, every block and every layer a ``jax.checkpoint``; the window is a
  mask over all keys (no key is skipped);
- ``K``, ``V`` and ``M`` are passed from layer to layer by hand;
- one sequence at a time;
- every differential layer's ``l`` takes an addend of zero for each (query
  token, pair), the ``probes``: the gradient of the loss to it is that token's
  and pair's TERM of ``dL/dl``, so the pass also says how far the terms cancel
  in their sum (``lambda_terms``: the sum's magnitude and the sum of the
  terms' magnitudes), which is what tells a gradient of ``l`` that rounding
  moves by its own size from one it does not;
- every differential layer's ``(A1 - l A2) V`` takes an addend of zero for
  each (pair, value channel), the same for every query token (``"out"`` beside
  ``"lam"`` in the layer's ``probes``): a softmax's rows sum to one, so a
  constant added to every value comes out of ``A1 V`` whole and out of ``l A2
  V`` times ``l``, and the gradient ``G`` to that addend, summed over the
  query pairs of a value pair, is the reading layer's TERM of the value
  projection's bias gradient by the first map, ``-l G`` by the second. The
  bias's gradient is the sum over its readers of ``(1 - l) G``: with ``l`` near
  1 it is the difference of two terms each ``1 / |1 - l|`` of its size, and
  the operator norm's bias, whose gradient is the three projections' bias
  gradients through their kernels, holds the same two terms (``value_terms``:
  each leaf's gradient's magnitude and the sum of its terms' magnitudes).

It also reports the largest ``|h|`` over the tokens that end a run of
``STAT_EVERY`` (those are the states the program's kernels keep and can
report), the mean ``dt`` and each differential layer's ``l``.

``wrong`` (a set of names) makes it the WRONG model in one stated way, for the
calibration of the cell's limits and nothing else: ``one_decay_a_channel``
(``A``'s mean over the states for all of them: Mamba-2's form),
``no_softplus``, ``no_d_term``, ``bf16_state`` (the state rounded to bf16 after
every token), ``memory_after_gate`` (``M = y * silu(z)``), ``gmu_reads_first_scan``
(the GMU gating the FIRST Mamba layer's output), ``cross_own_kv`` (the cross
layer's keys and values projected from its own input with the full layer's
matrices), ``no_window``, ``window_everywhere`` (the window also in the full
layer), ``no_subtraction`` (``l = 0``), ``no_one_minus_lambda_init``,
``no_subln`` (neither the norm nor its scale), ``fp8`` (every matmul's operands
rounded to fp8 e4m3's three mantissa bits: the nearest precision below the
bf16 the configuration states). ``bf16`` (the operands rounded to bf16) is the
configuration's OWN precision: required of nothing.

Weights come as the tree the program holds (``{"model": {"embed_tokens":
{"embedding"}, "layers_<i>": {"operator_norm", "ffn_norm" (scale, bias),
"mamba": {in_proj, conv_weight [L, C], conv_bias, x_proj, dt_proj {kernel,
bias}, A_log, D, out_proj} or {in_proj, out_proj} (gmu), or "self_attn":
{q_proj, [k_proj, v_proj,] o_proj (kernel, bias), lambda_q1.., subln}, "mlp":
{gate_proj, up_proj, down_proj}}, "norm"}}``; kernels ``[in, out]``).
"""

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.mistral import _HI
from benchmark.reference.sdar_moe import _mm

WRONG = ("one_decay_a_channel", "no_softplus", "no_d_term", "bf16_state",
         "memory_after_gate", "gmu_reads_first_scan", "cross_own_kv", "no_window",
         "window_everywhere", "no_subtraction", "no_one_minus_lambda_init", "no_subln",
         "fp8")
OWN_PRECISION = "bf16"
SCAN_BLOCK = 128     # tokens of the recurrence a checkpoint
STAT_EVERY = 128     # the program's kernels keep a state every so many tokens
SEQ_BLOCKS = 8       # blocks of the sequence the FFN and the head run in
KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads", "layer_norm_eps",
        "sliding_window", "layer_types", "layer_offset")


def layer_norm(x, p, eps: float):
    x = x.astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32))


def _in_blocks(fn, x, blocks: int):
    """``fn`` over ``blocks`` equal row blocks of ``x [rows, ...]``, each a
    checkpoint (one block where ``blocks`` does not divide the rows)."""
    rows = x.shape[0]
    if rows % blocks:
        blocks = 1
    out = jax.lax.map(jax.checkpoint(fn), x.reshape(blocks, rows // blocks, *x.shape[1:]))
    return out.reshape(rows, *out.shape[2:])


def _linear(h, p, wrong):
    out = _mm(h, p["kernel"], wrong)
    return out + p["bias"].astype(jnp.float32) if "bias" in p else out


def recurrence(x, dt, A, B, C, D, wrong=frozenset()):
    """``x``, ``dt`` ``[s, E]``, ``A`` ``[E, N]``, ``B``, ``C`` ``[s, N]``, ``D``
    ``[E]`` -> (``y [s, E]``, the largest ``|h|`` over the tokens that end a run
    of ``STAT_EVERY`` or the sequence): the recurrence token by token."""
    s, E = x.shape
    ends = ((jnp.arange(s) + 1) % STAT_EVERY == 0).at[s - 1].set(True)

    def token(carry, inp):
        h, top = carry
        xt, dtt, Bt, Ct, end = inp
        h = jnp.exp(dtt[:, None] * A) * h + (dtt * xt)[:, None] * Bt[None, :]
        if "bf16_state" in wrong:
            h = jax.lax.reduce_precision(h, 8, 7)
        y = jnp.sum(h * Ct[None, :], axis=-1)
        if "no_d_term" not in wrong:
            y = y + D * xt
        size = jax.lax.stop_gradient(jnp.max(jnp.abs(h)))
        return (h, jnp.where(end, jnp.maximum(top, size), top)), y

    block = next(b for b in (SCAN_BLOCK, 64, 32, 16, 8, 4, 2, 1) if s % b == 0)

    @jax.checkpoint
    def tokens(carry, inps):
        return jax.lax.scan(token, carry, inps)

    inputs = [a.reshape(s // block, block, *a.shape[1:]) for a in (x, dt, B, C, ends)]
    init = (jnp.zeros((E, A.shape[1]), jnp.float32), jnp.float32(0.0))
    (_, top), y = jax.lax.scan(tokens, init, inputs)
    return y.reshape(s, E), top


def mamba_mixer(u, mp, wrong=frozenset()):
    """``u [s, hidden]`` float32 -> (the mixer's output, the memory ``M [s,
    inner]`` it would hand on, ``[largest |h|, mean dt]``)."""
    s = u.shape[0]
    x, z = jnp.split(_mm(u, mp["in_proj"]["kernel"], wrong), 2, axis=-1)
    w = mp["conv_weight"].astype(jnp.float32)                        # [L, C]
    taps = w.shape[0]
    padded = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    x = sum(w[j] * padded[j:j + s] for j in range(taps))
    x = jax.nn.silu(x + mp["conv_bias"].astype(jnp.float32))
    rank = mp["dt_proj"]["kernel"].shape[0]
    A = -jnp.exp(mp["A_log"].astype(jnp.float32))
    N = A.shape[1]
    delta, B, C = jnp.split(_mm(x, mp["x_proj"]["kernel"], wrong), [rank, rank + N],
                            axis=-1)
    dt = _linear(delta, mp["dt_proj"], wrong)
    if "no_softplus" not in wrong:
        dt = jax.nn.softplus(dt)
    if "one_decay_a_channel" in wrong:
        A = jnp.broadcast_to(jnp.mean(A, axis=1, keepdims=True), A.shape)
    y, top = recurrence(x, dt, A, B, C, mp["D"].astype(jnp.float32), wrong)
    gated = y * jax.nn.silu(z)
    memory = gated if "memory_after_gate" in wrong else y
    return (_mm(gated, mp["out_proj"]["kernel"], wrong), memory,
            jnp.stack([top, jnp.mean(dt)]))


def differential_attention(q, k, v, ap, lam_init: float, window, eps: float,
                           wrong=frozenset(), q_block: int = 512, probe=None,
                           out_probe=None):
    """``q [s, H, d]``, ``k``, ``v`` ``[s, KV, d]`` -> (``[s, H * d]``, the
    layer's ``l``): two causal softmaxes a pair of heads (under ``window`` too
    where it is not None) and their difference over the pair's double-width
    value, a block of queries at a time. ``probe [s, H / 2]``: zeros added to
    ``l`` a query token and pair, ``out_probe [H / 2, 2 d]``: zeros added to
    every token's ``(A1 - l A2) V`` (the head of the file says what for)."""
    s, H, d = q.shape
    if probe is None:
        probe = jnp.zeros((s, H // 2), jnp.float32)
    KV = k.shape[1]
    group = (H // 2) // (KV // 2)
    if s % q_block:
        q_block = s
    f32 = jnp.float32
    lam = (jnp.exp(jnp.sum(ap["lambda_q1"].astype(f32) * ap["lambda_k1"].astype(f32)))
           - jnp.exp(jnp.sum(ap["lambda_q2"].astype(f32) * ap["lambda_k2"].astype(f32)))
           + lam_init)
    used = jnp.float32(0.0) if "no_subtraction" in wrong else lam
    # pair g of the keys and values for every query pair p
    k1 = jnp.repeat(k[:, 0::2], group, axis=1)                   # [s, H / 2, d]
    k2 = jnp.repeat(k[:, 1::2], group, axis=1)
    vv = jnp.repeat(v.reshape(s, KV // 2, 2 * d), group, axis=1)  # [s, H / 2, 2 d]
    scale = 1.0 / float(np.sqrt(d))

    @jax.checkpoint
    def block(args):
        q1, q2, zeros, first = args
        at = first + jnp.arange(q_block)[:, None]
        seen = jnp.arange(s)[None, :] <= at
        if window is not None:
            seen = seen & (at - jnp.arange(s)[None, :] < window)

        def softmax_map(qh, kh):
            scores = jnp.einsum("qhd,khd->hqk", qh, kh, precision=_HI) * scale
            return jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)

        both = softmax_map(q1, k1) - (used + zeros.T[:, :, None]) * softmax_map(q2, k2)
        return jnp.einsum("hqk,khd->qhd", both, vv, precision=_HI)

    split = lambda a: a.reshape(s // q_block, q_block, H // 2, d)    # noqa: E731
    if "no_subtraction" in wrong:
        probe = jax.lax.stop_gradient(probe)
    o = jax.lax.map(block, (split(q[:, 0::2]), split(q[:, 1::2]),
                            probe.reshape(s // q_block, q_block, H // 2),
                            jnp.arange(0, s, q_block))).reshape(s, H // 2, 2 * d)
    if out_probe is not None:
        o = o + out_probe[None]
    if "no_subln" not in wrong:
        o = (o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
             * ap["subln"].astype(f32))
    if "no_one_minus_lambda_init" not in wrong:
        o = o * (1.0 - lam_init)
    return o.reshape(s, H * d), lam


def swiglu(h, f, wrong=frozenset()):
    def block(rows):
        return _mm(jax.nn.silu(_mm(rows, f["gate_proj"]["kernel"], wrong))
                   * _mm(rows, f["up_proj"]["kernel"], wrong),
                   f["down_proj"]["kernel"], wrong)
    return _in_blocks(block, h, SEQ_BLOCKS)


def _layer(x, lp, shared, probe, i: int, cfg: dict, wrong):
    """Layer ``i`` on one sequence ``x [s, hidden]``; ``shared``: ``{"kv": (k,
    v) or None, "memory": M or None, "first_memory": ..., "kv_weights": ...}``
    as the layers before left them; ``probe``: a differential layer's zeros
    ``{"lam", "out"}`` (None: none) -> (the stream after it, what the layer hands on
    ``{...}``, its statistics)."""
    eps = float(cfg["layer_norm_eps"])
    kind = cfg["layer_types"][i]
    u = layer_norm(x, lp["operator_norm"], eps)
    handed, stats = {}, {}
    if kind == "mamba":
        out, memory, stat = mamba_mixer(u, lp["mamba"], wrong)
        handed, stats = {"memory": memory}, {"selscan": stat}
    elif kind == "gmu":
        memory = shared["first_memory" if "gmu_reads_first_scan" in wrong else "memory"]
        gate = jax.nn.silu(_mm(u, lp["mamba"]["in_proj"]["kernel"], wrong))
        out = _mm(memory * gate, lp["mamba"]["out_proj"]["kernel"], wrong)
    else:
        a = lp["self_attn"]
        heads, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
        s, d = x.shape[0], int(cfg["hidden_size"]) // heads
        q = _linear(u, a["q_proj"], wrong).reshape(s, heads, d)
        if kind == "cross_attention":
            k, v = shared["kv"]
            if "cross_own_kv" in wrong:
                k, v = (_linear(u, shared["kv_weights"][n], wrong).reshape(s, kv, d)
                        for n in ("k_proj", "v_proj"))
        else:
            k, v = (_linear(u, a[n], wrong).reshape(s, kv, d)
                    for n in ("k_proj", "v_proj"))
            handed = {"kv": (k, v)}
        windowed = (kind == "sliding_attention" and "no_window" not in wrong) or (
            kind == "full_attention" and "window_everywhere" in wrong)
        lam_init = 0.8 - 0.6 * float(np.exp(-0.3 * (i + int(cfg.get("layer_offset", 0)))))
        o, lam = differential_attention(
            q, k, v, a, lam_init, int(cfg["sliding_window"]) if windowed else None, eps,
            wrong, probe=(probe or {}).get("lam"), out_probe=(probe or {}).get("out"))
        out = _linear(o, a["o_proj"], wrong)
        stats = {"lambda": lam}
    x = x + out
    return x + swiglu(layer_norm(x, lp["ffn_norm"], eps), lp["mlp"], wrong), handed, stats


def hidden_states(params, ids, cfg: dict, wrong=frozenset(), probes=None):
    """One sequence ``ids [s]`` -> (final-norm hidden states ``[s, hidden]``,
    ``[largest |h|, mean dt]`` over the Mamba layers, each differential
    layer's ``l``). ``probes``: ``{"layers_<i>": {"lam": zeros [s, pairs],
    "out": zeros [pairs, 2 d]}}`` of the differential layers."""
    m = params["model"]
    x = jnp.take(m["embed_tokens"]["embedding"], ids, axis=0).astype(jnp.float32)
    shared = {"kv": None, "memory": None, "first_memory": None, "kv_weights": None}
    scans, lams = [], []
    for i, kind in enumerate(cfg["layer_types"]):
        layer = jax.checkpoint(functools.partial(_layer, i=i, cfg=cfg, wrong=wrong))
        x, handed, stats = layer(x, m[f"layers_{i}"], shared,
                                 (probes or {}).get(f"layers_{i}"))
        # K, V and M by hand: the LAST Mamba layer's memory and the full
        # layer's keys and values are what the layers after read
        if "memory" in handed:
            shared = dict(shared, memory=handed["memory"])
            if shared["first_memory"] is None:
                shared["first_memory"] = handed["memory"]
        if "kv" in handed and kind == "full_attention":
            shared = dict(shared, kv=handed["kv"],
                          kv_weights=m[f"layers_{i}"]["self_attn"])
        if "selscan" in stats:
            scans.append(stats["selscan"])
        if "lambda" in stats:
            lams.append(stats["lambda"])
    # a stack without a kind (a test's single layer) reports zeros for it
    scans = jnp.stack(scans) if scans else jnp.zeros((1, 2), jnp.float32)
    return (layer_norm(x, m["norm"], float(cfg["layer_norm_eps"])),
            jnp.stack([jnp.max(scans[:, 0]), jnp.mean(scans[:, 1])]),
            jnp.stack(lams) if lams else jnp.zeros((0, ), jnp.float32))


def _sequence_nll(params, probes, ids, at, cfg: dict, wrong, loss_positions: int):
    """One sequence ``ids [s]`` -> (the sum of its next-token losses over its
    first ``loss_positions`` positions (0: all), (logits ``[len(at), vocab]``,
    the scans' statistics, the lambdas))."""
    x, scans, lams = hidden_states(params, ids, cfg, wrong, probes)
    head = params["model"]["embed_tokens"]["embedding"].T
    seq = ids.shape[0]
    # the last position predicts nothing: its label is a filler, its loss dropped
    targets = jnp.concatenate([ids[1:], ids[:1]])
    counted = (jnp.arange(seq) < (loss_positions or seq - 1)) & (jnp.arange(seq) < seq - 1)
    blocks = SEQ_BLOCKS if seq % SEQ_BLOCKS == 0 else 1

    @jax.checkpoint
    def block(args):
        xb, tb, wb = args
        lg = _mm(xb, head, wrong)
        gold = jnp.take_along_axis(lg, tb[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(lg, axis=-1) - gold) * wb)

    split = lambda a: a.reshape(blocks, seq // blocks, *a.shape[1:])     # noqa: E731
    nll = jnp.sum(jax.lax.map(block, (split(x), split(targets),
                                      split(counted.astype(jnp.float32)))))
    return nll, (_mm(x[at], head, wrong), scans, lams)


@functools.lru_cache(maxsize=None)
def _compiled_pass(cfg_json: str, wrong: frozenset, gradients: bool, loss_positions: int):
    fn = functools.partial(_sequence_nll, cfg=json.loads(cfg_json), wrong=wrong,
                           loss_positions=loss_positions)
    return jax.jit(jax.value_and_grad(fn, argnums=(0, 1), has_aux=True) if gradients else fn)


def step_parts(params, ids, cfg: dict, at, wrong=frozenset(), gradients: bool = True,
               loss_positions: int = 0, one_program: bool = False) -> dict:
    """What one training step on ``ids [rows, seq]`` has to reproduce, one
    sequence at a time and each a single compiled pass: ``ce`` (the token-mean
    next-token loss; over each sequence's first ``loss_positions`` positions
    where that is not 0), ``grads`` (``jax.grad`` of ``ce``, numpy float32; None
    without ``gradients``), ``logits`` ``[rows, n, vocab]`` at each sequence's
    positions ``at[row]``, ``selscan_stats`` (``state_absmax`` the largest
    ``|h|`` at the ends of runs of ``STAT_EVERY`` tokens, ``dt_mean``) and
    ``diffattn_stats`` (``lambda_mean``: each differential layer's ``l``) and,
    with ``gradients``, ``lambda_terms`` (``{"layers_<i>": [|dL/dl|, the sum of
    the magnitudes of its terms, one a token and pair]}``) and ``value_terms``
    (``_value_terms``).
    ``one_program``: a pass without ``gradients`` runs the gradients' program
    and drops them (a second program of the cell's size costs more to compile
    than the backward to run)."""
    differentiated = gradients or one_program
    fn = _compiled_pass(json.dumps({k: cfg[k] for k in KEYS if k in cfg}),
                        frozenset(wrong), differentiated, int(loss_positions))
    ids = np.asarray(ids)
    rows, seq = ids.shape
    tokens = rows * (min(loss_positions, seq - 1) if loss_positions else seq - 1)
    heads, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    pairs, wide = heads // 2, 2 * (int(cfg["hidden_size"]) // heads)
    probes = {f"layers_{i}": {"lam": jnp.zeros((seq, pairs), jnp.float32),
                              "out": jnp.zeros((pairs, wide), jnp.float32)}
              for i, kind in enumerate(cfg["layer_types"]) if kind.endswith("attention")}
    readers_G = {layer: 0.0 for layer in probes}
    with jax.default_matmul_precision("highest"):
        nll, grads, logits, scans, lams, terms = 0.0, None, [], [], [], []
        for row in range(rows):
            out = fn(params, probes, jnp.asarray(ids[row]), jnp.asarray(at[row]))
            (part, (lg, scan, lam)), (grad, term) = out if differentiated else (out, (None, ) * 2)
            nll += float(part)
            logits.append(np.asarray(lg))
            scans.append(np.asarray(scan, np.float64))
            lams.append(np.asarray(lam, np.float64))
            if gradients:
                grad = jax.tree_util.tree_map(lambda g: np.asarray(g) / tokens, grad)
                grads = grad if grads is None else jax.tree_util.tree_map(
                    np.add, grads, grad)
                terms.append({layer: np.asarray(t["lam"], np.float64) / tokens
                              for layer, t in term.items()})
                for layer in probes:
                    # the reader's ``G`` a value pair, as the bias is laid out
                    readers_G[layer] = readers_G[layer] + (
                        np.asarray(term[layer]["out"], np.float64) / tokens).reshape(
                            kv // 2, pairs // (kv // 2), wide).sum(axis=1).ravel()
    scans = np.stack(scans)
    return {"ce": nll / tokens, "grads": grads, "logits": np.stack(logits),
            "selscan_stats": {"state_absmax": float(scans[:, 0].max()),
                              "dt_mean": float(scans[:, 1].mean())},
            "diffattn_stats": {"lambda_mean": np.mean(np.stack(lams), axis=0)},
            "lambda_terms": {layer: [float(abs(sum(t[layer].sum() for t in terms))),
                                     float(sum(np.abs(t[layer]).sum() for t in terms))]
                             for layer in (probes if gradients else ())},
            "value_terms": _value_terms(
                params, grads, cfg, readers_G,
                0.0 * lams[0] if "no_subtraction" in wrong else np.mean(lams, axis=0))
            if gradients else {}}


def _value_terms(params, grads, cfg: dict, readers_G: dict, lams) -> dict:
    """``{leaf: [|its gradient|, the sum of its terms' magnitudes]}`` of the two
    biases of a layer that are plain sums over the tokens of what the two maps
    of a differential layer send back to the values (the head of the file):
    ``v_proj``'s, ``sum over the readers of (1 - l) G`` (terms ``G`` and ``-l
    G`` a reader), and the operator norm's, which is ``W_q dq + W_k dk + W_v
    dv`` of the three projections' bias gradients (terms: the first two, and
    ``W_v G``, ``-l W_v G`` a reader). ``readers_G``: each differential layer's
    ``G``, ``lams`` their ``l``, both in the layers' order; a cross layer's
    values are the last full layer's before it. Leaves named as
    ``jax.tree_util.keystr`` names them."""
    out, full, own = {}, None, {}
    for i, kind in enumerate(cfg["layer_types"]):
        full = i if kind == "full_attention" else full
        if kind.endswith("attention"):
            own.setdefault(f"layers_{full if kind == 'cross_attention' else i}",
                           []).append(f"layers_{i}")
    l_of = dict(zip(readers_G, np.asarray(lams, np.float64)))
    norm = np.linalg.norm
    for layer, readers in own.items():
        a, g = (tree["model"][layer]["self_attn"] for tree in (params, grads))
        W = {n: np.asarray(a[n]["kernel"], np.float64) for n in ("q_proj", "k_proj", "v_proj")}
        parts = [(readers_G[r], l_of[r]) for r in readers]
        through = [W[n] @ np.asarray(g[n]["bias"], np.float64) for n in ("q_proj", "k_proj")]
        out[f"['model']['{layer}']['self_attn']['v_proj']['bias']"] = [
            float(norm(sum((1 - l) * G for G, l in parts))),
            float(sum((1 + abs(l)) * norm(G) for G, l in parts))]
        out[f"['model']['{layer}']['operator_norm']['bias']"] = [
            float(norm(sum(through) + sum((1 - l) * (W["v_proj"] @ G) for G, l in parts))),
            float(sum(norm(t) for t in through)
                  + sum((1 + abs(l)) * norm(W["v_proj"] @ G) for G, l in parts))]
    return out


def logits_of(params, ids, cfg: dict) -> np.ndarray:
    """Every position's logits of one sequence ``ids [s]``, ``[s, vocab]``
    (tests: a vocabulary slice's logits are the uncut model's columns)."""
    with jax.default_matmul_precision("highest"):
        x, _, _ = hidden_states(params, jnp.asarray(ids), cfg)
        return np.asarray(_mm(x, params["model"]["embed_tokens"]["embedding"].T))
