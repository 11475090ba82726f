"""Memory-efficient (chunked) unembed + cross-entropy.

TPU analog of the reference's fused-softmax/logit kernels for large vocab
(``csrc/transformer/inference/csrc/softmax.cu`` handles the on-device
softmax; training CE in the reference stays torch — at 32k–256k vocab the
``[tokens, vocab]`` logits tensor is the single biggest training activation:
bs16 x seq1024 x 32k fp32 is 2.1 GB saved for backward, 8+ GB at Gemma's
256k).

This op never materializes the full logits matrix, and runs the vocabulary
matmul three times a step, which is what a dense head costs:

- It chunks over **sequence positions** with the whole vocabulary in each
  chunk: the ``B * sc`` rows of ``x[:, s0:s0+sc]`` times ``w`` give fp32
  logits ``[B * sc, V]`` whose rows are complete, so a row's logsumexp, its
  loss term and its softmax gradient are all formed from that one matmul.
- Differentiated (``jax.custom_vjp``), the forward scan therefore also forms
  ``dl = (softmax - onehot) * mask``, writes ``dx[:, s0:s0+sc] = dl @ wᵀ``
  and accumulates ``dw += x_cᵀ @ dl`` in fp32: the residuals are the
  gradients of the loss's *sum*, and the backward only multiplies them by
  ``cotangent / N``. Nothing of size ``[tokens, V]`` is stored or recomputed.
- No scale is known when ``dl`` is rounded to ``compute_dtype``, so none is
  in it: ``|dl| <= 1`` whatever the token count or the fp16 loss scale (held
  2^12 up in fp16, whose five exponent bits would lose a softmax tail under
  6e-5), every sum is fp32, and ``cotangent / N`` multiplies the fp32 result
  (``dw``, ``dbias``) or the stored ``dx`` (``|dx| <= 2 max|w|``) in fp32.
- The primal (evaluation) runs the loss-only scan: one matmul a chunk.

``chunk`` (``LlamaConfig.ce_chunk_size``) bounds the transient logits at
``tokens x chunk`` elements; the positions a chunk holds follow from the
shape, ``sc`` = the largest power of two <= ``S * chunk / V`` (512 at S 4096,
V 50304, chunk 8384; fewer at Gemma's 256k vocabulary). When ``sc`` does not
divide S the tail is padded with weight-0 positions.

Cohere ``logit_scale`` and Gemma-2 ``final_logit_softcapping`` are applied
per chunk (elementwise), so the models that most need chunking keep their
exact logit semantics.

A model that reads its head T times a step (a looped stack) takes
``chunked_exit_cross_entropy``: the T streams are rows of ONE sweep, the head
read once a chunk, and each stream's weight at a position is an argument that
is differentiated too: the sum is linear in it, so its cotangent is the
position's own loss term, which the forward sweep already has.

Under a mesh that shards the batch (``data``, ``fsdp``) the sweep runs in a
``shard_map`` over those axes: the scan slices the sequence axis, never the
batch axis, so each device sweeps its own sequences' ``[B/n * sc, V]`` logits
against the whole head, which is gathered once before the loop as ZeRO-3
gathers a layer's weights, and ``dw`` is summed over the devices once after
it (a reduce-scatter where the head is sharded). Nothing crosses devices
inside the loop. The mesh's other axes stay GSPMD's: a tensor-parallel head
stays split along the vocabulary inside each device group; ``seq > 1``
shards the axis the scan slices, which stays correct (GSPMD gathers) but is
not tuned. Rows that do not divide the devices leave the whole to GSPMD.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def seq_chunk(S: int, chunk: int, V: int) -> int:
    """Positions a chunk, so that ``B * sc * V <= B * S * chunk``: the
    largest power of two <= ``S * chunk / V`` (at least 1), or all of S when
    ``chunk`` covers the vocabulary."""
    if chunk >= V:
        return S
    return 1 << (max(1, S * chunk // V).bit_length() - 1)


def _chunk_logits(xc, w, bias, logit_scale, softcap):
    """fp32 logits ``[rows, V]`` of one chunk's rows (+scale/softcap), plus
    the tanh(l/cap) needed for the softcap chain rule."""
    lc = jax.lax.dot_general(xc, w, (((1, ), (0, )), ((), ())),
                             preferred_element_type=jnp.float32)
    if bias is not None:
        lc = lc + bias
    if logit_scale is not None:
        lc = lc * jnp.float32(logit_scale)
    t = None
    if softcap is not None:
        t = jnp.tanh(lc / softcap)
        lc = softcap * t
    return lc, t


def _dl_shift(compute_dtype) -> float:
    """What ``dl`` (at most 1) is multiplied by before it is rounded to
    ``compute_dtype``: fp16 flushes under 6e-5, which is a softmax tail at
    any real vocabulary; 2^12 is exact and leaves the fp32 sums far from
    their range."""
    return 4096.0 if jnp.dtype(compute_dtype) == jnp.float16 else 1.0


def _local_sweep(x, w, bias, targets, mask, sc, logit_scale, softcap,
                 compute_dtype, with_grads, with_nll=False):
    """One scan over chunks of ``sc`` positions. Returns ``sum(mask * nll)``,
    ``with_grads`` its gradients: dx in x's dtype, dwᵀ ``[V, H]`` (times
    ``_dl_shift``) and dbias in fp32 (else None), and ``with_nll`` every
    position's own ``nll`` ``[B, S]`` in fp32, unmasked (else None)."""
    B, S, H = x.shape
    V = w.shape[1]
    xc_all = x.astype(compute_dtype)
    wc = w.astype(compute_dtype)
    b32 = None if bias is None else bias.astype(jnp.float32)
    grad_bias = with_grads and bias is not None
    shift = _dl_shift(compute_dtype)

    def step(carry, s0):
        loss, dx, dwt, dbias = carry
        # the chunk's B * sc rows as one matrix: on a v5e the three matmuls
        # run a fifth faster in two dimensions than with the batch axis kept
        # apart (head alone at the OLMoE shape: 69 ms for 83; PERF.md, PR 29)
        xc, tg, mk = (
            jax.lax.dynamic_slice_in_dim(a, s0, sc, axis=1).reshape(
                B * sc, *a.shape[2:]) for a in (xc_all, targets, mask))
        lc, t = _chunk_logits(xc, wc, b32, logit_scale, softcap)
        lse = jax.nn.logsumexp(lc, axis=-1)
        hit = jax.lax.broadcasted_iota(jnp.int32, lc.shape, 1) == tg[:, None]
        gold = jnp.where(hit, lc, 0.0).sum(axis=-1)
        loss = loss + ((lse - gold) * mk).sum()
        nll = lse - gold if with_nll else None
        if not with_grads:
            return (loss, dx, dwt, dbias), nll
        dl = (jnp.exp(lc - lse[:, None]) - hit) * mk[:, None]
        # chain back through softcap then logit_scale (applied in that order
        # forward: scale -> softcap)
        if softcap is not None:
            dl = dl * (1.0 - t * t)
        if logit_scale is not None:
            dl = dl * jnp.float32(logit_scale)
        if grad_bias:
            dbias = dbias + dl.sum(axis=0)
        if shift != 1.0:
            dl = dl * shift
        dl = dl.astype(compute_dtype)
        dxc = jax.lax.dot_general(dl, wc, (((1, ), (1, )), ((), ())),
                                  preferred_element_type=jnp.float32)
        if shift != 1.0:
            dxc = dxc * (1.0 / shift)
        dx = jax.lax.dynamic_update_slice_in_dim(
            dx, dxc.astype(dx.dtype).reshape(B, sc, H), s0, axis=1)
        dwt = dwt + jax.lax.dot_general(dl, xc, (((0, ), (0, )), ((), ())),
                                        preferred_element_type=jnp.float32)
        return (loss, dx, dwt, dbias), nll

    # dw is summed as its transpose [V, H], the layout XLA gives the
    # vocabulary matmuls on a TPU: summed as [H, V] a one-layer OLMoE step
    # compiled for a v5e needs 1.5 GB more temporaries (PERF.md, PR 29)
    init = (jnp.zeros((), jnp.float32),
            jnp.zeros(x.shape, x.dtype) if with_grads else None,
            jnp.zeros((V, H), jnp.float32) if with_grads else None,
            jnp.zeros((V, ), jnp.float32) if grad_bias else None)
    (loss, dx, dwt, dbias), nll = jax.lax.scan(
        step, init, jnp.arange(0, S, sc, dtype=jnp.int32))
    if with_nll:    # [chunks, B * sc] -> [B, S]
        nll = nll.reshape(-1, B, sc).swapaxes(0, 1).reshape(B, S)
    return loss, ((dx, dwt, dbias) if with_grads else None), nll


def _batch_axes(B: int):
    """The mesh and those of its axes that shard the batch (``data``,
    ``fsdp``: comm/mesh.py), less any an enclosing ``shard_map`` already made
    manual; none when no mesh is set or the rows do not divide."""
    from ..comm.mesh import get_mesh_context, mesh_is_initialized
    if not mesh_is_initialized():
        return None, ()
    ctx = get_mesh_context()
    manual = jax.sharding.get_abstract_mesh().manual_axes
    dp = tuple(a for a in ("data", "fsdp")
               if ctx.axis_size(a) > 1 and a not in manual)
    if not dp or B % ctx.axis_size(dp):
        return None, ()
    return ctx.mesh, dp


def _sweep(x, w, bias, targets, mask, sc, logit_scale, softcap,
           compute_dtype, with_grads, with_nll=False):
    """``_local_sweep`` of each device's own sequences: under a mesh that
    shards the batch the scan runs inside a ``shard_map`` over those axes,
    with the head whole on every device (gathered once, as ZeRO-3 gathers a
    layer) and ``dw`` summed over the devices once, after the last chunk. Left
    to GSPMD, a ``dw`` whose rows are spread over devices is all-reduced
    inside the loop, once a chunk."""
    static = (sc, logit_scale, softcap, compute_dtype, with_grads, with_nll)
    mesh, dp = _batch_axes(x.shape[0])
    if not dp:
        return _local_sweep(x, w, bias, targets, mask, *static)

    def body(*local):
        loss, grads, nll = _local_sweep(*local, *static)
        if grads is not None:
            dx, *sums = grads
            grads = (dx, *(None if d is None else jax.lax.psum(d, dp)
                           for d in sums))
        return jax.lax.psum(loss, dp), grads, nll

    rows, whole = P(dp), P()
    # jitted for a caller outside any jit: a partly manual shard_map (the
    # mesh's other axes stay GSPMD's) only lowers under one
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(rows, whole, whole, rows, rows),
        out_specs=(whole, (rows, whole, whole) if with_grads else None,
                   rows if with_nll else None),
        axis_names=frozenset(dp), check_vma=False))(
            x, w.astype(compute_dtype), bias, targets, mask)   # gathered as cast


def _dtypes_of(w, bias):
    # the backward casts to the weights' dtypes, which it learns from these
    return tuple(None if a is None else jnp.zeros((0, ), a.dtype)
                 for a in (w, bias))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _mean_ce(x, w, bias, targets, mask, inv_n, sc, logit_scale, softcap,
             compute_dtype):
    """``inv_n * sum(mask * nll)`` of ``softmax(x @ w + bias)``: ``x``
    [B, S, H] with ``sc`` dividing S, ``w`` [H, V], ``bias`` [V] or None,
    ``targets`` [B, S] int in range, ``mask`` [B, S] fp32, ``inv_n`` a
    scalar. A scalar, so that its cotangent is one too and ``dw`` can be
    summed before it is known."""
    return inv_n * _sweep(x, w, bias, targets, mask, sc, logit_scale,
                          softcap, compute_dtype, False)[0]


def _ce_fwd(x, w, bias, targets, mask, inv_n, sc, logit_scale, softcap,
            compute_dtype):
    loss, grads, _ = _sweep(x, w, bias, targets, mask, sc, logit_scale, softcap,
                            compute_dtype, True)
    return inv_n * loss, (grads, inv_n, _dtypes_of(w, bias))


def _ce_bwd(sc, logit_scale, softcap, compute_dtype, res, g):
    (dx, dwt, dbias), inv_n, (w_like, b_like) = res
    k = g.astype(jnp.float32) * inv_n
    dx = (k * dx.astype(jnp.float32)).astype(dx.dtype)
    dw = ((k / _dl_shift(compute_dtype)) * dwt).T.astype(w_like.dtype)
    if dbias is not None:
        dbias = (k * dbias).astype(b_like.dtype)
    return dx, dw, dbias, None, None, None


_mean_ce.defvjp(_ce_fwd, _ce_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _weighted_ce(x, w, bias, targets, weights, inv_n, sc, logit_scale, softcap,
                 compute_dtype):
    """-> ``(inv_n * sum(weights * nll), nll [B, S])``: ``_mean_ce`` with the
    weights an argument that is differentiated too. The sum is linear in
    them, so a weight's cotangent is ``inv_n`` times its position's own
    ``nll``, which the forward sweep has. ``nll`` comes back for the host's
    statistics and as that residual; its own cotangent is dropped (it is a
    reading, not a term of the loss)."""
    loss, _, nll = _sweep(x, w, bias, targets, weights, sc, logit_scale, softcap,
                          compute_dtype, False, True)
    return inv_n * loss, nll


def _wce_fwd(x, w, bias, targets, weights, inv_n, sc, logit_scale, softcap,
             compute_dtype):
    loss, grads, nll = _sweep(x, w, bias, targets, weights, sc, logit_scale,
                              softcap, compute_dtype, True, True)
    return (inv_n * loss, nll), (grads, nll, inv_n, _dtypes_of(w, bias))


def _wce_bwd(sc, logit_scale, softcap, compute_dtype, res, g):
    grads, nll, inv_n, like = res
    dx, dw, dbias, *_ = _ce_bwd(sc, logit_scale, softcap, compute_dtype,
                                (grads, inv_n, like), g[0])
    return dx, dw, dbias, None, g[0].astype(jnp.float32) * inv_n * nll, None


_weighted_ce.defvjp(_wce_fwd, _wce_bwd)


def _shifted_targets(labels, pad: int, ignore_index: int):
    """The shift as shifted targets: position s predicts labels[s + 1], the
    last position predicts nothing (weight 0), and S stays whole; so does a
    tail padded up to a multiple of sc. -> (targets in range, which positions
    count as fp32, one over their number)."""
    tg = jnp.pad(labels[:, 1:], ((0, 0), (0, 1 + pad)),
                 constant_values=ignore_index)
    mask = (tg != ignore_index).astype(jnp.float32)
    tg = jnp.where(tg == ignore_index, 0, tg)
    return tg, mask, 1.0 / jnp.maximum(mask.sum(), 1.0)


def chunked_cross_entropy_loss(x, w, bias, labels, chunk: int,
                               ignore_index: int = -100,
                               logit_scale: Optional[float] = None,
                               softcap: Optional[float] = None,
                               compute_dtype=jnp.bfloat16, weights=None):
    """Token-mean causal-LM CE (shift-by-one, ignore_index) over a streamed
    unembed — drop-in for ``models.llama.cross_entropy_loss`` fed hidden
    states instead of logits. ``x`` [B, S, H], ``labels`` [B, S]; the
    transient logits hold at most ``B * S * chunk`` elements.

    With ``weights`` [B, S] (float) nothing is shifted: position s predicts
    ``labels[s]`` itself, its term is multiplied by ``weights[s]`` (a label
    under a zero weight may be anything in range), and the sum is divided by
    all ``B * S`` positions: a masked-diffusion objective's ``1/t``-weighted
    loss. The same one sweep forms loss and gradients."""
    S = x.shape[1]
    sc = seq_chunk(S, chunk, w.shape[1])
    pad = -S % sc
    if weights is None:
        tg, mask, inv_n = _shifted_targets(labels, pad, ignore_index)
    else:
        tg = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(weights.astype(jnp.float32), ((0, 0), (0, pad)))
        inv_n = jnp.float32(1.0 / (x.shape[0] * S))
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return _mean_ce(x, w, bias, tg, mask, inv_n, sc, logit_scale, softcap,
                    compute_dtype)


def chunked_exit_cross_entropy(xs, w, bias, labels, weights, chunk: int,
                               ignore_index: int = -100,
                               logit_scale: Optional[float] = None,
                               softcap: Optional[float] = None,
                               compute_dtype=jnp.bfloat16):
    """The loss of a model that reads its head T times a step (a looped
    stack: ``models/llama.py``, ``total_ut_steps``): ``xs`` [B, T, S, H] the T
    streams, ``weights`` [B, T, S] (float) what each stream's term of a
    position counts, an argument that is DIFFERENTIATED as ``xs`` is (an exit
    distribution made of the model's own gates). Shift-by-one as without
    weights: position s of every stream predicts ``labels[s + 1]``, the last
    position and ``ignore_index`` predict nothing. -> ``(sum over streams and
    counted positions of weights * CE / the counted positions, CE [B, T, S]
    of every stream and position in fp32 (0 where nothing is counted),
    counted [B, S])``.

    The T streams go through ONE sweep, a row of the batch each: a chunk is
    the ``B * T * sc`` rows of every stream at the same positions, so the head
    is read once a chunk and not once a stream and chunk; ``sc`` is chosen for
    ``chunk / T``, which leaves the transient logits at ``B * S * chunk``
    elements."""
    B, T, S, H = xs.shape
    sc = seq_chunk(S, max(1, chunk // T), w.shape[1])
    pad = -S % sc
    tg, counted, inv_n = _shifted_targets(labels, pad, ignore_index)
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, 0), (0, pad), (0, 0)))
        weights = jnp.pad(weights, ((0, 0), (0, 0), (0, pad)))
    rows = lambda a: a.reshape(B * T, *a.shape[2:])     # noqa: E731
    each = lambda a: jnp.broadcast_to(a[:, None], (B, T, S + pad))  # noqa: E731
    loss, nll = _weighted_ce(
        rows(xs), w, bias, rows(each(tg)),
        rows(weights.astype(jnp.float32) * counted[:, None]), inv_n, sc,
        logit_scale, softcap, compute_dtype)
    nll = jax.lax.stop_gradient(nll).reshape(B, T, S + pad) * counted[:, None]
    return loss, nll[..., :S], counted[:, :S]
