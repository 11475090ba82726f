"""Kimi Delta Attention's scan in chunks (a delta rule whose state decays by
one rate a key channel; Kimi Linear, arXiv:2510.26692).

Per head, with ``g_t <= 0`` the log decay of each of the ``d_k`` key channels
(``alpha_t = exp(g_t)``), ``beta_t`` in [0, 1] and a state ``S`` of
``d_k x d_v`` in float32, zero before the first token:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

:func:`kda_reference` is that recurrence token by token in float32: the
kernels' oracle, and what runs where there is no TPU or the mesh has more
than one device. :func:`kda_scan` is the same function in chunks of ``Q``
tokens, of the bounded gate ``g = floor * sigmoid(rate_h * (pre + bias))``
(``pre`` the gate's projection, ``rate_h = exp(A_log_h)`` one a head, ``bias``
one a channel), which the kernels make themselves from ``pre``: ``g``, its
running sum and their gradients (four float32 values a channel and token)
never exist in HBM. With ``c_t`` the running sum of ``g`` inside a chunk (a
matmul with a triangle of ones, in float32), ``kb = beta * k``, ``vb = beta *
v`` and ``S_prev`` the state entering the chunk:

    A = strictly_lower(M_k),  M_k[t, s] = sum_d kb[t, d] k[s, d] e^{c[t, d] - c[s, d]}
    P = lower(M_q),           M_q[t, s] = sum_d  q[t, d] k[s, d] e^{c[t, d] - c[s, d]}
    T = (I + A)^{-1}
    U = T (vb - (kb * e^{c}) S_prev)
    O = (q * e^{c}) S_prev + P U
    S_new = Diag(e^{c_Q}) S_prev + (k * e^{c_Q - c})^T U

A decay is a vector, so ``M`` is not one decay matrix times ``K K^T`` as in
``ops/ssd.py``: the decay rides on the operands, and ``e^{-c}`` alone leaves
float32 after 18 tokens at ``g = -5``. The two triangular products are
therefore made a block of ``SUB`` = 16 rows at a time around a reference of
their own, the running sum at the block's first row ``r``: the rows carry
``e^{c_t - r} <= 1``, the columns ``e^{r - c_s}``, which is at most 1 before
the block and at most ``e^{15 * 5}`` inside it (the gate's bound,
``GATE_FLOOR``, is what keeps that inside float32: a lower floor is refused),
and the columns after the block, which the mask drops, are zeroed before the
matmul. ``A`` is strictly
lower triangular, so ``(I + A)^{-1} = (I - A)(I + A^2)(I + A^4)...`` exactly
(``A^Q = 0``): ``2 log2(Q) - 2`` matmuls of ``Q x Q`` in float32
(``contract_precision<fp32>``), no approximation and no ``[seq, seq]`` array.

A chunk of a BLOCK of heads is one grid step of ``kda_chunk_fwd`` (``block *
d`` contiguous lanes of every operand; how many heads follows from the shape,
``kernel_dispatch.choose_kda_heads``): a head's chunk is one long chain of
small dependent matmuls, which alone leaves the MXUs waiting, so the heads of
the block, which share no value, are issued a matmul stage abreast
(``_abreast``), each head's mathematics what it is alone, bit for bit. The
chunks of a sequence run in order with each head's state carried in VMEM in
float32, TRANSPOSED (``[d_v, d_k]``: the decay then scales lanes), the states
leaving the chunks written out for the backward and the largest ``|S|`` kept
as the kernel goes (``kda_stats``). ``kda_chunk_bwd`` walks the chunks in
reverse with the state's gradient in VMEM, makes ``A``, ``P``, ``T`` and ``U`` again from the
inputs and the saved state, and returns the gradients of ``q``, ``k``, ``kb``,
``vb`` and ``pre`` and, summed over the tokens as it goes, of ``bias`` and
``rate`` (a lane each); ``beta``'s follows outside (a product, XLA's).
Precision as ``ops/ssd.py``: operands of a matmul in ``q.dtype`` (bf16 in
training) with float32 accumulation, but for ``T``, which is made and applied
in float32; ``c``, every exponential and the
carried state are float32, and a state that is a matmul's operand goes in as
its two bf16 parts.

Under a layer's recomputation the output and the chunk states are named
(``SCAN_NAME``, a candidate of ``ops/remat.py``): where the plan keeps them
the forward kernel runs once a step, where it does not the recomputed layer
runs it again.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .registry import registry
from .remat import AGAIN, KDA_SCAN as SCAN_NAME
from .ssd import SUBLANES

SUB = 16                 # rows of a triangular product made around one reference
GATE_FLOOR = -5.0        # the least g a token may have (the bounded gate's)
_CLAMP = 80.0            # e^80 is inside float32; SUB - 1 tokens reach e^75
_NT = ((1, ), (1, ))     # contract the lanes of both operands
_TN = ((0, ), (0, ))     # contract the rows of both operands
_HIGHEST = jax.lax.Precision.HIGHEST


def kda_reference(q, k, v, g, beta, with_state_absmax: bool = False,
                  stat_every: int = 1):
    """``q``, ``k`` ``[b, s, H, d_k]``, ``v`` ``[b, s, H, d_v]``, ``g`` ``[b,
    s, H, d_k]`` (the log decay, <= 0), ``beta`` ``[b, s, H]`` -> ``o [b, s,
    H, d_v]`` in ``v.dtype``: the recurrence, one token after another, float32
    inside. ``with_state_absmax``: also the largest ``|S|`` left by the tokens
    that end a run of ``stat_every`` (every token by default) or the sequence."""
    f32 = jnp.float32
    b, s, H, dk = q.shape

    def step(carry, inp):
        S, top = carry                                      # [b, H, dk, dv]
        qt, kt, vt, gt, bt, counts = inp
        S = jnp.exp(gt)[..., None] * S
        seen = jnp.einsum("bhkv,bhk->bhv", S, kt, precision=_HIGHEST)
        S = S + (bt[..., None] * kt)[..., None] * (vt - seen)[:, :, None, :]
        o = jnp.einsum("bhkv,bhk->bhv", S, qt, precision=_HIGHEST)
        return (S, jnp.where(counts, jnp.maximum(top, jnp.max(jnp.abs(S))), top)), o

    counts = ((jnp.arange(s) + 1) % stat_every == 0).at[s - 1].set(True)
    time_major = [jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta)]
    init = (jnp.zeros((b, H, dk, v.shape[-1]), f32), jnp.zeros((), f32))
    (_, top), o = jax.lax.scan(step, init, time_major + [counts])
    o = jnp.moveaxis(o, 0, 1).astype(v.dtype)
    return (o, jax.lax.stop_gradient(top)) if with_state_absmax else o


def _split(s, dtype):
    """A float32 ``s`` as matmul operands: itself, or its two bf16 parts."""
    if dtype == jnp.float32:
        return (s, )
    hi = s.astype(dtype)
    return hi, (s - hi.astype(jnp.float32)).astype(dtype)


def _dot(a, b, dims=((1, ), (0, )), precision=None):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                               preferred_element_type=jnp.float32)


def _abreast(chains):
    """What each of the generators ``chains`` returns, every one advanced a
    stage (a ``yield``: a matmul or a few that wait for nothing of each
    other) in turn. The heads of a grid step's block share no value, but an
    MXU takes its matmuls in program order: written one head after another,
    a head's first product queues behind the last of the head before it, and
    the heads' chains run end to end; written a stage abreast, each chain's
    waits are filled by the others' work."""
    chains, done = list(chains), {}
    while len(done) < len(chains):
        for i, chain in enumerate(chains):
            if i not in done:
                try:
                    next(chain)
                except StopIteration as end:
                    done[i] = end.value
    return [done[i] for i in range(len(chains))]


def _triangles(q, k, kb, c, mm):
    """A chain (``_abreast``) -> ``A`` (strictly lower), ``P`` (lower), both
    ``[Q, Q]`` float32, and for each block of ``SUB`` rows what made it (the
    backward's operands): the columns' decay ``[Q, d]`` float32 (0 after the block), the columns
    ``k * decay`` in ``mm``, the rows' decay ``[SUB, d]`` float32 and the
    rows of ``kb`` and ``q`` times it in ``mm``."""
    f32 = jnp.float32
    Q, d = c.shape
    qf, kf, kbf = q.astype(f32), k.astype(f32), kb.astype(f32)
    token = jax.lax.broadcasted_iota(jnp.int32, (Q, d), 0)
    a_rows, p_rows, blocks = [], [], []
    for lo in range(0, Q, SUB):
        ref = c[lo:lo + 1, :]
        decay = jnp.where(token < lo + SUB,
                          jnp.exp(jnp.minimum(ref - c, _CLAMP)), 0.0)
        cols = (kf * decay).astype(mm)
        grow = jnp.exp(c[lo:lo + SUB] - ref)
        rk = (kbf[lo:lo + SUB] * grow).astype(mm)
        rq = (qf[lo:lo + SUB] * grow).astype(mm)
        a_rows.append(_dot(rk, cols, _NT))
        p_rows.append(_dot(rq, cols, _NT))
        blocks.append((decay, cols, grow, rk, rq))
    yield
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    A = jnp.where(row > col, jnp.concatenate(a_rows, axis=0), 0.0)
    P = jnp.where(row >= col, jnp.concatenate(p_rows, axis=0), 0.0)
    return A, P, blocks


def _inverse(A):
    """A chain -> ``(I + A)^{-1}`` of a strictly lower triangular ``[Q, Q]``
    float32 ``A``: ``(I - A)(I + A^2)(I + A^4)...`` up to ``A^{Q/2}``, exact because
    ``A^Q = 0``; the factors are polynomials in ``A`` and commute."""
    Q = A.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    T = jnp.where(row == col, 1.0, 0.0) - A
    power, n = A, 2
    while n < Q:
        power = _dot(power, power, precision=_HIGHEST)
        yield
        T = T + _dot(T, power, precision=_HIGHEST)
        yield
        n *= 2
    return T


def _chunk(q, k, kb, vb, pre, lanes, floor, Z, mm):
    """A chain -> what both kernels make of a chunk and the state entering it
    (``Z``, ``[d_v, d_k]``: the state transposed). ``lanes``: the head's rate in row
    0 and the channels' bias in row 1."""
    f32 = jnp.float32
    Q = pre.shape[0]
    rate, bias = lanes[0:1, :], lanes[1:2, :]
    shifted = pre.astype(f32) + bias
    sig = jax.nn.sigmoid(rate * shifted)
    ones = jnp.where(jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
                     >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1), 1.0, 0.0)
    c = _dot(ones, floor * sig, precision=_HIGHEST)       # the running sum of g
    yield
    A, P, blocks = yield from _triangles(q, k, kb, c, mm)
    T = yield from _inverse(A)
    E = jnp.exp(c)
    qp, kbp = q.astype(f32) * E, kb.astype(f32) * E
    zs = _split(Z, mm)
    R = vb.astype(f32) - sum(_dot(kbp.astype(mm), z, _NT) for z in zs)
    yield
    U = _dot(T, R, precision=_HIGHEST)                           # [Q, d_v]
    yield
    cend = c[-1:, :]
    to_end = jnp.exp(cend - c)
    ke = k.astype(f32) * to_end
    return dict(A=A, P=P, blocks=blocks, T=T, E=E, qp=qp, kbp=kbp, zs=zs, U=U,
                cend=cend, to_end=to_end, ke=ke, c=c, ones=ones, sig=sig, rate=rate,
                shifted=shifted)


def _fwd_head(q, k, kb, vb, pre, lanes, Z, top, floor, mm):
    """A chain, one head's chunk: -> its output ``[Q, d_v]``, the state leaving
    the chunk and the largest ``|S|`` so far, eight rows a lane."""
    w = yield from _chunk(q, k, kb, vb, pre, lanes, floor, Z, mm)
    Umm = w["U"].astype(mm)
    o = (sum(_dot(w["qp"].astype(mm), z, _NT) for z in w["zs"])
         + _dot(w["P"].astype(mm), Umm))
    yield
    new = jnp.exp(w["cend"]) * Z + _dot(Umm, w["ke"].astype(mm), _TN)
    yield
    # the state is in VMEM here, so the statistic costs no pass over the
    # saved states
    size = jnp.abs(new)
    for r in range(0, size.shape[0], SUBLANES):
        top = jnp.maximum(top, size[r:r + SUBLANES])
    return o, new, top


def _heads(state):
    """The lane slices of the heads of a grid step's block (``state``: the
    scratch, ``[Hb, d, d]``): a head is ``d`` lanes of every block."""
    block, d = state.shape[:2]
    return [slice(h * d, (h + 1) * d) for h in range(block)]


def _fwd_kernel(q_ref, k_ref, kb_ref, vb_ref, pre_ref, lanes_ref, o_ref, st_ref,
                top_ref, state, *, floor):
    mm = q_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)
        top_ref[...] = jnp.zeros_like(top_ref)

    heads = list(enumerate(_heads(state)))
    done = _abreast(
        _fwd_head(q_ref[0, :, sl], k_ref[0, :, sl], kb_ref[0, :, sl],
                  vb_ref[0, :, sl], pre_ref[0, :, sl], lanes_ref[:, sl],
                  state[h], top_ref[0, h], floor, mm)
        for h, sl in heads)
    for (h, sl), (o, new, top) in zip(heads, done):
        o_ref[0, :, sl] = o.astype(o_ref.dtype)
        state[h] = new
        st_ref[0, 0, :, sl] = new
        top_ref[0, h] = top


def _bwd_head(q, k, kb, vb, pre, lanes, dO, Z, dZ, floor, mm):
    """A chain, one head's chunk backward: -> dq, dk, d(kb), d(vb), d(pre)
    ``[Q, d]`` float32, the state's gradient entering the chunk before it and the
    two lanes ``[1, d]`` the bias and the rate receive from this chunk."""
    f32 = jnp.float32
    Q = q.shape[0]
    w = yield from _chunk(q, k, kb, vb, pre, lanes, floor, Z, mm)
    c = w["c"]
    zs, Umm = w["zs"], w["U"].astype(mm)
    dzs = _split(dZ, mm)
    kemm = w["ke"].astype(mm)
    dU = _dot(w["P"].astype(mm), dO, _TN) + sum(_dot(kemm, z, _NT) for z in dzs)
    yield
    dR = _dot(w["T"], dU, _TN, precision=_HIGHEST)               # T^T dU
    yield
    dRmm = dR.astype(mm)
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    dP = jnp.where(row >= col, _dot(dO, Umm, _NT), 0.0).astype(mm)
    dA = jnp.where(row > col, -_dot(dRmm, Umm, _NT), 0.0).astype(mm)
    dqp = sum(_dot(dO, z) for z in zs)                           # [Q, d_k]
    dkbp = -sum(_dot(dRmm, z) for z in zs)
    dke = sum(_dot(Umm, z) for z in dzs)
    before = (jnp.exp(w["cend"]) * dZ + _dot(dO, w["qp"].astype(mm), _TN)
              - _dot(dRmm, w["kbp"].astype(mm), _TN))
    yield
    via_end = dke * w["ke"]
    dq = dqp * w["E"]
    dkb = dkbp * w["E"]
    dk = dke * w["to_end"]
    dc = dqp * w["qp"] + dkbp * w["kbp"] - via_end
    last = jax.lax.broadcasted_iota(jnp.int32, c.shape, 0) == Q - 1
    dc = dc + jnp.where(last, jnp.sum(via_end, axis=0, keepdims=True)
                        + jnp.exp(w["cend"]) * jnp.sum(dZ * Z, axis=0, keepdims=True),
                        0.0)
    qf, kf, kbf = q.astype(f32), k.astype(f32), kb.astype(f32)
    dq_rows, dkb_rows, dc_rows = [], [], []
    for i, (decay, cols, grow, rk, rq) in enumerate(w["blocks"]):
        sl = slice(i * SUB, (i + 1) * SUB)
        dPi, dAi = dP[sl], dA[sl]
        gq = _dot(dPi, cols) * grow                              # [SUB, d_k]
        gk = _dot(dAi, cols) * grow
        dq_rows.append(gq)
        dkb_rows.append(gk)
        dc_rows.append(gq * qf[sl] + gk * kbf[sl])
        through = (_dot(dPi, rq, _TN) + _dot(dAi, rk, _TN)) * decay      # [Q, d_k]
        dk = dk + through
        dc = dc - through * kf
    yield
    dq = dq + jnp.concatenate(dq_rows, axis=0)
    dkb = dkb + jnp.concatenate(dkb_rows, axis=0)
    dc = dc + jnp.concatenate(dc_rows, axis=0)
    # g_s reaches every c_t with t >= s; then through the bounded gate
    dg = _dot(w["ones"], dc, _TN, precision=_HIGHEST)
    yield
    dz = dg * floor * w["sig"] * (1.0 - w["sig"])
    dpre = dz * w["rate"]
    return (dq, dk, dkb, dR, dpre, before, jnp.sum(dpre, axis=0, keepdims=True),
            jnp.sum(dz * w["shifted"], axis=0, keepdims=True))


def _bwd_kernel(q_ref, k_ref, kb_ref, vb_ref, pre_ref, lanes_ref, do_ref, st_ref,
                dq_ref, dk_ref, dkb_ref, dvb_ref, dpre_ref, dlanes_ref, dstate, *, floor):
    mm = q_ref.dtype
    step, steps = pl.program_id(2), pl.num_programs(2)

    @pl.when(step == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        dlanes_ref[...] = jnp.zeros_like(dlanes_ref)

    # the chunks run in reverse: the last step is the sequence's first chunk,
    # which no state enters
    heads = list(enumerate(_heads(dstate)))
    done = _abreast(
        _bwd_head(q_ref[0, :, sl], k_ref[0, :, sl], kb_ref[0, :, sl],
                  vb_ref[0, :, sl], pre_ref[0, :, sl], lanes_ref[:, sl],
                  do_ref[0, :, sl],
                  jnp.where(step == steps - 1, 0.0, st_ref[0, 0, :, sl]),
                  dstate[h], floor, mm)
        for h, sl in heads)
    for (h, sl), (*grads, before, dbias, drate) in zip(heads, done):
        for ref, grad in zip((dq_ref, dk_ref, dkb_ref, dvb_ref, dpre_ref), grads):
            ref[0, :, sl] = grad.astype(ref.dtype)
        dstate[h] = before
        # the bias's and the rate's, summed over the tokens as the kernel
        # goes: rows 0 and 1 of a block held over the chunks
        dlanes_ref[0, 0:1, sl] += dbias
        dlanes_ref[0, 1:2, sl] += drate


def _compiler_params(chunk: int, d: int, block: int, itemsize: int, arrays: int):
    from .kernel_dispatch import kda_vmem_bytes, vmem_limit_bytes
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=vmem_limit_bytes(
            kda_vmem_bytes(block, d, chunk, itemsize, arrays)))


def _fwd_call(q, k, kb, vb, pre, lanes, heads, chunk, floor, interpret, block):
    """A grid step is a chunk of ``block`` heads (``kernel_dispatch.
    choose_kda_heads``): ``block * d`` contiguous lanes of every operand."""
    b, s, width = q.shape
    d, nc = width // heads, s // chunk
    x = pl.BlockSpec((1, chunk, block * d), lambda b, h, n: (b, n, h))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, floor=floor),
        grid=(b, heads // block, nc),
        in_specs=[x] * 5 + [pl.BlockSpec((SUBLANES, block * d), lambda b, h, n: (0, h))],
        out_specs=[x,
                   pl.BlockSpec((1, 1, d, block * d), lambda b, h, n: (b, n, 0, h)),
                   # one block a (batch, head), held over its chunks
                   pl.BlockSpec((1, block, SUBLANES, d), lambda b, h, n: (b, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, nc, d, width), jnp.float32),
                   jax.ShapeDtypeStruct((b, heads, SUBLANES, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, d, d), jnp.float32)],
        compiler_params=_compiler_params(chunk, d, block, q.dtype.itemsize, 6),
        interpret=interpret,
        name="kda_chunk_fwd",
    )(q, k, kb, vb, pre, lanes)


def _bwd_call(q, k, kb, vb, pre, lanes, states, do, heads, chunk, floor, interpret,
              block):
    b, s, width = q.shape
    d, nc = width // heads, s // chunk
    x = pl.BlockSpec((1, chunk, block * d), lambda b, h, n: (b, nc - 1 - n, h))
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)      # noqa: E731
    *grads, dlanes = pl.pallas_call(
        functools.partial(_bwd_kernel, floor=floor),
        grid=(b, heads // block, nc),
        in_specs=[x] * 5 + [pl.BlockSpec((SUBLANES, block * d),
                                         lambda b, h, n: (0, h)), x] + [
            # the state ENTERING the chunk is the one the chunk before it
            # wrote; chunk 0 reads a block it does not use
            pl.BlockSpec((1, 1, d, block * d), lambda b, h, n: (
                b, jnp.maximum(nc - 2 - n, 0), 0, h))],
        out_specs=[x] * 5 + [pl.BlockSpec((1, SUBLANES, block * d),
                                          lambda b, h, n: (b, 0, h))],
        out_shape=[like(q), like(k), like(kb), like(vb), like(pre),
                   jax.ShapeDtypeStruct((b, SUBLANES, width), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, d, d), jnp.float32)],
        compiler_params=_compiler_params(chunk, d, block, q.dtype.itemsize, 11),
        interpret=interpret,
        name="kda_chunk_bwd",
    )(q, k, kb, vb, pre, lanes, do.astype(q.dtype), states)
    # what the lanes' rows received: the bias's gradient through ``rate *
    # (pre + bias)`` and the rate's, over the batch
    dlanes = jnp.sum(dlanes, axis=0)
    return (*grads, jnp.zeros_like(lanes).at[0].set(dlanes[1]).at[1].set(dlanes[0]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10, 11))
def _kda_chunks(q, k, kb, vb, pre, lanes, heads, chunk, floor, interpret, keep, block):
    o, _, tops = _fwd_call(q, k, kb, vb, pre, lanes, heads, chunk, floor, interpret,
                           block)
    return o, tops


def _kda_vjp_fwd(q, k, kb, vb, pre, lanes, heads, chunk, floor, interpret, keep, block):
    o, states, tops = _fwd_call(q, k, kb, vb, pre, lanes, heads, chunk, floor,
                                interpret, block)
    # what the backward needs of the forward kernel, under the name a
    # recomputation may keep them by (its layer's plan said which)
    name = SCAN_NAME if keep else SCAN_NAME + AGAIN
    o, states = checkpoint_name(o, name), checkpoint_name(states, name)
    return (o, tops), (q, k, kb, vb, pre, lanes, states)


def _kda_vjp_bwd(heads, chunk, floor, interpret, keep, block, res, cotangents):
    return _bwd_call(*res, cotangents[0], heads, chunk, floor, interpret, block)


_kda_chunks.defvjp(_kda_vjp_fwd, _kda_vjp_bwd)


@functools.partial(jax.jit, static_argnames=("heads", "chunk", "floor", "interpret",
                                             "keep", "block"))
def _kda_jit(q, k, kb, vb, pre, lanes, heads, chunk, floor, interpret, keep, block):
    # a frame of its own in the name stack, as for the state-space scan: the
    # kernels keep their names (``%kda_chunk_fwd*``, ``%kda_chunk_bwd*``)
    return _kda_chunks(q, k, kb, vb, pre, lanes, heads, chunk, floor, interpret, keep,
                       block)


def grid_of(batch: int, seq: int, heads: int, d: int, chunk: int, itemsize: int):
    """(heads a grid step, grid steps a call) of the kernels at this call:
    what ``kernel_dispatch.choose_kda_heads`` gives the shape."""
    from .kernel_dispatch import choose_kda_heads
    block = choose_kda_heads(heads, d, chunk, itemsize)
    return block, batch * (heads // block) * -(-seq // chunk)


def scan_bytes(batch: int, seq: int, heads: int, d_k: int, d_v: int, chunk: int,
               itemsize: int) -> int:
    """Bytes a layer keeps under ``SCAN_NAME``: the output and the float32
    states leaving the chunks."""
    padded = seq + (-seq % chunk)
    return batch * heads * (padded * d_v * itemsize
                            + (padded // chunk) * d_k * d_v * 4)


def bounded_gate(pre, rate, bias, floor: float = GATE_FLOOR):
    """``g = floor * sigmoid(rate_h * (pre + bias))`` in float32: ``pre`` ``[b,
    s, H, d]``, ``rate`` ``[H]``, ``bias`` ``[H * d]`` -> ``[b, s, H, d]`` in
    ``[floor, 0]``."""
    H, d = pre.shape[-2:]
    f32 = jnp.float32
    return floor * jax.nn.sigmoid(rate.astype(f32)[:, None] * (
        pre.astype(f32) + bias.astype(f32).reshape(H, d)))


_PAD_PRE = -1e30        # a padded token's gate: sigmoid(-inf) = 0, no decay


def kda_scan(q, k, v, pre, rate, bias, beta, chunk: int, *, use_kernel: bool,
             floor: float = GATE_FLOOR, interpret: bool = False,
             with_state_absmax: bool = False, keep: bool = True):
    """``o`` of the recurrence above under the bounded gate ``g =``
    :func:`bounded_gate` ``(pre, rate, bias, floor)``: ``q``, ``k``, ``v``,
    ``pre`` ``[b, s, H, d]``, ``rate`` ``[H]`` (positive), ``bias`` ``[H *
    d]``, ``beta`` ``[b, s, H]`` -> ``[b, s, H, d]`` in ``v.dtype``.
    ``use_kernel``: the Pallas kernels in chunks of ``chunk`` (a multiple of
    16; forward and hand-written backward) instead of the recurrence; the
    caller decides, as for flash attention (a raw ``pallas_call`` is not
    partitioned over a mesh of more than one device). The kernels want ``d_k
    = d_v``, a multiple of 128, and a floor no lower than ``GATE_FLOOR``. A
    sequence that ``chunk`` does not divide is padded with a gate of 0 and
    ``beta = 0``: no decay, nothing written. ``with_state_absmax``: also the
    largest ``|S|`` at the chunks' ends (the states the kernels keep), no
    gradient. ``keep``: whether a recomputation may keep the kernel's output
    and states (``SCAN_NAME``)."""
    b, s, H, d = q.shape
    if (k.shape != q.shape or pre.shape != q.shape or v.shape[:3] != (b, s, H)
            or beta.shape != (b, s, H) or rate.shape != (H, ) or bias.shape != (H * d, )):
        raise ValueError(f"kda_scan: q {q.shape}, k {k.shape}, v {v.shape}, pre "
                         f"{pre.shape}, rate {rate.shape}, bias {bias.shape}, beta "
                         f"{beta.shape}: want q, k, pre [b, s, H, d_k], v [b, s, H, "
                         "d_v], rate [H], bias [H * d_k], beta [b, s, H]")
    if not (use_kernel or interpret):
        return kda_reference(q, k, v, bounded_gate(pre, rate, bias, floor), beta,
                             with_state_absmax, stat_every=chunk)
    if v.shape[-1] != d or d % 128 or chunk % SUB or not GATE_FLOOR <= floor <= 0:
        raise ValueError(f"the kda kernels want d_k = d_v a multiple of 128, a chunk "
                         f"that is a multiple of {SUB} and a floor in [{GATE_FLOOR}, 0]: "
                         f"got d_k {d}, d_v {v.shape[-1]}, chunk {chunk}, floor {floor}")
    f32, dtype = jnp.float32, v.dtype
    pad = -s % chunk

    def flat(a, fill=0.0):
        return jnp.pad(a.astype(dtype).reshape(b, s, H * d), ((0, 0), (0, pad), (0, 0)),
                       constant_values=fill)

    with jax.named_scope("ds.kda.gates"):
        bt = beta.astype(f32)[..., None]
        kb = (bt * k.astype(f32)).astype(dtype)
        vb = (bt * v.astype(f32)).astype(dtype)
        lanes = jnp.zeros((SUBLANES, H * d), f32)
        lanes = lanes.at[0].set(jnp.repeat(rate.astype(f32), d)).at[1].set(bias.astype(f32))
    block, _ = grid_of(b, s, H, d, chunk, dtype.itemsize)
    o, tops = _kda_jit(flat(q), flat(k), flat(kb), flat(vb), flat(pre, _PAD_PRE), lanes,
                       H, chunk, float(floor), interpret, bool(keep), block)
    o = o[:, :s].reshape(b, s, H, d)
    if with_state_absmax:
        return o, jax.lax.stop_gradient(jnp.max(tops))
    return o


registry.register("kda", "pallas", True,
                  "Kimi Delta Attention chunked scan, forward and backward")
