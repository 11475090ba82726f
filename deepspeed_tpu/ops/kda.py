"""Kimi Delta Attention's scan in chunks (a delta rule whose state decays by
one rate a key channel; Kimi Linear, arXiv:2510.26692).

Per head, with ``g_t <= 0`` the log decay of each of the ``d_k`` key channels
(``alpha_t = exp(g_t)``), ``beta_t`` in [0, 1] and a state ``S`` of
``d_k x d_v`` in float32, zero before the first token:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

:func:`kda_reference` is that recurrence token by token in float32: the
kernels' oracle, and what runs where there is no TPU or the mesh has more
than one device. :func:`kda_fused` is the mixer between its three
convolutions and ``o_proj``: the same recurrence at ``q = l2norm(q) /
sqrt(d)``, ``k = l2norm(k)`` under the bounded gate ``g = floor *
sigmoid(rate_h * (pre + bias))`` (``pre`` the gate's projection, ``rate_h =
exp(A_log_h)`` one a head, ``bias`` one a channel), then ``RMSNorm_d(o) *
weight * sigmoid(gate)`` head by head. Where the kernels run, everything in
it is made on the ``[chunk, d]`` tiles they hold: ``g``, its running sum and
their gradients (four float32 values a channel and token), the unit rows, ``kb
= beta * k``, ``vb = beta * v``, the un-normalised ``o`` and the decays
``exp(g)`` behind ``decay_mean`` never exist in HBM; the kernels read the
convolutions' q, k, v, the two pre-activations and ``beta`` (a head a lane)
and write what ``o_proj`` reads. All of that arithmetic is float32, a row's
sum a lane reduction, and every value is rounded to the operands' type where
XLA's passes rounded it (after the norm, after the product, ``o`` before its
norm). Elsewhere XLA makes the norms (:func:`l2norm`, :func:`gated_norm`)
around the recurrence. With ``c_t`` the running sum of ``g`` inside a chunk (a
matmul with a triangle of ones, in float32) and ``S_prev`` the state entering
the chunk:

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
lower triangular and ``T`` is its exact inverse by blocks (``_inverse``): the
diagonal blocks of eight rows as ``(I - A)(I + A^2)(I + A^4)`` (``A^8 = 0``
in a block), all of them at once with the blocks on the lanes, then the
blocks below the diagonal a level at a time (8 -> 16 -> 32 -> ``Q``), two
products a level: ``T10 = -T1 (A10 T0)``. Every product is float32
(``contract_precision<fp32>``): nine a chunk of 64 whose left rows sum to
224, no approximation and no ``[seq, seq]`` array.

A chunk of a BLOCK of heads is one grid step of ``kda_chunk_fwd`` (``block *
d`` contiguous lanes of every operand; how many heads follows from the shape,
``kernel_dispatch.choose_kda_heads``): a head's chunk is one long chain of
small dependent matmuls, which alone leaves the MXUs waiting, so the heads of
the block, which share no value, are issued a matmul stage abreast
(``_abreast``), each head's mathematics what it is alone, bit for bit. The
chunks of a sequence run in order with each head's state carried in VMEM in
float32, TRANSPOSED (``[d_v, d_k]``: the decay then scales lanes), the states
leaving the chunks written out for the backward and the largest ``|S|`` kept
as the kernel goes, and the decays summed (``kda_stats``). ``kda_chunk_bwd``
walks the chunks in reverse with the state's gradient in VMEM, makes the
operands, ``A``, ``P``, ``T``, ``U`` and ``o`` again from the inputs and the
saved state, turns the output's gradient into ``o``'s through the gated norm,
and returns the gradients of the RAW q, k and v (a unit row's Jacobian is the
projection off the row, over its norm), of both pre-activations, of ``beta``
(``sum_d (d(kb) k + d(vb) v)``, a head a lane, a block a grid step: their sum
over the head blocks follows outside) and, summed over the tokens as it goes,
of ``rate``, ``bias`` and the norm's weight (a lane each).
Precision as ``ops/ssd.py``: operands of a matmul in ``q.dtype`` (bf16 in
training) with float32 accumulation, but for ``T``, which is made and applied
in float32; ``c``, every exponential and the
carried state are float32, and a state that is a matmul's operand goes in as
its two bf16 parts.

Under a layer's recomputation the output (what ``o_proj`` reads) and the chunk
states are named (``SCAN_NAME``, a candidate of ``ops/remat.py``): where the
plan keeps them the forward kernel runs once a step, where it does not the
recomputed layer runs it again.
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
L2_EPS = 1e-6            # under the root of q's and k's row norms
LANES = 128              # beta and its gradient ride a head a lane
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
    float32 ``A`` (``Q`` a multiple of ``SUB``), exactly, by blocks, because an
    MXU product costs by its left operand's rows. The diagonal blocks of
    ``SUBLANES`` = 8 rows first, as ``(I - A)(I + A^2)(I + A^4)`` (``A^8 = 0``
    inside a block; the factors are polynomials in ``A`` and commute), all at
    once with the blocks riding the LANES: ``[8, Q]`` against the
    block-diagonal ``[Q, Q]``, a factor and the next square one product. Then
    the blocks below the diagonal a level at a time, two neighbours into one
    of twice the size, ``[[T0, 0], [-T1 A10 T0, T1]]``, every pair of a level
    at once: two products of the lower blocks' rows. Nine products a chunk of
    64, their left rows 32 + 3 x 64 (the same factors up to ``A^32`` on the
    whole chunk are ten of 64 rows). The blocks are no larger than eight for
    the rounding's sake: with keys nearly collinear and ``beta`` near 1
    (entries of ``A`` near 1) a partial product ``sum_k (-A)^k`` holds entries
    the size of a binomial coefficient that the last factor has to cancel, 35
    in a block of 8 (4e-6 of ``|T|`` is lost), 6,435 in one of 16 (7e-4) and
    everything in a chunk of 64; the levels above multiply inverses, whose
    entries stay near 1."""
    f32 = jnp.float32
    Q = A.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)

    @functools.cache
    def same(size):                        # a mask: in one diagonal block of ``size``
        bits = size.bit_length() - 1
        return (row >> bits) == (col >> bits)

    def spread(S):                         # [8, Q], a block a lane group -> block diagonal
        return jnp.where(same(SUBLANES),
                         jnp.concatenate([S] * (Q // SUBLANES), axis=0), 0.0)

    wide = jnp.where(same(SUBLANES), A, 0.0)
    power = jnp.sum(wide.reshape(Q // SUBLANES, SUBLANES, Q), axis=0)
    # (iotas of their own: Mosaic refuses a slice of ``row``)
    eye = (jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, Q), 0)
           == (jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, Q), 1) & (SUBLANES - 1)))
    T = jnp.where(eye, 1.0, 0.0) - power                                 # I - A
    power = _dot(power, wide, precision=_HIGHEST)                        # A^2
    yield
    both = _dot(jnp.concatenate([T, power], axis=0), spread(power), precision=_HIGHEST)
    yield
    T, power = T + both[:SUBLANES], both[SUBLANES:]                      # (I + A^2), A^4
    T = spread(T + _dot(T, spread(power), precision=_HIGHEST))           # (I + A^4)
    yield
    size = SUBLANES
    while size < Q:
        lower = range(size, Q, 2 * size)   # the first row of each pair's lower block
        below = jnp.where(same(2 * size) & ~same(size), A, 0.0)          # A10 of each pair
        zeros = jnp.zeros((size, Q), f32)

        def rows(M):                       # the lower blocks' rows, stacked
            return jnp.concatenate([M[lo:lo + size] for lo in lower], axis=0)

        def placed(M):                     # ``rows``' rows where they came from, in zeros
            parts, at = [], 0
            for lo in range(0, Q, size):
                n = min(size, Q - lo)
                parts.append(M[at:at + n] if lo in lower else zeros[:n])
                at += n if lo in lower else 0
            return jnp.concatenate(parts, axis=0)

        T1 = rows(T)
        X = _dot(rows(below), T, precision=_HIGHEST)                     # A10 T0
        yield
        X = _dot(T1, placed(X), precision=_HIGHEST)                      # T1 (A10 T0)
        yield
        T = T - placed(X)
        size *= 2
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


def _rowsum(a):
    """The sum of each row of ``a [Q, lanes]`` float32: ``[Q, 1]``."""
    return jnp.sum(a, axis=-1, keepdims=True)


def _unit(a, scale):
    """A head's rows ``a`` ``[Q, d]`` -> the rows over their L2 norm in float32,
    the norms' reciprocal ``[Q, 1]`` and ``scale`` times the unit rows, the
    operand before its rounding (a row of zeros stays one: ``L2_EPS``)."""
    a = a.astype(jnp.float32)
    r = jax.lax.rsqrt(_rowsum(a * a) + L2_EPS)
    unit = a * r
    return unit, r, unit * scale


def _operands(q, k, v, bt, mm):
    """What the scan reads of a head's chunk, made in VMEM and rounded where
    XLA rounded them: ``l2norm(q) / sqrt(d)``, ``l2norm(k)``, ``beta k``,
    ``beta v`` in ``mm`` (``bt``: the head's beta, ``[Q, 1]`` float32), and
    what the norms' backward reads (``_raw_grads``)."""
    f32 = jnp.float32
    qh, rq, qn = _unit(q, float(q.shape[-1]) ** -0.5)
    kh, rk, kn = _unit(k, 1.0)
    qn, kn = qn.astype(mm), kn.astype(mm)
    kb = (bt * kn.astype(f32)).astype(mm)
    vb = (bt * v.astype(f32)).astype(mm)
    return (qn, kn, kb, vb), (qh, rq, kh, rk)


def _normed(o, gate, eps, mm):
    """What the gated norm ``RMSNorm_d(o) * weight * sigmoid(gate)`` is made of,
    of a head's ``o [Q, d]`` float32, rounded to ``mm`` first (the array XLA's
    norm read): -> the normalised rows ``o r``, ``r = rsqrt(mean o^2 + eps)``
    ``[Q, 1]`` and the gate's sigmoid, float32."""
    f32 = jnp.float32
    o = o.astype(mm).astype(f32)
    r = jax.lax.rsqrt(_rowsum(o * o) / o.shape[-1] + eps)
    return o * r, r, jax.nn.sigmoid(gate.astype(f32))


def _scan_out(w, mm):
    """``o = (q e^c) S_prev + P U`` of a chunk (``w``: ``_chunk``'s), float32."""
    return (sum(_dot(w["qp"].astype(mm), z, _NT) for z in w["zs"])
            + _dot(w["P"].astype(mm), w["U"].astype(mm)))


def _fold(a):
    """The rows of ``a [Q, d]`` summed eight apart: ``[SUBLANES, d]``."""
    return sum(a[r:r + SUBLANES] for r in range(0, a.shape[0], SUBLANES))


def _fwd_head(q, k, v, pre, gate, bt, lanes, Z, top, decays, floor, eps, mm):
    """A chain, one head's chunk: -> the mixer's output before ``o_proj``
    ``[Q, d_v]`` float32, the state leaving the chunk, the largest ``|S|`` so
    far and the sum of the decays ``exp(g)`` so far, eight rows a lane."""
    (qn, kn, kb, vb), _ = _operands(q, k, v, bt, mm)
    w = yield from _chunk(qn, kn, kb, vb, pre, lanes, floor, Z, mm)
    o = _scan_out(w, mm)
    yield
    new = jnp.exp(w["cend"]) * Z + _dot(w["U"].astype(mm), w["ke"].astype(mm), _TN)
    yield
    oh, _, sg = _normed(o, gate, eps, mm)
    y = oh * lanes[2:3, :] * sg
    # the state and the gate are in VMEM here, so the statistics cost no pass
    # over the saved states or over the gate's projection
    size = jnp.abs(new)
    for r in range(0, size.shape[0], SUBLANES):
        top = jnp.maximum(top, size[r:r + SUBLANES])
    return y, new, top, decays + _fold(jnp.exp(floor * w["sig"]))


def _heads(state):
    """The lane slices of the heads of a grid step's block (``state``: the
    scratch, ``[Hb, d, d]``): a head is ``d`` lanes of every block."""
    block, d = state.shape[:2]
    return [slice(h * d, (h + 1) * d) for h in range(block)]


def _beta_lanes(beta_ref, block):
    """Of ``beta``'s block ``[Q, lanes]`` float32, a head a lane: for each head
    of the grid step's block its column ``[Q, 1]`` and the mask of its lane."""
    beta = beta_ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, beta.shape, 1)
    first = pl.program_id(1) * block
    mine = [lane == first + h for h in range(block)]
    return [_rowsum(jnp.where(m, beta, 0.0)) for m in mine], mine


def _fwd_kernel(q_ref, k_ref, v_ref, pre_ref, gate_ref, beta_ref, lanes_ref, y_ref,
                st_ref, top_ref, decay_ref, state, *, floor, eps):
    mm = q_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)
        top_ref[...] = jnp.zeros_like(top_ref)
        decay_ref[...] = jnp.zeros_like(decay_ref)

    heads = list(enumerate(_heads(state)))
    betas, _ = _beta_lanes(beta_ref, len(heads))
    done = _abreast(
        _fwd_head(q_ref[0, :, sl], k_ref[0, :, sl], v_ref[0, :, sl], pre_ref[0, :, sl],
                  gate_ref[0, :, sl], betas[h], lanes_ref[:, sl], state[h], top_ref[0, h],
                  decay_ref[0, h], floor, eps, mm)
        for h, sl in heads)
    for (h, sl), (y, new, top, decays) in zip(heads, done):
        y_ref[0, :, sl] = y.astype(y_ref.dtype)
        state[h] = new
        st_ref[0, 0, :, sl] = new
        top_ref[0, h] = top
        decay_ref[0, h] = decays


def _raw_grads(dqn, dkn, dkb, dvb, v, bt, kn, rows):
    """The gradients of the scan's operands (float32, ``[Q, d]``) -> those of the
    RAW q, k and v and of the head's beta ``[Q, 1]``: ``kb = beta k`` and ``vb
    = beta v`` give k, v and beta theirs, and a unit row's Jacobian is the
    projection off the row, over its norm."""
    f32 = jnp.float32
    qh, rq, kh, rk = rows
    kf, vf = kn.astype(f32), v.astype(f32)
    dkn = dkn + bt * dkb
    dbeta = _rowsum(dkb * kf + dvb * vf)

    def off_the_row(g, unit, r, scale):
        return (g - unit * _rowsum(g * unit)) * (r * scale)

    return (off_the_row(dqn, qh, rq, float(qh.shape[-1]) ** -0.5),
            off_the_row(dkn, kh, rk, 1.0), bt * dvb, dbeta)


def _bwd_head(q, k, v, pre, gate, bt, lanes, dY, Z, dZ, floor, eps, mm):
    """A chain, one head's chunk backward: -> the gradients of the raw q, k, v,
    of ``pre`` and of the output gate ``[Q, d]`` float32, of the head's beta
    ``[Q, 1]``, the state's gradient entering the chunk before it and the
    three lanes ``[1, d]`` the rate, the bias and the output norm's weight
    receive from this chunk. ``o`` is made again as the forward made it."""
    f32 = jnp.float32
    Q = q.shape[0]
    (qn, kn, kb, vb), rows = _operands(q, k, v, bt, mm)
    w = yield from _chunk(qn, kn, kb, vb, pre, lanes, floor, Z, mm)
    o = _scan_out(w, mm)
    yield
    # through the gated norm: y = (o r) weight sigmoid(gate)
    weight = lanes[2:3, :]
    oh, r, sg = _normed(o, gate, eps, mm)
    dYf = dY.astype(f32)
    via = dYf * weight * sg
    dO = (r * (via - oh * (_rowsum(via * oh) / oh.shape[-1]))).astype(mm)
    dgate = dYf * (oh * weight) * sg * (1.0 - sg)
    dweight = jnp.sum(dYf * oh * sg, axis=0, keepdims=True)
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
    qf, kf, kbf = qn.astype(f32), kn.astype(f32), kb.astype(f32)
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
    dq, dk, dv, dbeta = _raw_grads(dq, dk, dkb, dR, v, bt, kn, rows)
    return (dq, dk, dv, dpre, dgate, dbeta, before,
            jnp.sum(dz * w["shifted"], axis=0, keepdims=True),
            jnp.sum(dpre, axis=0, keepdims=True), dweight)


def _bwd_kernel(q_ref, k_ref, v_ref, pre_ref, gate_ref, beta_ref, lanes_ref, dy_ref,
                st_ref, dq_ref, dk_ref, dv_ref, dpre_ref, dgate_ref, dbeta_ref,
                dlanes_ref, dstate, *, floor, eps):
    mm = q_ref.dtype
    step, steps = pl.program_id(2), pl.num_programs(2)

    @pl.when(step == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        dlanes_ref[...] = jnp.zeros_like(dlanes_ref)

    # the chunks run in reverse: the last step is the sequence's first chunk,
    # which no state enters
    heads = list(enumerate(_heads(dstate)))
    betas, mine = _beta_lanes(beta_ref, len(heads))
    done = _abreast(
        _bwd_head(q_ref[0, :, sl], k_ref[0, :, sl], v_ref[0, :, sl], pre_ref[0, :, sl],
                  gate_ref[0, :, sl], betas[h], lanes_ref[:, sl], dy_ref[0, :, sl],
                  jnp.where(step == steps - 1, 0.0, st_ref[0, 0, :, sl]),
                  dstate[h], floor, eps, mm)
        for h, sl in heads)
    dbeta = jnp.zeros(dbeta_ref.shape[2:], jnp.float32)
    for (h, sl), result in zip(heads, done):
        grads, (db, before), dlanes = result[:5], result[5:7], result[7:]
        for ref, grad in zip((dq_ref, dk_ref, dv_ref, dpre_ref, dgate_ref), grads):
            ref[0, :, sl] = grad.astype(ref.dtype)
        dbeta = jnp.where(mine[h], db, dbeta)
        dstate[h] = before
        # the rate's, the bias's and the norm weight's, summed over the tokens
        # as the kernel goes: the rows of ``lanes``, a block held over the chunks
        for row, lane in enumerate(dlanes):
            dlanes_ref[0, row:row + 1, sl] += lane
    dbeta_ref[0, 0] = dbeta


def _compiler_params(chunk: int, d: int, block: int, itemsize: int, arrays: int):
    from .kernel_dispatch import kda_vmem_bytes, vmem_limit_bytes
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=vmem_limit_bytes(
            kda_vmem_bytes(block, d, chunk, itemsize, arrays)))


def _specs(shape, heads, chunk, block, beta, at):
    """The block a grid step takes of an operand ``[b, s, heads * d]``, of
    ``beta [b, s, lanes]`` (all its lanes: a head's is picked in VMEM) and of
    ``lanes``; ``at``: the chunk of grid step ``n``."""
    d = shape[2] // heads
    return (pl.BlockSpec((1, chunk, block * d), lambda b, h, n: (b, at(n), h)),
            pl.BlockSpec((1, chunk, beta.shape[2]), lambda b, h, n: (b, at(n), 0)),
            pl.BlockSpec((SUBLANES, block * d), lambda b, h, n: (0, h)))


def _fwd_call(q, k, v, pre, gate, beta, lanes, heads, chunk, floor, eps, interpret,
              block):
    """A grid step is a chunk of ``block`` heads (``kernel_dispatch.
    choose_kda_heads``): ``block * d`` contiguous lanes of every operand.
    -> the gated, normalised output ``[b, s, heads * d]``, the states leaving
    the chunks, and a head's largest ``|S|`` and summed decays, eight rows a
    lane."""
    b, s, width = q.shape
    d, nc = width // heads, s // chunk
    x, bx, lx = _specs(q.shape, heads, chunk, block, beta, lambda n: n)
    # one block a (batch, head), held over its chunks
    stat = pl.BlockSpec((1, block, SUBLANES, d), lambda b, h, n: (b, h, 0, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, floor=floor, eps=eps),
        grid=(b, heads // block, nc),
        in_specs=[x] * 5 + [bx, lx],
        out_specs=[x, pl.BlockSpec((1, 1, d, block * d), lambda b, h, n: (b, n, 0, h)),
                   stat, stat],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, nc, d, width), jnp.float32)]
        + [jax.ShapeDtypeStruct((b, heads, SUBLANES, d), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((block, d, d), jnp.float32)],
        compiler_params=_compiler_params(chunk, d, block, q.dtype.itemsize, 6),
        interpret=interpret,
        name="kda_chunk_fwd",
    )(q, k, v, pre, gate, beta, lanes)


def _bwd_call(q, k, v, pre, gate, beta, lanes, states, dy, heads, chunk, floor, eps,
              interpret, block):
    """-> the gradients of q, k, v, ``pre`` and ``gate`` (as they are), of
    ``beta`` and of ``lanes``."""
    b, s, width = q.shape
    d, nc = width // heads, s // chunk
    x, bx, lx = _specs(q.shape, heads, chunk, block, beta, lambda n: nc - 1 - n)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)      # noqa: E731
    *grads, dbeta, dlanes = pl.pallas_call(
        functools.partial(_bwd_kernel, floor=floor, eps=eps),
        grid=(b, heads // block, nc),
        in_specs=[x] * 5 + [bx, lx, x] + [
            # the state ENTERING the chunk is the one the chunk before it
            # wrote; chunk 0 reads a block it does not use
            pl.BlockSpec((1, 1, d, block * d), lambda b, h, n: (
                b, jnp.maximum(nc - 2 - n, 0), 0, h))],
        out_specs=[x] * 5 + [
            # beta's, a head a lane as beta came: a grid step fills its own
            # heads' lanes of a block of its own
            pl.BlockSpec((1, 1, chunk, beta.shape[2]),
                         lambda b, h, n: (b, h, nc - 1 - n, 0)),
            pl.BlockSpec((1, SUBLANES, block * d), lambda b, h, n: (b, 0, h))],
        out_shape=[like(q), like(k), like(v), like(pre), like(gate),
                   jax.ShapeDtypeStruct((b, heads // block, s, beta.shape[2]),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((b, SUBLANES, width), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, d, d), jnp.float32)],
        compiler_params=_compiler_params(chunk, d, block, q.dtype.itemsize, 11),
        interpret=interpret,
        name="kda_chunk_bwd",
    )(q, k, v, pre, gate, beta, lanes, dy.astype(q.dtype), states)
    with jax.named_scope("ds.kda.gates"):
        # the grid steps' blocks of beta's gradient are disjoint by lane;
        # the lanes' rows (rate, bias, the norm's weight) over the batch
        return (*grads, jnp.sum(dbeta, axis=1), jnp.sum(dlanes, axis=0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12, 13))
def _kda_chunks(q, k, v, pre, gate, beta, lanes, heads, chunk, floor, eps, interpret,
                keep, block):
    y, _, tops, decays = _fwd_call(q, k, v, pre, gate, beta, lanes, heads, chunk, floor,
                                   eps, interpret, block)
    return y, tops, decays


def _kda_vjp_fwd(q, k, v, pre, gate, beta, lanes, heads, chunk, floor, eps, interpret,
                 keep, block):
    y, states, tops, decays = _fwd_call(q, k, v, pre, gate, beta, lanes, heads, chunk,
                                        floor, eps, interpret, block)
    # what the backward and ``o_proj``'s need of the forward kernel, under the
    # name a recomputation may keep them by (its layer's plan said which)
    name = SCAN_NAME if keep else SCAN_NAME + AGAIN
    y, states = checkpoint_name(y, name), checkpoint_name(states, name)
    return (y, tops, decays), (q, k, v, pre, gate, beta, lanes, states)


def _kda_vjp_bwd(heads, chunk, floor, eps, interpret, keep, block, res, cotangents):
    return _bwd_call(*res, cotangents[0], heads, chunk, floor, eps, interpret, block)


_kda_chunks.defvjp(_kda_vjp_fwd, _kda_vjp_bwd)


@functools.partial(jax.jit, static_argnames=("heads", "chunk", "floor", "eps",
                                             "interpret", "keep", "block"))
def _kda_jit(q, k, v, pre, gate, beta, lanes, heads, chunk, floor, eps, interpret, keep,
             block):
    # a frame of its own in the name stack, as for the state-space scan: the
    # kernels keep their names (``%kda_chunk_fwd*``, ``%kda_chunk_bwd*``)
    return _kda_chunks(q, k, v, pre, gate, beta, lanes, heads, chunk, floor, eps,
                       interpret, keep, block)


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


# the norms' float32 insides are made again in the backward from their
# operands in the model's type (a checkpoint each): kept, they are five
# float32 arrays of tokens x inner a layer, 1.3 GB at 16,384 tokens
@functools.partial(jax.checkpoint, static_argnums=(1, 2))
def l2norm(a, scale: float, dtype):
    """The rows ``a [..., d]`` over their L2 norm, times ``scale``, float32
    inside, in ``dtype``."""
    a = a.astype(jnp.float32)
    return (a * jax.lax.rsqrt(jnp.sum(a * a, axis=-1, keepdims=True) + L2_EPS)
            * scale).astype(dtype)


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def gated_norm(o, gate, weight, eps: float, dtype):
    """``RMSNorm_d(o) * weight * sigmoid(gate)`` head by head: ``o``, ``gate``
    ``[b, s, H, d]``, ``weight`` ``[d]``, float32 inside, in ``dtype``."""
    o = o.astype(jnp.float32)
    var = jnp.mean(o * o, axis=-1, keepdims=True)
    return (o * jax.lax.rsqrt(var + eps) * weight
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(dtype)


def kda_fused(q, k, v, pre, rate, bias, beta, gate, weight, chunk: int, *, eps: float,
              use_kernel: bool, floor: float = GATE_FLOOR, interpret: bool = False,
              with_stats: bool = False, keep: bool = True):
    """The mixer between its convolutions and ``o_proj``: ``RMSNorm_d(o) *
    weight * sigmoid(gate)`` of the recurrence's ``o`` at ``l2norm(q) /
    sqrt(d)``, ``l2norm(k)``, ``v`` under the bounded gate ``g =``
    :func:`bounded_gate` ``(pre, rate, bias, floor)``. ``q``, ``k``, ``v``,
    ``pre``, ``gate`` ``[b, s, H, d]`` (the convolutions' and projections'
    outputs as they are), ``rate`` ``[H]`` (positive), ``bias`` ``[H * d]``,
    ``beta`` ``[b, s, H]``, ``weight`` ``[d]`` -> ``[b, s, H, d]`` in
    ``v.dtype``.

    ``use_kernel``: the Pallas kernels in chunks of ``chunk`` (a multiple of
    16; forward and hand-written backward), which make the norms, ``beta k``,
    ``beta v`` and the gated output norm on the tiles they hold; the caller
    decides, as for flash attention (a raw ``pallas_call`` is not partitioned
    over a mesh of more than one device). Otherwise XLA makes them
    (:func:`l2norm`, :func:`gated_norm`, under ``ds.kda.norm``) around the
    recurrence. The kernels want ``d_k = d_v``, a multiple of 128, and a
    floor no lower than ``GATE_FLOOR``. A sequence that ``chunk`` does not
    divide is padded with rows of zeros, a gate of 0 and ``beta = 0``: no
    decay, nothing written.

    ``with_stats``: also a dict without gradient: ``state_absmax`` (the
    largest ``|S|`` at the chunks' ends, the states the kernels keep),
    ``decay_mean`` (of ``exp(g)``, every channel and token) and
    ``fused_rows`` (1.0 where the kernels made the norms and beta products,
    0.0 where XLA did). ``keep``: whether a recomputation may keep the
    kernel's output and states (``SCAN_NAME``)."""
    b, s, H, d = q.shape
    if (any(a.shape != q.shape for a in (k, pre, gate)) or v.shape[:3] != (b, s, H)
            or beta.shape != (b, s, H) or rate.shape != (H, ) or bias.shape != (H * d, )
            or weight.shape != v.shape[-1:]):
        raise ValueError(f"kda_fused: q {q.shape}, k {k.shape}, v {v.shape}, pre "
                         f"{pre.shape}, gate {gate.shape}, rate {rate.shape}, bias "
                         f"{bias.shape}, beta {beta.shape}, weight {weight.shape}: want "
                         "q, k, pre, gate [b, s, H, d_k], v [b, s, H, d_v], rate [H], "
                         "bias [H * d_k], beta [b, s, H], weight [d_v]")
    f32, dtype = jnp.float32, v.dtype
    if not (use_kernel or interpret):
        with jax.named_scope("ds.kda.norm"):
            qn, kn = l2norm(q, float(d) ** -0.5, dtype), l2norm(k, 1.0, dtype)
        g = bounded_gate(pre, rate, bias, floor)
        o = kda_reference(qn, kn, v, g, beta, with_stats, stat_every=chunk)
        o, top = o if with_stats else (o, None)
        with jax.named_scope("ds.kda.norm"):
            y = gated_norm(o, gate, weight, eps, dtype)
        if not with_stats:
            return y
        with jax.named_scope("ds.kda.gates"):
            decay = jnp.mean(jnp.exp(g))
        return y, {"state_absmax": top, "decay_mean": jax.lax.stop_gradient(decay),
                   "fused_rows": jnp.zeros((), f32)}
    if v.shape[-1] != d or d % 128 or chunk % SUB or not GATE_FLOOR <= floor <= 0:
        raise ValueError(f"the kda kernels want d_k = d_v a multiple of 128, a chunk "
                         f"that is a multiple of {SUB} and a floor in [{GATE_FLOOR}, 0]: "
                         f"got d_k {d}, d_v {v.shape[-1]}, chunk {chunk}, floor {floor}")
    pad = -s % chunk

    def flat(a, fill=0.0):
        return jnp.pad(a.astype(dtype).reshape(b, s, H * d), ((0, 0), (0, pad), (0, 0)),
                       constant_values=fill)

    with jax.named_scope("ds.kda.gates"):
        # a head a lane, in whole registers; the rate, the bias and the norm's
        # weight a row each
        bt = jnp.pad(beta.astype(f32), ((0, 0), (0, pad), (0, -H % LANES)))
        lanes = jnp.zeros((SUBLANES, H * d), f32)
        lanes = (lanes.at[0].set(jnp.repeat(rate.astype(f32), d)).at[1].set(bias.astype(f32))
                 .at[2].set(jnp.tile(weight.astype(f32), H)))
    block, _ = grid_of(b, s, H, d, chunk, dtype.itemsize)
    y, tops, decays = _kda_jit(flat(q), flat(k), flat(v), flat(pre, _PAD_PRE), flat(gate),
                               bt, lanes, H, chunk, float(floor), float(eps), interpret,
                               bool(keep), block)
    y = y[:, :s].reshape(b, s, H, d)
    if not with_stats:
        return y
    with jax.named_scope("ds.kda.gates"):
        # a padded token's decay is exp(0)
        decay = (jnp.sum(decays) - b * pad * H * d) / (b * s * H * d)
        stats = {"state_absmax": jnp.max(tops), "decay_mean": decay,
                 "fused_rows": jnp.ones((), f32)}
    return y, jax.lax.stop_gradient(stats)


registry.register("kda", "pallas", True,
                  "Kimi Delta Attention chunked scan, forward and backward")
