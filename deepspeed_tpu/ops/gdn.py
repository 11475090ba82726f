"""Gated DeltaNet's scan in chunks (a delta rule whose state decays by one
rate a VALUE head and token; Yang et al., arXiv:2412.06464, as Qwen3-Next's
linear-attention layers have it).

Per value head ``h``, reading key head ``h // ratio`` (``ratio`` value heads
share a key head's q and k), with ``g_t`` the log decay (any value <= 0:
``-exp(A_log_h) * softplus(.)`` has no floor), ``beta_t`` in [0, 1] and a
state ``S`` of ``d_k x d_v`` in float32, zero before the first token:

    S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

(the decay commutes with the projection, so this is Kimi Delta Attention's
recurrence, ``ops/kda.py``, at a decay that is constant over a head's key
channels.) :func:`gdn_reference` is that recurrence token by token in
float32: the kernels' oracle, and what runs where there is no TPU or the mesh
has more than one device. :func:`gdn_fused` is the mixer between its
convolution and ``out_proj``: the recurrence at ``q = l2norm(q) / sqrt(d)``,
``k = l2norm(k)``, then ``RMSNorm_d(o) * weight * silu(z)`` head by head.

The chunk algebra is KDA's, and its triangular solve (``_inverse``: by
blocks, the diagonal blocks of eight rows first, then the blocks below them a
level at a time), its state passing, its unit rows and its matmul stages
abreast are KDA's code.
What differs, and why the kernels are these and not those:

- **The gate is one float32 a head and token and is never spread over a
  head's lanes in HBM**: ``g`` and ``beta`` come ``[batch, seq, 128]``, a head
  a lane, and their gradients leave the same way. A grid step picks its heads'
  columns in VMEM.
- **No floor under the gate.** KDA's decay rides on the operands (``k e^{r -
  c}`` around a block's reference ``r``), which a floor of -5 keeps inside
  float32. A scalar decay need not: ``M[t, s] = (k_t . k_s) e^{c_t - c_s}``,
  and the exponent, the sum of ``g`` over ``(s, t]``, is made by ONE matmul
  of a triangle of ones with ``g`` masked to the rows after ``s``. It is never
  a difference of two running sums and never positive: a token with ``g =
  -1000`` underflows to 0 as it should and nothing overflows.
- **A key head is read once for the value heads that share it.** A grid step
  holds a block of value heads and the ``block / ratio`` key heads under them;
  the unit rows of q and k are made once a key head, and the gradients of the
  raw q and k are the sum over the value heads, formed in VMEM.
- **SiLU in the output norm** (KDA's gate is a sigmoid), the weight as it is.

With ``c_t`` the running sum of ``g`` in a chunk, ``D[t, s] = e^{c_t - c_s}``
(``s <= t``), ``kb = beta k``, ``vb = beta v`` and ``S_prev`` the state
entering the chunk:

    A = strictly_lower((kb k^T) o D),   P = lower((q k^T) o D)
    T = (I + A)^{-1}
    U = T (vb - (kb e^{c}) S_prev)
    O = (q e^{c}) S_prev + P U
    S_new = e^{c_Q} S_prev + (k e^{c_Q - c})^T U

Precision as ``ops/kda.py``: matmul operands in ``q.dtype`` with float32
accumulation, ``T`` made and applied in float32, every exponential and the
carried state float32, a state that is an operand as its two bf16 parts.

Under a layer's recomputation the output and the chunk states are named
(``SCAN_NAME``, a candidate of ``ops/remat.py``), as KDA's are.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# ``scan_bytes`` (what a layer keeps under ``SCAN_NAME``: the output and the
# float32 chunk states, by the VALUE heads here) and the kernels' compiler
# parameters are KDA's as they are
from .kda import (LANES, SUB, _HIGHEST, _NT, _TN, _abreast, _beta_lanes, _compiler_params,
                  _dot, _heads, _inverse, _normed, _rowsum, _scan_out, _split, _unit,
                  l2norm, scan_bytes)  # noqa: F401  (scan_bytes: re-exported)
from .registry import registry
from .remat import AGAIN, GDN_SCAN as SCAN_NAME
from .ssd import SUBLANES


def gdn_reference(q, k, v, g, beta, with_state_absmax: bool = False,
                  stat_every: int = 1):
    """``q``, ``k`` ``[b, s, Hk, d_k]``, ``v`` ``[b, s, Hv, d_v]`` (``Hv`` a
    multiple of ``Hk``: value head ``h`` reads key head ``h // (Hv / Hk)``),
    ``g`` (the log decay, <= 0) and ``beta`` ``[b, s, Hv]`` -> ``o [b, s, Hv,
    d_v]`` in ``v.dtype``: the recurrence, one token after another, float32
    inside. ``with_state_absmax``: also the largest ``|S|`` left by the tokens
    that end a run of ``stat_every`` (every token by default) or the sequence."""
    f32 = jnp.float32
    b, s, Hk, dk = q.shape
    Hv = v.shape[2]
    ratio = Hv // Hk

    def step(carry, inp):
        S, top = carry                                      # [b, Hv, dk, dv]
        qt, kt, vt, gt, bt, counts = inp
        qt, kt = jnp.repeat(qt, ratio, axis=1), jnp.repeat(kt, ratio, axis=1)
        S = jnp.exp(gt)[..., None, None] * S
        seen = jnp.einsum("bhkv,bhk->bhv", S, kt, precision=_HIGHEST)
        S = S + (bt[..., None] * kt)[..., None] * (vt - seen)[:, :, None, :]
        o = jnp.einsum("bhkv,bhk->bhv", S, qt, precision=_HIGHEST)
        return (S, jnp.where(counts, jnp.maximum(top, jnp.max(jnp.abs(S))), top)), o

    counts = ((jnp.arange(s) + 1) % stat_every == 0).at[s - 1].set(True)
    time_major = [jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta)]
    init = (jnp.zeros((b, Hv, dk, v.shape[-1]), f32), jnp.zeros((), f32))
    (_, top), o = jax.lax.scan(step, init, time_major + [counts])
    o = jnp.moveaxis(o, 0, 1).astype(v.dtype)
    return (o, jax.lax.stop_gradient(top)) if with_state_absmax else o


def _triangle(Q):
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    return row, col, jnp.where(row >= col, 1.0, 0.0)


def _chunk(qn, kn, kb, vb, gt, Z, mm):
    """A chain (``kda._abreast``) -> what both kernels make of a value head's
    chunk and the state entering it (``Z``, ``[d_v, d_k]``: the state
    transposed). ``gt``: the head's log decay, ``[Q, 1]`` float32."""
    f32 = jnp.float32
    Q, d = qn.shape
    row, col, ones = _triangle(Q)
    # the log decay from s to t, the sum of g over (s, t], and the running
    # sum itself on every lane of the head
    span = _dot(ones, jnp.where(row > col, gt, 0.0), precision=_HIGHEST)
    c = _dot(ones, jnp.broadcast_to(gt, (Q, d)), precision=_HIGHEST)
    yield
    D = jnp.where(row >= col, jnp.exp(span), 0.0)
    A = jnp.where(row > col, _dot(kb, kn, _NT) * D, 0.0)
    P = _dot(qn, kn, _NT) * D
    yield
    T = yield from _inverse(A)
    E = jnp.exp(c)
    qp, kbp = qn.astype(f32) * E, kb.astype(f32) * E
    zs = _split(Z, mm)
    R = vb.astype(f32) - sum(_dot(kbp.astype(mm), z, _NT) for z in zs)
    yield
    U = _dot(T, R, precision=_HIGHEST)                           # [Q, d_v]
    yield
    cend = c[-1:, :]
    to_end = jnp.exp(cend - c)
    ke = kn.astype(f32) * to_end
    return dict(A=A, P=P, D=D, T=T, E=E, qp=qp, kbp=kbp, zs=zs, U=U, cend=cend,
                to_end=to_end, ke=ke, ones=ones, row=row, col=col)


def _key_rows(q, k, mm):
    """What the value heads over a key head share of its chunk: ``l2norm(q) /
    sqrt(d)`` and ``l2norm(k)`` in ``mm``, and what the norms' backward reads."""
    qh, rq, qn = _unit(q, float(q.shape[-1]) ** -0.5)
    kh, rk, kn = _unit(k, 1.0)
    return (qn.astype(mm), kn.astype(mm)), (qh, rq, kh, rk)


def _beta_rows(kn, v, bt, mm):
    f32 = jnp.float32
    return (bt * kn.astype(f32)).astype(mm), (bt * v.astype(f32)).astype(mm)


def _fwd_head(qn, kn, v, z, bt, gt, weight, Z, top, eps, mm):
    """A chain, one value head's chunk: -> the mixer's output before
    ``out_proj`` ``[Q, d_v]`` float32, the state leaving the chunk and the
    largest ``|S|`` so far, eight rows a lane."""
    kb, vb = _beta_rows(kn, v, bt, mm)
    w = yield from _chunk(qn, kn, kb, vb, gt, Z, mm)
    o = _scan_out(w, mm)
    yield
    new = jnp.exp(w["cend"]) * Z + _dot(w["U"].astype(mm), w["ke"].astype(mm), _TN)
    yield
    oh, _, sg = _normed(o, z, eps, mm)
    y = oh * weight * (z.astype(jnp.float32) * sg)
    size = jnp.abs(new)
    for r in range(0, size.shape[0], SUBLANES):
        top = jnp.maximum(top, size[r:r + SUBLANES])
    return y, new, top


def _key_slices(block, ratio, d):
    return [slice(j * d, (j + 1) * d) for j in range(block // ratio)]


def _fwd_kernel(q_ref, k_ref, v_ref, z_ref, g_ref, beta_ref, lanes_ref, y_ref, st_ref,
                top_ref, state, *, ratio, eps):
    mm = v_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)
        top_ref[...] = jnp.zeros_like(top_ref)

    heads = list(enumerate(_heads(state)))
    betas, _ = _beta_lanes(beta_ref, len(heads))
    gates, _ = _beta_lanes(g_ref, len(heads))
    keys = [_key_rows(q_ref[0, :, sl], k_ref[0, :, sl], mm)[0]
            for sl in _key_slices(len(heads), ratio, state.shape[1])]
    done = _abreast(
        _fwd_head(*keys[h // ratio], v_ref[0, :, sl], z_ref[0, :, sl], betas[h], gates[h],
                  lanes_ref[0:1, sl], state[h], top_ref[0, h], eps, mm)
        for h, sl in heads)
    for (h, sl), (y, new, top) in zip(heads, done):
        y_ref[0, :, sl] = y.astype(y_ref.dtype)
        state[h] = new
        st_ref[0, 0, :, sl] = new
        top_ref[0, h] = top


def _bwd_head(qn, kn, v, z, bt, gt, weight, dY, Z, dZ, eps, mm):
    """A chain, one value head's chunk backward: -> the gradients of the
    scan's ``qn`` and ``kn`` (what the key head's unit rows receive from this
    value head), of the raw v and of ``z`` ``[Q, d]`` float32, of the head's
    beta and log decay ``[Q, 1]``, the state's gradient entering the chunk
    before it and the lane ``[1, d]`` the output norm's weight receives. ``o``
    is made again as the forward made it."""
    f32 = jnp.float32
    Q = qn.shape[0]
    kb, vb = _beta_rows(kn, v, bt, mm)
    w = yield from _chunk(qn, kn, kb, vb, gt, Z, mm)
    o = _scan_out(w, mm)
    yield
    # through the gated norm: y = (o r) weight silu(z)
    oh, r, sg = _normed(o, z, eps, mm)
    zf, dYf = z.astype(f32), dY.astype(f32)
    act = zf * sg
    via = dYf * weight * act
    dO = (r * (via - oh * (_rowsum(via * oh) / oh.shape[-1]))).astype(mm)
    dz = dYf * (oh * weight) * sg * (1.0 + zf * (1.0 - sg))
    dweight = jnp.sum(dYf * oh * act, axis=0, keepdims=True)
    row, col = w["row"], w["col"]
    zs, Umm = w["zs"], w["U"].astype(mm)
    dzs = _split(dZ, mm)
    kemm = w["ke"].astype(mm)
    dU = _dot(w["P"].astype(mm), dO, _TN) + sum(_dot(kemm, s, _NT) for s in dzs)
    yield
    dR = _dot(w["T"], dU, _TN, precision=_HIGHEST)               # T^T dU
    yield
    dRmm = dR.astype(mm)
    dP = jnp.where(row >= col, _dot(dO, Umm, _NT), 0.0)
    dA = jnp.where(row > col, -_dot(dRmm, Umm, _NT), 0.0)
    dqp = sum(_dot(dO, s) for s in zs)                           # [Q, d_k]
    dkbp = -sum(_dot(dRmm, s) for s in zs)
    dke = sum(_dot(Umm, s) for s in dzs)
    before = (jnp.exp(w["cend"]) * dZ + _dot(dO, w["qp"].astype(mm), _TN)
              - _dot(dRmm, w["kbp"].astype(mm), _TN))
    yield
    # through the two triangles: M o D with D = exp(span)
    dspan = dP * w["P"] + dA * w["A"]
    dPD, dAD = (dP * w["D"]).astype(mm), (dA * w["D"]).astype(mm)
    dq = dqp * w["E"] + _dot(dPD, kn)
    dkb = dkbp * w["E"] + _dot(dAD, kn)
    dk = dke * w["to_end"] + _dot(dPD, qn, _TN) + _dot(dAD, kb, _TN)
    dG = _dot(w["ones"], dspan, _TN, precision=_HIGHEST)
    yield
    via_end = dke * w["ke"]
    dc = dqp * w["qp"] + dkbp * w["kbp"] - via_end
    last = jax.lax.broadcasted_iota(jnp.int32, dc.shape, 0) == Q - 1
    dc = dc + jnp.where(last, jnp.sum(via_end, axis=0, keepdims=True)
                        + jnp.exp(w["cend"]) * jnp.sum(dZ * Z, axis=0, keepdims=True),
                        0.0)
    # g_s reaches every c_t with t >= s, on every lane, and every span that
    # starts before s and ends at or after it
    dg = (_rowsum(_dot(w["ones"], dc, _TN, precision=_HIGHEST))
          + _rowsum(jnp.where(row > col, dG, 0.0)))
    yield
    dkn = dk + bt * dkb
    dbeta = _rowsum(dkb * kn.astype(f32) + dR * v.astype(f32))
    return dq, dkn, bt * dR, dz, dbeta, dg, before, dweight


def _off_the_row(g, unit, r, scale):
    """A unit row's Jacobian: the projection off the row, over its norm."""
    return (g - unit * _rowsum(g * unit)) * (r * scale)


def _bwd_kernel(q_ref, k_ref, v_ref, z_ref, g_ref, beta_ref, lanes_ref, dy_ref, st_ref,
                dq_ref, dk_ref, dv_ref, dz_ref, dg_ref, dbeta_ref, dlanes_ref, dstate, *,
                ratio, eps):
    mm = v_ref.dtype
    step, steps = pl.program_id(2), pl.num_programs(2)

    @pl.when(step == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        dlanes_ref[...] = jnp.zeros_like(dlanes_ref)

    # the chunks run in reverse: the last step is the sequence's first chunk,
    # which no state enters
    heads = list(enumerate(_heads(dstate)))
    d = dstate.shape[1]
    betas, mine = _beta_lanes(beta_ref, len(heads))
    gates, _ = _beta_lanes(g_ref, len(heads))
    key_slices = _key_slices(len(heads), ratio, d)
    keys = [_key_rows(q_ref[0, :, sl], k_ref[0, :, sl], mm) for sl in key_slices]
    done = _abreast(
        _bwd_head(*keys[h // ratio][0], v_ref[0, :, sl], z_ref[0, :, sl], betas[h],
                  gates[h], lanes_ref[0:1, sl], dy_ref[0, :, sl],
                  jnp.where(step == steps - 1, 0.0, st_ref[0, 0, :, sl]), dstate[h], eps, mm)
        for h, sl in heads)
    dbeta = jnp.zeros(dbeta_ref.shape[2:], jnp.float32)
    dgate = jnp.zeros(dg_ref.shape[2:], jnp.float32)
    for (h, sl), (_, _, dv, dz, db, dg, before, dweight) in zip(heads, done):
        dv_ref[0, :, sl] = dv.astype(dv_ref.dtype)
        dz_ref[0, :, sl] = dz.astype(dz_ref.dtype)
        dbeta = jnp.where(mine[h], db, dbeta)
        dgate = jnp.where(mine[h], dg, dgate)
        dstate[h] = before
        dlanes_ref[0, 0:1, sl] += dweight
    dbeta_ref[0, 0] = dbeta
    dg_ref[0, 0] = dgate
    # a key head's q and k receive the sum over the value heads that read them
    for j, sl in enumerate(key_slices):
        qh, rq, kh, rk = keys[j][1]
        over = done[j * ratio:(j + 1) * ratio]
        dq_ref[0, :, sl] = _off_the_row(sum(r[0] for r in over), qh, rq,
                                        float(d) ** -0.5).astype(dq_ref.dtype)
        dk_ref[0, :, sl] = _off_the_row(sum(r[1] for r in over), kh, rk,
                                        1.0).astype(dk_ref.dtype)


def _specs(d, chunk, block, ratio, at):
    """The blocks a grid step takes: of a value-head operand ``[b, s, Hv *
    d]``, of a key-head operand ``[b, s, Hk * d]`` (the key heads under the
    step's value heads), of ``g`` and ``beta`` ``[b, s, LANES]`` (all lanes: a
    head's is picked in VMEM) and of ``lanes``; ``at``: the chunk of step
    ``n``."""
    return (pl.BlockSpec((1, chunk, block * d), lambda b, h, n: (b, at(n), h)),
            pl.BlockSpec((1, chunk, block // ratio * d), lambda b, h, n: (b, at(n), h)),
            pl.BlockSpec((1, chunk, LANES), lambda b, h, n: (b, at(n), 0)),
            pl.BlockSpec((SUBLANES, block * d), lambda b, h, n: (0, h)))


def _fwd_call(q, k, v, z, g, beta, lanes, heads, chunk, eps, interpret, block):
    """-> the gated, normalised output ``[b, s, Hv * d]``, the states leaving
    the chunks and a head's largest ``|S|``, eight rows a lane. ``heads``:
    (key heads, value heads)."""
    b, s, width = v.shape
    Hk, Hv = heads
    d, nc, ratio = width // Hv, s // chunk, Hv // Hk
    xv, xk, gx, lx = _specs(d, chunk, block, ratio, lambda n: n)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, ratio=ratio, eps=eps),
        grid=(b, Hv // block, nc),
        in_specs=[xk, xk, xv, xv, gx, gx, lx],
        out_specs=[xv, pl.BlockSpec((1, 1, d, block * d), lambda b, h, n: (b, n, 0, h)),
                   # one block a (batch, head), held over its chunks
                   pl.BlockSpec((1, block, SUBLANES, d), lambda b, h, n: (b, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b, nc, d, width), jnp.float32),
                   jax.ShapeDtypeStruct((b, Hv, SUBLANES, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, d, d), jnp.float32)],
        compiler_params=_compiler_params(chunk, d, block, v.dtype.itemsize, 5),
        interpret=interpret,
        name="gdn_chunk_fwd",
    )(q, k, v, z, g, beta, lanes)


def _bwd_call(q, k, v, z, g, beta, lanes, states, dy, heads, chunk, eps, interpret,
              block):
    """-> the gradients of q, k, v and ``z`` (as they are), of ``g``, ``beta``
    and ``lanes``."""
    b, s, width = v.shape
    Hk, Hv = heads
    d, nc, ratio = width // Hv, s // chunk, Hv // Hk
    xv, xk, gx, lx = _specs(d, chunk, block, ratio, lambda n: nc - 1 - n)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)      # noqa: E731
    # a head a lane as g and beta came: a grid step fills its own heads' lanes
    # of a block of its own
    by_step = pl.BlockSpec((1, 1, chunk, LANES), lambda b, h, n: (b, h, nc - 1 - n, 0))
    by_step_shape = jax.ShapeDtypeStruct((b, Hv // block, s, LANES), jnp.float32)
    *grads, dg, dbeta, dlanes = pl.pallas_call(
        functools.partial(_bwd_kernel, ratio=ratio, eps=eps),
        grid=(b, Hv // block, nc),
        in_specs=[xk, xk, xv, xv, gx, gx, lx, xv,
                  # the state ENTERING the chunk is the one the chunk before it
                  # wrote; chunk 0 reads a block it does not use
                  pl.BlockSpec((1, 1, d, block * d), lambda b, h, n: (
                      b, jnp.maximum(nc - 2 - n, 0), 0, h))],
        out_specs=[xk, xk, xv, xv, by_step, by_step,
                   pl.BlockSpec((1, SUBLANES, block * d), lambda b, h, n: (b, 0, h))],
        out_shape=[like(q), like(k), like(v), like(z), by_step_shape, by_step_shape,
                   jax.ShapeDtypeStruct((b, SUBLANES, width), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, d, d), jnp.float32)],
        compiler_params=_compiler_params(chunk, d, block, v.dtype.itemsize, 9),
        interpret=interpret,
        name="gdn_chunk_bwd",
    )(q, k, v, z, g, beta, lanes, dy.astype(v.dtype), states)
    with jax.named_scope("ds.gdn.gates"):
        # the grid steps' blocks of g's and beta's gradients are disjoint by
        # lane; the norm weight's row over the batch
        return (*grads, jnp.sum(dg, axis=1), jnp.sum(dbeta, axis=1),
                jnp.sum(dlanes, axis=0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12))
def _gdn_chunks(q, k, v, z, g, beta, lanes, heads, chunk, eps, interpret, keep, block):
    y, _, tops = _fwd_call(q, k, v, z, g, beta, lanes, heads, chunk, eps, interpret, block)
    return y, tops


def _gdn_vjp_fwd(q, k, v, z, g, beta, lanes, heads, chunk, eps, interpret, keep, block):
    y, states, tops = _fwd_call(q, k, v, z, g, beta, lanes, heads, chunk, eps, interpret,
                                block)
    # what the backward and ``out_proj``'s need of the forward kernel, under
    # the name a recomputation may keep them by (its layer's plan said which)
    name = SCAN_NAME if keep else SCAN_NAME + AGAIN
    y, states = checkpoint_name(y, name), checkpoint_name(states, name)
    return (y, tops), (q, k, v, z, g, beta, lanes, states)


def _gdn_vjp_bwd(heads, chunk, eps, interpret, keep, block, res, cotangents):
    return _bwd_call(*res, cotangents[0], heads, chunk, eps, interpret, block)


_gdn_chunks.defvjp(_gdn_vjp_fwd, _gdn_vjp_bwd)


@functools.partial(jax.jit, static_argnames=("heads", "chunk", "eps", "interpret", "keep",
                                             "block"))
def _gdn_jit(q, k, v, z, g, beta, lanes, heads, chunk, eps, interpret, keep, block):
    # a frame of its own in the name stack, as for KDA's scan: the kernels
    # keep their names (``%gdn_chunk_fwd*``, ``%gdn_chunk_bwd*``)
    return _gdn_chunks(q, k, v, z, g, beta, lanes, heads, chunk, eps, interpret, keep,
                       block)


def grid_of(batch: int, seq: int, k_heads: int, v_heads: int, d: int, chunk: int,
            itemsize: int):
    """(value heads a grid step, grid steps a call) of the kernels at this
    call: what ``kernel_dispatch.choose_kda_heads`` gives the shape, and no
    fewer than the value heads over one key head."""
    from .kernel_dispatch import choose_kda_heads
    block = max(choose_kda_heads(v_heads, d, chunk, itemsize), v_heads // k_heads)
    return block, batch * (v_heads // block) * -(-seq // chunk)


def log_decay(a, a_log, dt_bias):
    """``g = -exp(A_log_h) * softplus(a + dt_bias_h)`` in float32: ``a`` ``[b,
    s, H]``, ``a_log``, ``dt_bias`` ``[H]`` -> ``[b, s, H]``, <= 0 and
    unbounded below."""
    f32 = jnp.float32
    return -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
        a.astype(f32) + dt_bias.astype(f32))


# the norm's float32 insides are made again in the backward (``kda.gated_norm``
# says why)
@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def gated_norm_silu(o, z, weight, eps: float, dtype):
    """``RMSNorm_d(o) * weight * silu(z)`` head by head: ``o``, ``z`` ``[b, s,
    H, d]``, ``weight`` ``[d]``, float32 inside, in ``dtype``."""
    o = o.astype(jnp.float32)
    var = jnp.mean(o * o, axis=-1, keepdims=True)
    return (o * jax.lax.rsqrt(var + eps) * weight
            * jax.nn.silu(z.astype(jnp.float32))).astype(dtype)


def gdn_fused(q, k, v, g, beta, z, weight, chunk: int, *, eps: float, use_kernel: bool,
              interpret: bool = False, with_stats: bool = False, keep: bool = True):
    """The mixer between its convolution and ``out_proj``: ``RMSNorm_d(o) *
    weight * silu(z)`` of the recurrence's ``o`` at ``l2norm(q) / sqrt(d)``,
    ``l2norm(k)``, ``v`` under the log decay ``g``. ``q``, ``k`` ``[b, s, Hk,
    d_k]`` (the convolution's outputs as they are), ``v``, ``z`` ``[b, s, Hv,
    d_v]``, ``g``, ``beta`` ``[b, s, Hv]`` float32, ``weight`` ``[d_v]`` ->
    ``[b, s, Hv, d_v]`` in ``v.dtype``.

    ``use_kernel``: the Pallas kernels in chunks of ``chunk`` (a multiple of
    16; forward and hand-written backward), which make the norms, ``beta k``,
    ``beta v`` and the gated output norm on the tiles they hold; the caller
    decides, as for flash attention. Otherwise XLA makes them (``kda.l2norm``,
    :func:`gated_norm_silu`, under ``ds.gdn.norm``) around the recurrence. The
    kernels want ``d_k = d_v``, a multiple of 128, and at most 128 value
    heads. A sequence that ``chunk`` does not divide is padded with rows of
    zeros, ``g = 0`` and ``beta = 0``: no decay, nothing written.

    ``with_stats``: also a dict without gradient: ``state_absmax`` (the
    largest ``|S|`` at the chunks' ends), ``decay_mean`` (of ``exp(g)``, every
    head and token) and ``fused_rows`` (1.0 where the kernels made the norms
    and beta products, 0.0 where XLA did). ``keep``: whether a recomputation
    may keep the kernel's output and states (``SCAN_NAME``)."""
    b, s, Hk, d = q.shape
    Hv = v.shape[2]
    if (k.shape != q.shape or v.shape[:2] != (b, s) or z.shape != v.shape or Hv % Hk
            or g.shape != (b, s, Hv) or beta.shape != (b, s, Hv)
            or weight.shape != v.shape[-1:]):
        raise ValueError(f"gdn_fused: q {q.shape}, k {k.shape}, v {v.shape}, z {z.shape}, "
                         f"g {g.shape}, beta {beta.shape}, weight {weight.shape}: want q, "
                         "k [b, s, Hk, d_k], v, z [b, s, Hv, d_v] with Hv a multiple of "
                         "Hk, g, beta [b, s, Hv], weight [d_v]")
    f32, dtype = jnp.float32, v.dtype
    stats = {}
    if with_stats:
        with jax.named_scope("ds.gdn.gates"):
            stats["decay_mean"] = jnp.mean(jnp.exp(g.astype(f32)))
    if not (use_kernel or interpret):
        with jax.named_scope("ds.gdn.norm"):
            qn, kn = l2norm(q, float(d) ** -0.5, dtype), l2norm(k, 1.0, dtype)
        o = gdn_reference(qn, kn, v, g, beta, with_stats, stat_every=chunk)
        o, top = o if with_stats else (o, None)
        with jax.named_scope("ds.gdn.norm"):
            y = gated_norm_silu(o, z, weight, eps, dtype)
        if not with_stats:
            return y
        stats.update(state_absmax=top, fused_rows=jnp.zeros((), f32))
        return y, jax.lax.stop_gradient(stats)
    if v.shape[-1] != d or d % 128 or chunk % SUB or Hv > LANES:
        raise ValueError(f"the gdn kernels want d_k = d_v a multiple of 128, a chunk "
                         f"that is a multiple of {SUB} and at most {LANES} value heads: "
                         f"got d_k {d}, d_v {v.shape[-1]}, chunk {chunk}, {Hv} heads")
    pad = -s % chunk

    def flat(a):
        return jnp.pad(a.astype(dtype).reshape(b, s, -1), ((0, 0), (0, pad), (0, 0)))

    with jax.named_scope("ds.gdn.gates"):
        # a head a lane, in whole registers; the norm's weight a row
        by_lane = lambda a: jnp.pad(a.astype(f32),                   # noqa: E731
                                    ((0, 0), (0, pad), (0, LANES - Hv)))
        gl, bl = by_lane(g), by_lane(beta)
        lanes = jnp.zeros((SUBLANES, Hv * d), f32).at[0].set(
            jnp.tile(weight.astype(f32), Hv))
    block, _ = grid_of(b, s, Hk, Hv, d, chunk, dtype.itemsize)
    y, tops = _gdn_jit(flat(q), flat(k), flat(v), flat(z), gl, bl, lanes, (Hk, Hv), chunk,
                       float(eps), interpret, bool(keep), block)
    y = y[:, :s].reshape(b, s, Hv, d)
    if not with_stats:
        return y
    stats.update(state_absmax=jnp.max(tops), fused_rows=jnp.ones((), f32))
    return y, jax.lax.stop_gradient(stats)


registry.register("gdn", "pallas", True,
                  "Gated DeltaNet chunked scan, forward and backward")
