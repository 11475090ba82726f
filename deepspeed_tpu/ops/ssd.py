"""Mamba-2's state-space scan in chunks (state-space duality, SSD).

Per head ``h`` (``P`` values a head, a state of ``P x N``, one group: ``B``
and ``C`` are shared by the heads), with ``a_t = dt_t * A_h <= 0``:

    S_t = exp(a_t) * S_{t-1} + dt_t * x_t (x) B_t        (S zero before t = 0)
    y_t = S_t C_t + D_h * x_t

:func:`ssd_reference` is that recurrence token by token in float32: the
kernels' oracle, and what runs where there is no TPU. :func:`ssd_scan` is the
same function in chunks of ``Q`` tokens. With ``c_t`` the running sum of ``a``
inside a chunk, ``x~ = dt * x`` and ``S_prev`` the state entering the chunk:

    Y = (L o C B^T) X~ + exp(c) * (C S_prev^T) + D X,    L_ts = exp(c_t - c_s), s <= t
    S_new = exp(c_end) * S_prev + sum_s exp(c_end - c_s) * x~_s (x) B_s

Only differences of ``c`` are exponentiated (each is <= 0), never a ratio of
two exponentials, so a fast-decaying head underflows to 0 and nothing
overflows. Left to XLA the decay matrices ``L`` (heads x Q x Q float32 a
chunk) are materialised in HBM and the fusions are anonymous in a trace. Here
a chunk is one grid step of a Pallas kernel (``ssd_chunk_fwd``): the chunks
of a sequence run in order and the state is carried in VMEM in float32
(``[N, heads * P]``, the heads along the lanes), so no separate pass carries
it; the states entering later chunks are written out for the backward, and
the largest ``|S|`` is kept beside them as the kernel goes (``ssm_stats``). The
backward (``ssd_chunk_bwd``) walks the chunks in reverse with the state's
gradient in VMEM, recomputes ``L`` and the products from the inputs and the
saved states, and works in the transposed frame (``L^T``, ``B C^T``) so that
every matmul is plain or contracts the lanes of both operands.

Layout. ``x`` is ``[batch, seq, heads * P]``; a grid step takes ``hb`` heads
of it. ``P = 64`` is half a lane tile, so the heads are worked in lane groups
of ``128 // P``: one head's product is a full-width matmul on the group's
lanes with the other heads' lanes zeroed. The per-head vectors ``dt`` and
``c`` come in twice, tokens along the rows ``[batch, heads / hb, seq, hb]``
and along the lanes ``[batch, heads / hb, hb, seq]``, and ``B`` and ``C`` come
with their transposes, all laid out by XLA (a few MB), so the kernels
transpose nothing. ``c`` and its gradient cross the kernel boundary: the
running sum and ``a = dt * A`` are XLA's, forward and backward.

Precision: operands of a matmul are in ``x.dtype`` (bf16 in training) with
float32 accumulation; ``dt``, ``c``, every exponential and the carried state
are float32, and a state that is a matmul's operand goes in as the sum of two
bf16 parts (16 bits of it), so the carry adds no rounding of its own.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .registry import registry

LANES = 128
SUBLANES = 8
HEADS_PER_BLOCK = 32     # heads a grid step; docs/kernel_dispatch.md has the sweep


def ssd_reference(x, dt, A, B, C, D, with_state_absmax: bool = False,
                  stat_every: int = 1):
    """``x [b, s, H, P]``, ``dt [b, s, H]`` (after softplus), ``A [H]``
    (negative), ``B``, ``C`` ``[b, s, N]``, ``D [H]`` -> ``y [b, s, H, P]`` in
    ``x.dtype``: the recurrence, one token after another, float32 inside.
    ``with_state_absmax``: also the largest ``|S|`` left by the tokens that
    end a run of ``stat_every`` (every token by default) or the sequence."""
    f32 = jnp.float32
    A, D = A.astype(f32), D.astype(f32)

    def step(carry, inp):
        S, top = carry
        xt, dtt, Bt, Ct, counts = inp
        S = (jnp.exp(dtt * A)[:, :, None, None] * S
             + (dtt[:, :, None] * xt)[..., None] * Bt[:, None, None, :])
        y = jnp.einsum("bhpn,bn->bhp", S, Ct,
                       precision=jax.lax.Precision.HIGHEST) + D[:, None] * xt
        return (S, jnp.where(counts, jnp.maximum(top, jnp.max(jnp.abs(S))), top)), y

    b, s, H, P = x.shape
    counts = ((jnp.arange(s) + 1) % stat_every == 0).at[s - 1].set(True)
    time_major = [jnp.moveaxis(a.astype(f32), 1, 0) for a in (x, dt, B, C)] + [counts]
    init = (jnp.zeros((b, H, P, B.shape[-1]), f32), jnp.zeros((), f32))
    (_, top), y = jax.lax.scan(step, init, time_major)
    y = jnp.moveaxis(y, 0, 1).astype(x.dtype)
    return (y, jax.lax.stop_gradient(top)) if with_state_absmax else y


def _split(s, dtype):
    """A float32 ``s`` as matmul operands: itself, or its two bf16 parts."""
    if dtype == jnp.float32:
        return (s, )
    hi = s.astype(dtype)
    return hi, (s - hi.astype(jnp.float32)).astype(dtype)


def _dot(a, b, dims=((1, ), (0, ))):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1, ), (1, ))     # contract the lanes of both operands


def _lane_groups(head: int, hb: int):
    """(heads a lane group, its lanes, groups a block)."""
    hp = max(1, LANES // head)
    hp = hp if hb % hp == 0 else 1
    return hp, hp * head, hb // hp


def _per_lane(ref, j, hp, which, rows, width):
    """Heads ``j * hp ..`` of a ``[1, 1, rows, hb]`` ref, each head's column
    spread over its own lanes of a ``[rows, width]`` array."""
    out = jnp.broadcast_to(ref[0, 0, :, j * hp:j * hp + 1], (rows, width))
    for i in range(1, hp):
        out = jnp.where(which == i, ref[0, 0, :, j * hp + i:j * hp + i + 1], out)
    return out


def _fwd_kernel(x_ref, dt_ref, cr_ref, cc_ref, bt_ref, c_ref, d_ref,
                y_ref, st_ref, top_ref, state, *, head, hb):
    f32, mm = jnp.float32, x_ref.dtype
    Q = x_ref.shape[1]
    hp, W, groups = _lane_groups(head, hb)

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)
        top_ref[...] = jnp.zeros_like(top_ref)

    Bt, Cm = bt_ref[0], c_ref[0]
    G = _dot(Cm, Bt)                                            # [t, s]
    causal = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1))
    which = jax.lax.broadcasted_iota(jnp.int32, (Q, W), 1) // head
    for j in range(groups):
        sl = slice(j * W, (j + 1) * W)
        xs = x_ref[0, :, sl].astype(f32)
        dtw = _per_lane(dt_ref, j, hp, which, Q, W)
        cw = _per_lane(cr_ref, j, hp, which, Q, W)
        xt = xs * dtw
        prev = state[:, sl]                                     # [N, W]
        y = jnp.exp(cw) * sum(_dot(Cm, part) for part in _split(prev, mm))
        for i in range(hp):
            h = j * hp + i
            decay = jnp.where(causal, jnp.exp(cr_ref[0, 0, :, h:h + 1]
                                              - cc_ref[0, 0, h:h + 1, :]), 0.0)
            xm = xt if hp == 1 else jnp.where(which == i, xt, 0.0)
            y = y + _dot((decay * G).astype(mm), xm.astype(mm))
        y_ref[0, :, sl] = (y + d_ref[:, sl] * xs).astype(y_ref.dtype)
        cend = cw[Q - 1:Q, :]
        new = jnp.exp(cend) * prev + _dot(Bt, (xt * jnp.exp(cend - cw)).astype(mm))
        state[:, sl] = new
        st_ref[0, 0, :, sl] = new
        # the largest |S| so far, eight rows a lane: the state is in VMEM
        # here, so the statistic costs no pass over the saved states
        size = jnp.abs(new)
        top = top_ref[0, 0, :, sl]
        for r in range(0, size.shape[0], SUBLANES):
            top = jnp.maximum(top, size[r:r + SUBLANES])
        top_ref[0, 0, :, sl] = top


def _bwd_kernel(x_ref, dt_ref, cr_ref, cc_ref, b_ref, bt_ref, c_ref, ct_ref, d_ref,
                dy_ref, st_ref, dx_ref, ddt_ref, dcr_ref, dcc_ref, db_ref, dct_ref,
                dd_ref, dstate, *, head, hb):
    f32, mm = jnp.float32, x_ref.dtype
    Q = x_ref.shape[1]
    hp, W, groups = _lane_groups(head, hb)
    step, steps = pl.program_id(2), pl.num_programs(2)
    first_chunk = step == steps - 1          # the chunks run in reverse

    @pl.when(step == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    Bm, Bt, Cm, Ct = b_ref[0], bt_ref[0], c_ref[0], ct_ref[0]
    GT = _dot(Bm, Ct)                                           # [s, t]
    keep = (jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
            >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0))
    which = jax.lax.broadcasted_iota(jnp.int32, (Q, W), 1) // head
    last_row = jax.lax.broadcasted_iota(jnp.int32, (Q, W), 0) == Q - 1
    head_col = jax.lax.broadcasted_iota(jnp.int32, (Q, hb), 1)
    dGT = jnp.zeros((Q, Q), f32)
    db = jnp.zeros(db_ref.shape[2:], f32)
    dct = jnp.zeros(dct_ref.shape[2:], f32)
    ddt = jnp.zeros((Q, hb), f32)
    dcr = jnp.zeros((Q, hb), f32)
    for j in range(groups):
        sl = slice(j * W, (j + 1) * W)
        xs, dys = x_ref[0, :, sl].astype(f32), dy_ref[0, :, sl].astype(f32)
        dtw = _per_lane(dt_ref, j, hp, which, Q, W)
        cw = _per_lane(cr_ref, j, hp, which, Q, W)
        xt = xs * dtw
        prev = jnp.where(first_chunk, 0.0, st_ref[0, 0, :, sl])     # [N, W]
        dS = dstate[:, sl]
        cend = cw[Q - 1:Q, :]
        to_end, grow = jnp.exp(cend - cw), jnp.exp(cend)
        from_prev = sum(_dot(Cm, part) for part in _split(prev, mm))     # C S_prev^T
        from_next = sum(_dot(Bm, part) for part in _split(dS, mm))       # B dS^T
        dys_scaled = jnp.exp(cw) * dys
        dxt = to_end * from_next
        # what the running sum c receives a row, the decay matrix aside
        via_end = xt * dxt
        r = dys_scaled * from_prev - via_end
        r = r + jnp.where(last_row, jnp.sum(via_end, axis=0, keepdims=True)
                          + grow * jnp.sum(dS * prev, axis=0, keepdims=True), 0.0)
        scaled = dys_scaled.astype(mm)
        dct = dct + sum(_dot(part, scaled, _NT) for part in _split(prev, mm))
        db = db + sum(_dot((xt * to_end).astype(mm), part, _NT)
                      for part in _split(dS, mm))
        dstate[:, sl] = grow * dS + _dot(Ct, scaled)
        for i in range(hp):
            h = j * hp + i
            mine = which == i
            decay_t = jnp.where(keep, jnp.exp(cc_ref[0, 0, h:h + 1, :]
                                              - cr_ref[0, 0, :, h:h + 1]), 0.0)
            dym = (dys if hp == 1 else jnp.where(mine, dys, 0.0)).astype(mm)
            d_decay = _dot(xt.astype(mm), dym, _NT) * decay_t       # [s, t]
            dGT = dGT + d_decay
            both = d_decay * GT
            dxt = dxt + _dot((decay_t * GT).astype(mm), dym)
            col = (jnp.sum(jnp.where(mine, r, 0.0), axis=1, keepdims=True)
                   - jnp.sum(both, axis=1, keepdims=True))
            dcr = jnp.where(head_col == h, col, dcr)
            dcc_ref[0, 0, h:h + 1, :] = jnp.sum(both, axis=0, keepdims=True)
        direct = dxt * xs
        for i in range(hp):
            ddt = jnp.where(head_col == j * hp + i,
                            jnp.sum(jnp.where(which == i, direct, 0.0), axis=1,
                                    keepdims=True), ddt)
        dx_ref[0, :, sl] = (dtw * dxt + d_ref[:, sl] * dys).astype(dx_ref.dtype)
        dd_ref[0, 0:1, sl] += jnp.sum(dys * xs, axis=0, keepdims=True)
    db_ref[0, 0] = db + _dot(dGT.astype(mm), Cm)
    dct_ref[0, 0] = dct + _dot(Bt, dGT.astype(mm))
    ddt_ref[0, 0] = ddt
    dcr_ref[0, 0] = dcr


def _heads_per_block(heads: int) -> int:
    hb = min(HEADS_PER_BLOCK, heads)
    while heads % hb:
        hb -= 1
    return hb


def _compiler_params(chunk: int, width: int, state: int, arrays: int, itemsize: int):
    # double-buffered [chunk, width] blocks, the states' float32 blocks and
    # scratch, two dozen [chunk, chunk] and [chunk, 128] float32 temporaries
    # (17 and 19 MiB forward and backward at 32 heads of 64 a step in bf16)
    from .kernel_dispatch import vmem_limit_bytes
    limit = vmem_limit_bytes(2 * arrays * chunk * width * itemsize
                             + 4 * 4 * state * width
                             + 24 * 4 * chunk * (chunk + LANES))
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=limit)


def _by_rows(a, hb):        # [b, s, H] -> [b, H / hb, s, hb]
    b, s, H = a.shape
    return a.reshape(b, s, H // hb, hb).transpose(0, 2, 1, 3)


def _by_lanes(a, hb):       # [b, s, H] -> [b, H / hb, hb, s]
    b, s, H = a.shape
    return a.reshape(b, s, H // hb, hb).transpose(0, 2, 3, 1)


def _specs(chunk, hb, head, N, order):
    """The BlockSpecs both kernels share; ``order(k)`` is the chunk a grid
    step works on."""
    W = hb * head
    return {
        "x": pl.BlockSpec((1, chunk, W), lambda b, g, k: (b, order(k), g)),
        "rows": pl.BlockSpec((1, 1, chunk, hb), lambda b, g, k: (b, g, order(k), 0)),
        "lanes": pl.BlockSpec((1, 1, hb, chunk), lambda b, g, k: (b, g, 0, order(k))),
        "bc": pl.BlockSpec((1, chunk, N), lambda b, g, k: (b, order(k), 0)),
        "bct": pl.BlockSpec((1, N, chunk), lambda b, g, k: (b, 0, order(k))),
        "d": pl.BlockSpec((1, W), lambda b, g, k: (0, g)),
    }


def _fwd_call(x, dt, c, B, C, D, chunk, interpret):
    b, s, HP = x.shape
    H, N = dt.shape[-1], B.shape[-1]
    head = HP // H
    hb = _heads_per_block(H)
    groups, nc, W = H // hb, s // chunk, hb * head
    sp = _specs(chunk, hb, head, N, lambda k: k)
    d_lanes = jnp.repeat(D.astype(jnp.float32), head)[None]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, head=head, hb=hb),
        grid=(b, groups, nc),
        in_specs=[sp["x"], sp["rows"], sp["rows"], sp["lanes"], sp["bct"], sp["bc"],
                  sp["d"]],
        out_specs=[sp["x"],
                   pl.BlockSpec((1, 1, N, W), lambda b, g, k: (b, k, 0, g)),
                   # one block a (batch, head block), held over its chunks
                   pl.BlockSpec((1, 1, SUBLANES, W), lambda b, g, k: (b, g, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, nc, N, HP), jnp.float32),
                   jax.ShapeDtypeStruct((b, groups, SUBLANES, W), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, W), jnp.float32)],
        compiler_params=_compiler_params(chunk, W, N, 2, x.dtype.itemsize),
        interpret=interpret,
        name="ssd_chunk_fwd",
    )(x, _by_rows(dt, hb), _by_rows(c, hb), _by_lanes(c, hb), B.swapaxes(1, 2), C,
      d_lanes)


def _bwd_call(x, dt, c, B, C, D, states, dy, chunk, interpret):
    b, s, HP = x.shape
    H, N = dt.shape[-1], B.shape[-1]
    head = HP // H
    hb = _heads_per_block(H)
    groups, nc, W = H // hb, s // chunk, hb * head
    sp = _specs(chunk, hb, head, N, lambda k: nc - 1 - k)
    f32 = jnp.float32
    d_lanes = jnp.repeat(D.astype(f32), head)[None]
    part = lambda *shape: jax.ShapeDtypeStruct((b, groups) + shape, f32)   # noqa: E731
    dx, ddt, dcr, dcc, db, dct, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, head=head, hb=hb),
        grid=(b, groups, nc),
        in_specs=[sp["x"], sp["rows"], sp["rows"], sp["lanes"], sp["bc"], sp["bct"],
                  sp["bc"], sp["bct"], sp["d"], sp["x"],
                  # the state ENTERING the chunk is the one the chunk before
                  # it wrote; chunk 0 reads a block it does not use
                  pl.BlockSpec((1, 1, N, W), lambda b, g, k: (
                      b, jnp.maximum(nc - 2 - k, 0), 0, g))],
        out_specs=[sp["x"], sp["rows"], sp["rows"], sp["lanes"],
                   pl.BlockSpec((1, 1, chunk, N), lambda b, g, k: (b, g, nc - 1 - k, 0)),
                   pl.BlockSpec((1, 1, N, chunk), lambda b, g, k: (b, g, 0, nc - 1 - k)),
                   pl.BlockSpec((1, 8, W), lambda b, g, k: (b, 0, g))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), part(s, hb), part(s, hb),
                   part(hb, s), part(s, N), part(N, s),
                   jax.ShapeDtypeStruct((b, 8, HP), f32)],
        scratch_shapes=[pltpu.VMEM((N, W), f32)],
        compiler_params=_compiler_params(chunk, W, N, 3, x.dtype.itemsize),
        interpret=interpret,
        name="ssd_chunk_bwd",
    )(x, _by_rows(dt, hb), _by_rows(c, hb), _by_lanes(c, hb), B, B.swapaxes(1, 2),
      C, C.swapaxes(1, 2), d_lanes, dy.astype(x.dtype), states)

    def heads_last(a):      # [b, H / hb, s, hb] -> [b, s, H]
        return a.transpose(0, 2, 1, 3).reshape(b, s, H)

    dc = heads_last(dcr) + heads_last(dcc.swapaxes(2, 3))
    return (dx, heads_last(ddt), dc, db.sum(axis=1).astype(B.dtype),
            dct.sum(axis=1).swapaxes(1, 2).astype(C.dtype),
            dd.sum(axis=(0, 1)).reshape(H, head).sum(axis=1).astype(D.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _ssd_chunks(x, dt, c, B, C, D, chunk, interpret):
    y, _, tops = _fwd_call(x, dt, c, B, C, D, chunk, interpret)
    return y, tops


def _ssd_vjp_fwd(x, dt, c, B, C, D, chunk, interpret):
    y, states, tops = _fwd_call(x, dt, c, B, C, D, chunk, interpret)
    return (y, tops), (x, dt, c, B, C, D, states)


def _ssd_vjp_bwd(chunk, interpret, res, cotangents):
    return _bwd_call(*res, cotangents[0], chunk, interpret)


_ssd_chunks.defvjp(_ssd_vjp_fwd, _ssd_vjp_bwd)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _ssd_jit(x, dt, c, B, C, D, chunk, interpret):
    # a frame of its own in the name stack, as for the short convolution: the
    # kernels keep their names (``%ssd_chunk_fwd*``, ``%ssd_chunk_bwd*``)
    return _ssd_chunks(x, dt, c, B, C, D, chunk, interpret)


def _check(x, dt, A, B, C, D):
    b, s, H, _ = x.shape
    if (dt.shape != (b, s, H) or A.shape != (H, ) or D.shape != (H, )
            or B.shape != C.shape or B.shape[:2] != (b, s) or B.ndim != 3):
        raise ValueError(f"ssd_scan: x {x.shape}, dt {dt.shape}, A {A.shape}, "
                         f"B {B.shape}, C {C.shape}, D {D.shape}: want x [b, s, H, "
                         "P], dt [b, s, H], A and D [H], B and C [b, s, N] (one group)")


def ssd_scan(x, dt, A, B, C, D, chunk: int, *, use_kernel: bool,
             interpret: bool = False, with_state_absmax: bool = False):
    """``y`` of the recurrence above: ``x [b, s, H, P]``, ``dt [b, s, H]``
    (positive: after softplus), ``A [H]`` (negative), ``B``, ``C`` ``[b, s,
    N]``, ``D [H]`` -> ``[b, s, H, P]`` in ``x.dtype``. ``use_kernel``: the
    Pallas kernels in chunks of ``chunk`` (forward and hand-written backward)
    instead of the recurrence; the caller decides, as for flash attention (a
    raw ``pallas_call`` is not partitioned over a mesh of more than one
    device). A sequence that ``chunk`` does not divide is padded with ``dt =
    0``: no decay, nothing added. ``with_state_absmax``: also the largest
    ``|S|`` at the chunks' ends (the states the kernels keep), no gradient."""
    _check(x, dt, A, B, C, D)
    if not (use_kernel or interpret):
        return ssd_reference(x, dt, A, B, C, D, with_state_absmax, stat_every=chunk)
    b, s, H, P = x.shape
    pad = -s % chunk
    f32 = jnp.float32
    seq = lambda a: jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0), ) * (a.ndim - 2))  # noqa: E731
    dtp = seq(dt.astype(f32))
    a = (dtp * A.astype(f32)).reshape(b, -1, chunk, H)
    c = jnp.cumsum(a, axis=2).reshape(b, s + pad, H)
    y, tops = _ssd_jit(seq(x.reshape(b, s, H * P)), dtp, c, seq(B.astype(x.dtype)),
                       seq(C.astype(x.dtype)), D, chunk, interpret)
    y = y[:, :s].reshape(b, s, H, P)
    if with_state_absmax:
        return y, jax.lax.stop_gradient(jnp.max(tops))
    return y


registry.register("ssd", "pallas", True,
                  "Mamba-2 chunked state-space scan, forward and backward")
