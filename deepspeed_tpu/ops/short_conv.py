"""Short depthwise causal convolutions: LFM2's gated ``conv`` operator
between its projections, and (at the end of the file) the ungated, biased,
SiLU form Mamba-2 runs before its scan.

``in_proj`` gives three ``C``-wide parts a token, ``B``, ``C`` and ``u``
(split in that order); the operator is

    v = B * u
    c[t] = sum_j w[j] * v[t - (L - 1) + j]      (depthwise, causal, L taps,
                                                 zeros left of the sequence)
    y = C * c

with no activation and no bias: three elementwise passes and an ``L``-tap
filter along the sequence, memory-bound (2 FLOP a byte). Left to XLA the
depthwise convolution and the two gates are separate fusions, anonymous in a
device trace. Here they are one Pallas kernel forward (``short_conv_fwd``:
read ``[tokens, 3C]``, write ``[tokens, C]``, 8 bytes a channel and token in
bf16) and one backward (``short_conv_bwd``: read ``[tokens, 3C]`` and
``dy``, write ``d[tokens, 3C]`` and the taps' gradient, 14 bytes), each a
single pass over HBM. The backward recomputes ``v`` and ``c`` from its inputs
and saves nothing but them:

    dC = dy * c         dc = dy * C
    dv[t] = sum_j w[j] * dc[t + (L - 1) - j]     (the filter reversed)
    dB = dv * u         du = dv * B
    dw[j] = sum_t dc[t] * v[t - (L - 1) + j]

A block of rows needs the ``L - 1`` rows before it (and, backward, after
it): a second view of the same array, one 16-row tile wide, brings them, so
no shifted copy crosses HBM. :func:`short_conv_reference` is the same
arithmetic in ``jax.numpy``: the kernels' oracle, and what runs where there
is no TPU (its gradient is autodiff's).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .registry import registry

HALO = 16            # rows of the neighbouring block a block can see: one bf16 tile
TAP_ROWS = 8         # the taps and their gradient travel as one float32 tile
BLOCK_ROWS = 256     # rows a grid step; 3 MB of bf16 a [256, 3 * 2048] block
BLOCK_COLS = 512     # channels worked on at a time inside a grid step


def short_conv_reference(bcx, w):
    """``bcx`` ``[batch, seq, 3C]`` (``B | C | u``), ``w`` ``[L, C]`` ->
    ``[batch, seq, C]`` in ``bcx.dtype``; float32 inside, as the kernel."""
    gate_b, gate_c, u = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    taps, seq = w.shape[0], bcx.shape[1]
    v = jnp.pad(gate_b * u, ((0, 0), (taps - 1, 0), (0, 0)))
    c = sum(w[j].astype(jnp.float32) * v[:, j:j + seq] for j in range(taps))
    return (gate_c * c).astype(bcx.dtype)


def _shifted(block, before, shift):
    """Row ``t`` of the result is row ``t - shift`` of ``before ++ block``
    (``shift >= 0``; ``before`` is the HALO rows that precede ``block``)."""
    if shift == 0:
        return block
    both = jnp.concatenate([before, block], axis=0)
    return pltpu.roll(both, shift, 0)[HALO:]


def _ahead(block, after, shift):
    """Row ``t`` of the result is row ``t + shift`` of ``block ++ after``."""
    if shift == 0:
        return block
    both = jnp.concatenate([block, after], axis=0)
    return pltpu.roll(both, both.shape[0] - shift, 0)[:block.shape[0]]


def _parts(ref, c0, cols, width):
    """The ``B``, ``C`` and ``u`` columns ``[c0, c0 + cols)`` of a
    ``[1, rows, 3 * width]`` block, in float32."""
    return [ref[0, :, p * width + c0:p * width + c0 + cols].astype(jnp.float32)
            for p in range(3)]


def _fwd_kernel(x_ref, prev_ref, w_ref, o_ref, *, taps, cols):
    width = o_ref.shape[2]
    first = pl.program_id(1) == 0
    for c0 in range(0, width, cols):
        gate_b, gate_c, u = _parts(x_ref, c0, cols, width)
        pb, _, pu = _parts(prev_ref, c0, cols, width)
        v, pv = gate_b * u, jnp.where(first, 0.0, pb * pu)
        c = sum(w_ref[j:j + 1, c0:c0 + cols] * _shifted(v, pv, taps - 1 - j)
                for j in range(taps))
        o_ref[0, :, c0:c0 + cols] = (gate_c * c).astype(o_ref.dtype)


def _bwd_kernel(x_ref, prev_ref, next_ref, dy_ref, dy_next_ref, w_ref,
                dx_ref, dw_ref, *, taps, cols):
    width = dy_ref.shape[2]
    first = pl.program_id(1) == 0
    last = pl.program_id(1) == pl.num_programs(1) - 1
    tap_row = jax.lax.broadcasted_iota(jnp.int32, (TAP_ROWS, cols), 0)
    for c0 in range(0, width, cols):
        sl = slice(c0, c0 + cols)
        gate_b, gate_c, u = _parts(x_ref, c0, cols, width)
        pb, _, pu = _parts(prev_ref, c0, cols, width)
        nc = next_ref[0, :, width + c0:width + c0 + cols].astype(jnp.float32)
        dy = dy_ref[0, :, sl].astype(jnp.float32)
        v, pv = gate_b * u, jnp.where(first, 0.0, pb * pu)
        dc = dy * gate_c
        ndc = jnp.where(last, 0.0, dy_next_ref[0, :, sl].astype(jnp.float32) * nc)
        c, dv, dw = 0.0, 0.0, jnp.zeros((TAP_ROWS, cols), jnp.float32)
        for j in range(taps):
            wj = w_ref[j:j + 1, sl]
            vj = _shifted(v, pv, taps - 1 - j)
            c = c + wj * vj
            dv = dv + wj * _ahead(dc, ndc, taps - 1 - j)
            dw = jnp.where(tap_row == j,
                           jnp.sum(dc * vj, axis=0, keepdims=True), dw)
        dx_ref[0, :, sl] = (dv * u).astype(dx_ref.dtype)
        dx_ref[0, :, width + c0:width + c0 + cols] = (dy * c).astype(dx_ref.dtype)
        dx_ref[0, :, 2 * width + c0:2 * width + c0 + cols] = \
            (dv * gate_b).astype(dx_ref.dtype)
        dw_ref[0, :, sl] = dw


def _blocking(seq: int, width: int):
    """(rows a block, padded sequence, channels a pass)."""
    rows = min(BLOCK_ROWS, -(-seq // HALO) * HALO)
    cols = BLOCK_COLS if width % BLOCK_COLS == 0 else width
    return rows, -(-seq // rows) * rows, cols


def _compiler_params(rows: int, width: int, arrays: int):
    # double-buffered bf16 blocks of `arrays` times [rows, width] in all,
    # and some twenty [rows + HALO, cols] float32 temporaries
    from .kernel_dispatch import vmem_limit_bytes
    limit = vmem_limit_bytes(2 * 2 * rows * width * arrays
                             + 20 * 4 * (rows + HALO) * min(width, BLOCK_COLS))
    return None if limit is None else pltpu.CompilerParams(vmem_limit_bytes=limit)


def _pad_taps(w):
    return jnp.pad(w.astype(jnp.float32), ((0, TAP_ROWS - w.shape[0]), (0, 0)))


def _check(bcx, w):
    if bcx.ndim != 3 or bcx.shape[-1] != 3 * w.shape[1]:
        raise ValueError(f"short_conv: bcx {bcx.shape} is not [batch, seq, 3C] "
                         f"for taps {w.shape}")
    if not 1 <= w.shape[0] <= TAP_ROWS:
        raise ValueError(f"short_conv: {w.shape[0]} taps; the kernel takes 1 "
                         f"to {TAP_ROWS}")


def _fwd_call(bcx, w, interpret):
    _check(bcx, w)
    batch, seq, _ = bcx.shape
    taps, width = w.shape
    rows, padded, cols = _blocking(seq, width)
    x = jnp.pad(bcx, ((0, 0), (0, padded - seq), (0, 0)))
    per = rows // HALO
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, taps=taps, cols=cols),
        grid=(batch, padded // rows),
        in_specs=[
            pl.BlockSpec((1, rows, 3 * width), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, HALO, 3 * width),
                         lambda b, i: (b, jnp.maximum(i * per - 1, 0), 0)),
            pl.BlockSpec((TAP_ROWS, width), lambda b, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, width), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, padded, width), bcx.dtype),
        compiler_params=_compiler_params(rows, width, 4),
        interpret=interpret,
        name="short_conv_fwd",
    )(x, x, _pad_taps(w))
    return out[:, :seq]


def _bwd_call(bcx, w, dy, interpret):
    batch, seq, _ = bcx.shape
    taps, width = w.shape
    rows, padded, cols = _blocking(seq, width)
    pad = ((0, 0), (0, padded - seq), (0, 0))
    x, g = jnp.pad(bcx, pad), jnp.pad(dy.astype(bcx.dtype), pad)
    per, blocks = rows // HALO, padded // rows
    tiles = padded // HALO

    def ahead(b, i):
        return b, jnp.minimum((i + 1) * per, tiles - 1), 0

    dx, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, taps=taps, cols=cols),
        grid=(batch, blocks),
        in_specs=[
            pl.BlockSpec((1, rows, 3 * width), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, HALO, 3 * width),
                         lambda b, i: (b, jnp.maximum(i * per - 1, 0), 0)),
            pl.BlockSpec((1, HALO, 3 * width), ahead),
            pl.BlockSpec((1, rows, width), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, HALO, width), ahead),
            pl.BlockSpec((TAP_ROWS, width), lambda b, i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, rows, 3 * width), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, TAP_ROWS, width),
                         lambda b, i: (b * blocks + i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, padded, 3 * width), bcx.dtype),
            jax.ShapeDtypeStruct((batch * blocks, TAP_ROWS, width), jnp.float32),
        ],
        compiler_params=_compiler_params(rows, width, 7),
        interpret=interpret,
        name="short_conv_bwd",
    )(x, x, x, g, g, _pad_taps(w))
    return dx[:, :seq], jnp.sum(dw, axis=0)[:taps].astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, ))
def _short_conv_kernel(bcx, w, interpret):
    return _fwd_call(bcx, w, interpret)


def _short_conv_vjp_fwd(bcx, w, interpret):
    return _fwd_call(bcx, w, interpret), (bcx, w)


def _short_conv_vjp_bwd(interpret, res, dy):
    return _bwd_call(*res, dy, interpret)


_short_conv_kernel.defvjp(_short_conv_vjp_fwd, _short_conv_vjp_bwd)


@functools.partial(jax.jit, static_argnames=("interpret", ))
def _short_conv_jit(bcx, w, interpret):
    # a frame of its own in the name stack: under differentiation the
    # transforms wrap this frame's name, and the kernels keep theirs
    # (``%short_conv_fwd*``, ``%short_conv_bwd*`` in a device trace)
    return _short_conv_kernel(bcx, w, interpret)


def short_conv(bcx, w, *, use_kernel: bool, interpret: bool = False):
    """``y = C * conv_L(B * u)`` of ``bcx = B | C | u`` ``[batch, seq, 3C]``
    with taps ``w`` ``[L, C]``. ``use_kernel``: the Pallas kernels (forward
    and hand-written backward) instead of the ``jax.numpy`` form; the caller
    decides, as for flash attention (a raw ``pallas_call`` is not partitioned
    over a mesh of more than one device)."""
    if use_kernel or interpret:
        return _short_conv_jit(bcx, w, interpret)
    _check(bcx, w)
    return short_conv_reference(bcx, w)


registry.register("short_conv", "pallas", True,
                  "gated depthwise causal convolution, forward and backward")


# ---- the ungated form (Mamba-2's convolution before its scan) ----
#
#     y = silu(conv_L(x) + bias)          x [batch, seq, C], any C
#
# The same row blocks and halo views; kernels with names of their own
# (``causal_conv_fwd``, ``causal_conv_bwd``): they move 2 and 3 values a
# channel and token where the gated ones move 4 and 7, and a trace reader
# counts bytes by the name. The bias travels as row ``L`` of the taps' tile,
# its gradient as row ``L`` of the taps' gradient. The backward recomputes
# the pre-activation of its own rows and of the HALO rows after them (the
# filter reversed reaches ``L - 1`` rows ahead) and saves nothing.


def causal_conv_reference(x, w, bias):
    """``x`` ``[batch, seq, C]``, ``w`` ``[L, C]``, ``bias`` ``[C]`` ->
    ``silu(conv(x) + bias)`` in ``x.dtype``; float32 inside, as the kernel."""
    taps, seq = w.shape[0], x.shape[1]
    v = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    c = bias.astype(jnp.float32) + sum(w[j].astype(jnp.float32) * v[:, j:j + seq]
                                       for j in range(taps))
    return (c * jax.nn.sigmoid(c)).astype(x.dtype)


def _pre_activation(x, before, w_ref, sl, taps):
    """``bias + sum_j w[j] * x[t - (L - 1) + j]`` of a block, and the shifted
    views it summed (what the taps' gradient multiplies)."""
    views = [_shifted(x, before, taps - 1 - j) for j in range(taps)]
    return (w_ref[taps:taps + 1, sl]
            + sum(w_ref[j:j + 1, sl] * views[j] for j in range(taps))), views


def _conv_fwd_kernel(x_ref, prev_ref, w_ref, o_ref, *, taps, cols):
    first = pl.program_id(1) == 0
    for c0 in range(0, o_ref.shape[2], cols):
        sl = slice(c0, c0 + cols)
        before = jnp.where(first, 0.0, prev_ref[0, :, sl].astype(jnp.float32))
        c, _ = _pre_activation(x_ref[0, :, sl].astype(jnp.float32), before, w_ref,
                               sl, taps)
        o_ref[0, :, sl] = (c * jax.nn.sigmoid(c)).astype(o_ref.dtype)


def _silu_grad(c):
    s = jax.nn.sigmoid(c)
    return s * (1.0 + c * (1.0 - s))


def _conv_bwd_kernel(x_ref, prev_ref, next_ref, dy_ref, dy_next_ref, w_ref,
                     dx_ref, dw_ref, *, taps, cols):
    rows = dy_ref.shape[1]
    first = pl.program_id(1) == 0
    last = pl.program_id(1) == pl.num_programs(1) - 1
    tap_row = jax.lax.broadcasted_iota(jnp.int32, (TAP_ROWS, cols), 0)
    for c0 in range(0, dy_ref.shape[2], cols):
        sl = slice(c0, c0 + cols)
        x = x_ref[0, :, sl].astype(jnp.float32)
        before = jnp.where(first, 0.0, prev_ref[0, :, sl].astype(jnp.float32))
        c, views = _pre_activation(x, before, w_ref, sl, taps)
        after, _ = _pre_activation(next_ref[0, :, sl].astype(jnp.float32),
                                   x[rows - HALO:], w_ref, sl, taps)
        dc = dy_ref[0, :, sl].astype(jnp.float32) * _silu_grad(c)
        ndc = jnp.where(last, 0.0, dy_next_ref[0, :, sl].astype(jnp.float32)
                        * _silu_grad(after))
        dx = sum(w_ref[j:j + 1, sl] * _ahead(dc, ndc, taps - 1 - j)
                 for j in range(taps))
        dw = jnp.where(tap_row == taps, jnp.sum(dc, axis=0, keepdims=True), 0.0)
        for j in range(taps):
            dw = jnp.where(tap_row == j,
                           jnp.sum(dc * views[j], axis=0, keepdims=True), dw)
        dx_ref[0, :, sl] = dx.astype(dx_ref.dtype)
        dw_ref[0, :, sl] = dw


def _conv_blocking(seq: int, width: int):
    """(rows a block, padded sequence, channels a pass): ``_blocking`` with a
    pass that divides any width of whole lane tiles."""
    rows, padded, _ = _blocking(seq, width)
    cols = next((c for c in range(BLOCK_COLS, 0, -128) if width % c == 0), width)
    return rows, padded, cols


def _conv_check(x, w, bias):
    if x.ndim != 3 or x.shape[-1] != w.shape[1] or bias.shape != w.shape[1:]:
        raise ValueError(f"causal_conv: x {x.shape} is not [batch, seq, C] for "
                         f"taps {w.shape} and bias {bias.shape}")
    if not 1 <= w.shape[0] < TAP_ROWS:
        raise ValueError(f"causal_conv: {w.shape[0]} taps; the kernel takes 1 "
                         f"to {TAP_ROWS - 1} and a bias")


def _taps_and_bias(w, bias):
    return _pad_taps(jnp.concatenate([w.astype(jnp.float32),
                                      bias.astype(jnp.float32)[None]]))


def _conv_fwd_call(x, w, bias, interpret):
    _conv_check(x, w, bias)
    batch, seq, width = x.shape
    taps = w.shape[0]
    rows, padded, cols = _conv_blocking(seq, width)
    xp = jnp.pad(x, ((0, 0), (0, padded - seq), (0, 0)))
    per = rows // HALO
    out = pl.pallas_call(
        functools.partial(_conv_fwd_kernel, taps=taps, cols=cols),
        grid=(batch, padded // rows),
        in_specs=[
            pl.BlockSpec((1, rows, width), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, HALO, width),
                         lambda b, i: (b, jnp.maximum(i * per - 1, 0), 0)),
            pl.BlockSpec((TAP_ROWS, width), lambda b, i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, rows, width), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, padded, width), x.dtype),
        compiler_params=_compiler_params(rows, width, 2),
        interpret=interpret,
        name="causal_conv_fwd",
    )(xp, xp, _taps_and_bias(w, bias))
    return out[:, :seq]


def _conv_bwd_call(x, w, bias, dy, interpret):
    batch, seq, width = x.shape
    taps = w.shape[0]
    rows, padded, cols = _conv_blocking(seq, width)
    pad = ((0, 0), (0, padded - seq), (0, 0))
    xp, g = jnp.pad(x, pad), jnp.pad(dy.astype(x.dtype), pad)
    per, blocks = rows // HALO, padded // rows
    tiles = padded // HALO

    def ahead(b, i):
        return b, jnp.minimum((i + 1) * per, tiles - 1), 0

    block = pl.BlockSpec((1, rows, width), lambda b, i: (b, i, 0))
    dx, dw = pl.pallas_call(
        functools.partial(_conv_bwd_kernel, taps=taps, cols=cols),
        grid=(batch, blocks),
        in_specs=[
            block,
            pl.BlockSpec((1, HALO, width),
                         lambda b, i: (b, jnp.maximum(i * per - 1, 0), 0)),
            pl.BlockSpec((1, HALO, width), ahead),
            block,
            pl.BlockSpec((1, HALO, width), ahead),
            pl.BlockSpec((TAP_ROWS, width), lambda b, i: (0, 0)),
        ],
        out_specs=[
            block,
            pl.BlockSpec((1, TAP_ROWS, width),
                         lambda b, i: (b * blocks + i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((batch, padded, width), x.dtype),
            jax.ShapeDtypeStruct((batch * blocks, TAP_ROWS, width), jnp.float32),
        ],
        compiler_params=_compiler_params(rows, width, 3),
        interpret=interpret,
        name="causal_conv_bwd",
    )(xp, xp, xp, g, g, _taps_and_bias(w, bias))
    dw = jnp.sum(dw, axis=0)
    return dx[:, :seq], dw[:taps].astype(w.dtype), dw[taps].astype(bias.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, ))
def _causal_conv_kernel(x, w, bias, interpret):
    return _conv_fwd_call(x, w, bias, interpret)


def _causal_conv_vjp_fwd(x, w, bias, interpret):
    return _conv_fwd_call(x, w, bias, interpret), (x, w, bias)


def _causal_conv_vjp_bwd(interpret, res, dy):
    return _conv_bwd_call(*res, dy, interpret)


_causal_conv_kernel.defvjp(_causal_conv_vjp_fwd, _causal_conv_vjp_bwd)


@functools.partial(jax.jit, static_argnames=("interpret", ))
def _causal_conv_jit(x, w, bias, interpret):
    # a name-stack frame of its own, as ``_short_conv_jit``
    return _causal_conv_kernel(x, w, bias, interpret)


def causal_conv(x, w, bias, *, use_kernel: bool, interpret: bool = False):
    """``silu(conv_L(x) + bias)``: ``x`` ``[batch, seq, C]``, depthwise causal
    taps ``w`` ``[L, C]`` (``w[j]`` multiplies ``x[t - (L - 1) + j]``, zeros
    left of the sequence), ``bias`` ``[C]``. ``use_kernel`` as for
    :func:`short_conv`."""
    if use_kernel or interpret:
        return _causal_conv_jit(x, w, bias, interpret)
    _conv_check(x, w, bias)
    return causal_conv_reference(x, w, bias)


registry.register("causal_conv", "pallas", True,
                  "depthwise causal convolution with bias and SiLU, forward and backward")
