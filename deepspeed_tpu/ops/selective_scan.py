"""Mamba-1's selective scan (Gu & Dao, arXiv:2312.00752): a state-space
recurrence whose state decays by a rate of its own for every (channel, state)
pair.

Per channel ``e`` of ``E`` and state ``n`` of ``N``, with ``A[e, n] < 0``, the
step size ``dt_t[e] > 0`` and a state ``h`` of ``E x N`` in float32, zero before
the first token:

    h_t[e, n] = exp(dt_t[e] * A[e, n]) * h_{t-1}[e, n] + dt_t[e] * x_t[e] * B_t[n]
    y_t[e]    = sum_n h_t[e, n] * C_t[n] + D[e] * x_t[e]

:func:`selective_scan_reference` is that recurrence token by token in float32:
the kernels' oracle, and what runs where there is no TPU or the mesh has more
than one device. :func:`selective_scan` is the same function as two Pallas
kernels, ``selscan_fwd`` and ``selscan_bwd``. ``ops/ssd.py`` cannot take it: its
chunk algebra needs ONE decay a head and token (``L_ts = exp(c_t - c_s)``), here
``exp(dt_t A)`` is ``[E, N]`` a token, so there is no matmul form and the work
is the vector unit's.

Layout. The channels fill whole vector registers: ``x``, ``dt`` and ``y`` cross
the kernel boundary as ``[batch, seq, E / 128, 128]`` and a grid step takes
``TILE`` = 1,024 channels of ``BLOCK`` tokens, so one token's channels are one
float32 register ``[8, 128]`` and the ``N`` states beside them are ``N`` such
registers, carried through the block's tokens as a loop's values and from
block to block in VMEM. ``B_t[n]`` and ``C_t[n]`` are scalars a token and
state: they come through SMEM (``[C | B]``, ``2 N`` a token) and meet the
registers as splats, so the sum over ``n`` is ``N`` multiply-adds and no
reduction inside a register. The states ENTERING the blocks are written out
for the backward (``E x N`` float32 a block: 328 KB at 5,120 x 16) and the
largest ``|h|`` at the blocks' ends is kept as the kernel goes
(``selscan_stats``).

``selscan_bwd`` walks the blocks in reverse with the state's gradient carried
the same way: a block's forward is made again from its entering state, the
state BEFORE each token held in VMEM (``BLOCK x N`` registers: 8 MiB), then
the tokens are walked backwards. The gradients of ``B_t[n]`` and ``C_t[n]`` are
sums over ALL channels a token and state: a grid step reduces its 1,024
channels (the lanes of a register at a time, the results laid side by side on
one row a token) and XLA adds the ``E / 1,024`` rows. ``A``'s and ``D``'s
gradients are summed over the tokens in VMEM as the kernel goes.

Precision: ``dt``, ``exp(dt A)``, the state, its gradient and every product are
float32; ``x`` and ``y`` (and their gradients) cross HBM in ``x.dtype`` (bf16
in training), ``B`` and ``C`` as float32 scalars.

Under a layer's recomputation the output and the block states are named
(``SCAN_NAME``, a candidate of ``ops/remat.py``): where the plan keeps them the
forward kernel runs once a step, where it does not the recomputed layer runs it
again.
"""

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .registry import registry
from .remat import AGAIN, SELSCAN_SCAN as SCAN_NAME

LANES = 128
SUBLANES = 8
TILE = SUBLANES * LANES     # channels a grid step: one float32 register a state
BLOCK = 128                 # tokens a grid step; docs/kernel_dispatch.md


def selective_scan_reference(x, dt, A, B, C, D, with_state_absmax: bool = False,
                             stat_every: int = 1):
    """``x``, ``dt`` ``[b, s, E]``, ``A`` ``[E, N]``, ``B``, ``C`` ``[b, s, N]``,
    ``D`` ``[E]`` -> ``y [b, s, E]`` in ``x.dtype``: the recurrence, one token
    after another, float32 inside. ``with_state_absmax``: also the largest
    ``|h|`` left by the tokens that end a run of ``stat_every`` (every token by
    default) or the sequence."""
    f32 = jnp.float32
    b, s, E = x.shape
    A, D = A.astype(f32), D.astype(f32)

    def step(carry, inp):
        h, top = carry                                          # [b, E, N]
        xt, dtt, Bt, Ct, counts = inp
        h = jnp.exp(dtt[..., None] * A) * h + (dtt * xt)[..., None] * Bt[:, None, :]
        y = jnp.sum(h * Ct[:, None, :], axis=-1) + D * xt
        return (h, jnp.where(counts, jnp.maximum(top, jnp.max(jnp.abs(h))), top)), y

    counts = ((jnp.arange(s) + 1) % stat_every == 0).at[s - 1].set(True)
    time_major = [jnp.moveaxis(a.astype(f32), 1, 0) for a in (x, dt, B, C)]
    init = (jnp.zeros((b, E, A.shape[1]), f32), jnp.zeros((), f32))
    (_, top), y = jax.lax.scan(step, init, time_major + [counts])
    y = jnp.moveaxis(y, 0, 1).astype(x.dtype)
    return (y, jax.lax.stop_gradient(top)) if with_state_absmax else y


def _fwd_kernel(bc_ref, x_ref, d_ref, a_ref, skip_ref, y_ref, st_ref, top_ref, h_ref,
                *, n_state, block):
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)
        top_ref[...] = jnp.zeros_like(top_ref)

    st_ref[0, 0] = h_ref[...]
    skip = skip_ref[...]

    def token(i, hs):
        d, xv = d_ref[0, i], x_ref[0, i].astype(f32)
        dx, y, out = d * xv, skip * xv, []
        for n in range(n_state):
            h = jnp.exp(d * a_ref[n]) * hs[n] + dx * bc_ref[0, i, n_state + n]
            y = y + h * bc_ref[0, i, n]
            out.append(h)
        y_ref[0, i] = y.astype(y_ref.dtype)
        return tuple(out)

    hs = jax.lax.fori_loop(0, block, token, tuple(h_ref[n] for n in range(n_state)))
    top = top_ref[0, 0]
    for n in range(n_state):
        h_ref[n] = hs[n]
        top = jnp.maximum(top, jnp.abs(hs[n]))
    top_ref[0, 0] = top


def _bwd_kernel(bc_ref, x_ref, d_ref, a_ref, skip_ref, st_ref, dy_ref, dx_ref, dd_ref,
                da_ref, dskip_ref, dbc_ref, g_ref, hist_ref, *, n_state, block):
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dskip_ref[...] = jnp.zeros_like(dskip_ref)

    # the block's forward again from its entering state: the state BEFORE
    # each token is what the walk back reads
    def forward(i, hs):
        d = d_ref[0, i]
        dx, out = d * x_ref[0, i].astype(f32), []
        for n in range(n_state):
            hist_ref[i, n] = hs[n]
            out.append(jnp.exp(d * a_ref[n]) * hs[n] + dx * bc_ref[0, i, n_state + n])
        return tuple(out)

    jax.lax.fori_loop(0, block, forward, tuple(st_ref[0, 0, n] for n in range(n_state)))
    skip = skip_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 1)

    def token(j, gs):
        i = block - 1 - j
        d, xv, dy = d_ref[0, i], x_ref[0, i].astype(f32), dy_ref[0, i].astype(f32)
        dx = d * xv
        through_b = jnp.zeros_like(d)       # sum_n g_n B_n: what x and dt see of B
        dd = jnp.zeros_like(d)
        row = jnp.zeros_like(d)             # lanes [0, N): dC_n; [N, 2N): dB_n
        out = []
        for n in range(n_state):
            a, before = a_ref[n], hist_ref[i, n]
            decay = jnp.exp(d * a)
            b_n, c_n = bc_ref[0, i, n_state + n], bc_ref[0, i, n]
            h = decay * before + dx * b_n
            g = dy * c_n + gs[n]
            row = jnp.where(lane == n, jnp.sum(dy * h, axis=1, keepdims=True), row)
            row = jnp.where(lane == n_state + n,
                            jnp.sum(g * dx, axis=1, keepdims=True), row)
            through_b = through_b + g * b_n
            d_decay = g * before * decay
            dd = dd + d_decay * a
            da_ref[0, n] += d_decay * d
            out.append(decay * g)
        dx_ref[0, i] = (through_b * d + skip * dy).astype(dx_ref.dtype)
        dd_ref[0, i] = dd + through_b * xv
        dskip_ref[0] += dy * xv
        dbc_ref[0, 0, pl.ds(i, 1), :] = jnp.sum(row, axis=0, keepdims=True)
        return tuple(out)

    gs = jax.lax.fori_loop(0, block, token, tuple(g_ref[n] for n in range(n_state)))
    for n in range(n_state):
        g_ref[n] = gs[n]


def vmem_bytes(leg: str, n_state: int, block: int, itemsize: int) -> int:
    """Upper estimate of the VMEM one grid step holds, counted as
    ``kernel_dispatch.flash_vmem_bytes`` counts: the pipelined blocks twice,
    the carried state and, in the backward, the block's states."""
    tile = TILE * 4
    per_token = TILE * (itemsize + 4)           # x (or its gradient) and dt
    if leg == "fwd":
        blocks = block * (per_token + TILE * itemsize) + (2 * n_state + 2) * tile
        return 2 * blocks + n_state * tile
    blocks = (block * (2 * per_token + TILE * itemsize + LANES * 4)
              + (3 * n_state + 2) * tile)
    return 2 * blocks + (block + 1) * n_state * tile


def _compiler_params(leg, n_state, block, itemsize):
    from .kernel_dispatch import vmem_limit_bytes
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=vmem_limit_bytes(vmem_bytes(leg, n_state, block, itemsize)))


def _specs(n_state, block, order):
    """Block specs of one grid step ``(batch, channel tile, token block)``,
    the token blocks walked by ``order(t)``."""
    tokens = pl.BlockSpec((1, block, SUBLANES, LANES),
                          lambda b, e, t: (b, order(t), e, 0))
    scalars = pl.BlockSpec((1, block, 2 * n_state), lambda b, e, t: (b, order(t), 0),
                           memory_space=pltpu.SMEM)
    rates = pl.BlockSpec((n_state, SUBLANES, LANES), lambda b, e, t: (0, e, 0))
    skip = pl.BlockSpec((SUBLANES, LANES), lambda b, e, t: (e, 0))
    return tokens, scalars, rates, skip


def _fwd_call(x, dt, a, skip, bc, block, interpret):
    b, s, groups, _ = x.shape
    n_state, nb, tiles = a.shape[0], s // block, groups // SUBLANES
    tokens, scalars, rates, skips = _specs(n_state, block, lambda t: t)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, n_state=n_state, block=block),
        grid=(b, tiles, nb),
        in_specs=[scalars, tokens, tokens, rates, skips],
        out_specs=[tokens,
                   pl.BlockSpec((1, 1, n_state, SUBLANES, LANES),
                                lambda b, e, t: (b, t, 0, e, 0)),
                   # one block a (batch, channel tile), held over its tokens
                   pl.BlockSpec((1, 1, SUBLANES, LANES), lambda b, e, t: (b, e, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, nb, n_state, groups, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((b, tiles, SUBLANES, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n_state, SUBLANES, LANES), jnp.float32)],
        compiler_params=_compiler_params("fwd", n_state, block, x.dtype.itemsize),
        interpret=interpret,
        name="selscan_fwd",
    )(bc, x, dt, a, skip)


def _bwd_call(x, dt, a, skip, bc, states, dy, block, interpret):
    b, s, groups, _ = x.shape
    n_state, nb, tiles = a.shape[0], s // block, groups // SUBLANES
    back = lambda t: nb - 1 - t      # noqa: E731
    tokens, scalars, rates, skips = _specs(n_state, block, back)
    dx, dd, da, dskip, dbc = pl.pallas_call(
        functools.partial(_bwd_kernel, n_state=n_state, block=block),
        grid=(b, tiles, nb),
        in_specs=[scalars, tokens, tokens, rates, skips,
                  pl.BlockSpec((1, 1, n_state, SUBLANES, LANES),
                               lambda b, e, t: (b, back(t), 0, e, 0)), tokens],
        out_specs=[tokens, tokens,
                   # summed over a (batch, channel tile)'s tokens in VMEM
                   pl.BlockSpec((1, n_state, SUBLANES, LANES),
                                lambda b, e, t: (b, 0, e, 0)),
                   pl.BlockSpec((1, SUBLANES, LANES), lambda b, e, t: (b, e, 0)),
                   pl.BlockSpec((1, 1, block, LANES),
                                lambda b, e, t: (b, e, back(t), 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(dt.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, ) + a.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, ) + skip.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, tiles, s, LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n_state, SUBLANES, LANES), jnp.float32),
                        pltpu.VMEM((block, n_state, SUBLANES, LANES), jnp.float32)],
        compiler_params=_compiler_params("bwd", n_state, block, x.dtype.itemsize),
        interpret=interpret,
        name="selscan_bwd",
    )(bc, x, dt, a, skip, states, dy.astype(x.dtype))
    # the channel tiles' rows added up: [C's | B's] gradients a token
    dbc = jnp.sum(dbc, axis=1)[..., :2 * n_state]
    return dx, dd, jnp.sum(da, axis=0), jnp.sum(dskip, axis=0), dbc


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _scan(x, dt, a, skip, bc, block, interpret, keep):
    y, _, tops = _fwd_call(x, dt, a, skip, bc, block, interpret)
    return y, tops


def _scan_vjp_fwd(x, dt, a, skip, bc, block, interpret, keep):
    y, states, tops = _fwd_call(x, dt, a, skip, bc, block, interpret)
    # what the backward needs of the forward kernel, under the name a
    # recomputation may keep them by (its layer's plan said which)
    name = SCAN_NAME if keep else SCAN_NAME + AGAIN
    y, states = checkpoint_name(y, name), checkpoint_name(states, name)
    return (y, tops), (x, dt, a, skip, bc, states)


def _scan_vjp_bwd(block, interpret, keep, res, cotangents):
    return _bwd_call(*res, cotangents[0], block, interpret)


_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


@functools.partial(jax.jit, static_argnames=("block", "interpret", "keep"))
def _scan_jit(x, dt, a, skip, bc, block, interpret, keep):
    # a frame of its own in the name stack, as for the other scans: the
    # kernels keep their names (``%selscan_fwd*``, ``%selscan_bwd*``)
    return _scan(x, dt, a, skip, bc, block, interpret, keep)


def scan_bytes(batch: int, seq: int, channels: int, n_state: int, itemsize: int,
               block: int = BLOCK) -> int:
    """Bytes a layer keeps under ``SCAN_NAME``: the output and the float32
    states entering the blocks."""
    padded = seq + (-seq % block)
    wide = channels + (-channels % TILE)
    return batch * wide * (padded * itemsize + (padded // block) * n_state * 4)


def selective_scan(x, dt, A, B, C, D, *, use_kernel: bool, interpret: bool = False,
                   block: int = BLOCK, with_state_absmax: bool = False,
                   keep: bool = True):
    """``y`` of the recurrence above: ``x``, ``dt`` ``[b, s, E]``, ``A`` ``[E,
    N]`` (negative), ``B``, ``C`` ``[b, s, N]``, ``D`` ``[E]`` -> ``[b, s, E]``
    in ``x.dtype``. ``use_kernel``: the Pallas kernels in blocks of ``block``
    tokens (forward and hand-written backward) instead of the recurrence; the
    caller decides, as for flash attention (a raw ``pallas_call`` is not
    partitioned over a mesh of more than one device). A sequence that ``block``
    does not divide is padded with ``dt = 0`` (no decay, nothing written), the
    channels to whole tiles of ``TILE`` with zeros. ``with_state_absmax``:
    also the largest ``|h|`` at the blocks' ends (the states the kernels
    keep), no gradient. ``keep``: whether a recomputation may keep the
    kernel's output and states (``SCAN_NAME``)."""
    b, s, E = x.shape
    N = A.shape[-1]
    if (dt.shape != x.shape or A.shape != (E, N) or B.shape != (b, s, N)
            or C.shape != B.shape or D.shape != (E, )):
        raise ValueError(f"selective_scan: x {x.shape}, dt {dt.shape}, A {A.shape}, B "
                         f"{B.shape}, C {C.shape}, D {D.shape}: want x, dt [b, s, E], "
                         "A [E, N], B, C [b, s, N], D [E]")
    if not (use_kernel or interpret):
        return selective_scan_reference(x, dt, A, B, C, D, with_state_absmax,
                                        stat_every=block)
    if 2 * N > LANES or block % SUBLANES:
        raise ValueError(f"the selscan kernels want at most {LANES // 2} states and a "
                         f"block that is a multiple of {SUBLANES}: got {N}, {block}")
    f32 = jnp.float32
    pad_s, pad_e = -s % block, -E % TILE
    groups = (E + pad_e) // LANES

    def tiled(a, dtype):
        a = jnp.pad(a.astype(dtype), ((0, 0), (0, pad_s), (0, pad_e)))
        return a.reshape(b, s + pad_s, groups, LANES)

    with jax.named_scope("ds.selscan.dt"):
        rates = jnp.pad(A.astype(f32).T, ((0, 0), (0, pad_e))).reshape(N, groups, LANES)
        skip = jnp.pad(D.astype(f32), (0, pad_e)).reshape(groups, LANES)
        bc = jnp.pad(jnp.concatenate([C, B], axis=-1).astype(f32),
                     ((0, 0), (0, pad_s), (0, 0)))
        x4, d4 = tiled(x, x.dtype), tiled(dt, f32)
    y, tops = _scan_jit(x4, d4, rates, skip, bc, block, interpret, bool(keep))
    y = y.reshape(b, s + pad_s, groups * LANES)[:, :s, :E]
    if with_state_absmax:
        return y, jax.lax.stop_gradient(jnp.max(tops))
    return y


registry.register("selective_scan", "pallas", True,
                  "Mamba-1 selective scan, forward and backward")
