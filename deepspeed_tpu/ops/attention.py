"""Flash attention — Pallas TPU kernels (fwd AND bwd) with XLA fallback.

TPU-native replacement for the reference's fused attention kernels
(``csrc/transformer/inference/csrc/softmax.cu``, the fused training-kernel
attention in ``csrc/transformer/`` and the blocked flash paths in
``deepspeed/inference/v2/kernels/ragged_ops/blocked_flash``): blocked
online-softmax attention that never materializes the [S, S] score matrix —
in either direction.

Layout: GQA is native. Queries arrive ``[B, S, H, D]`` and K/V
``[B, S, KV, D]`` with ``H = KV * G``; tensors are regrouped to
``[B*KV, G, S, D]`` so one grid step contracts the ``G * block_q`` query
rows of a KV group against one K/V block — K/V are never expanded to query
heads (G× HBM saving), and the folded G dimension *fattens* the MXU matmul.

Forward (grid ``(B*KV, q_blocks, kv_blocks)``, kv innermost): accumulators
(o, m, l) persist in VMEM scratch across the kv sweep, m and l replicated
over the lanes; the log-sum-exp is written out as a residual. Backward: one
kernel (``flash_dkdv_dq``) sweeps q blocks per kv block on the TRANSPOSED
score tile (``k . q^T``, so dv and dk contract the minor dimension) and
takes dq from the same tile, accumulating the dQ of a KV head's whole
sequence in float32 in VMEM; where that does not fit
(``kernel_dispatch`` decides from the shape) the same walk is made a query
RANGE at a time, one range's dQ in VMEM, and a kv block's dk and dv leave as
a float32 partial a range, summed after the call. The two-pass pair (that
sweep for dk/dv alone and a dq kernel that sweeps kv blocks per q block) is
what ``impl_bwd="pallas"`` pins: the tests' second oracle and the sweep's
other column. All rebuild p from the saved LSE (no second online softmax).
A block wholly above the causal diagonal or outside the window is no grid
step at all: a masked call's forward and fused backward walk a TABLE of
their live tiles (``kernel_dispatch.flash_walk``, scalar-prefetched; grid
``(B*KV, live tiles)`` in the rectangle's order, so every sum takes the same
terms in turn). An unmasked call has no dead tile and keeps the rectangle; so
do the pair and a call whose table would pass SMEM
(``kernel_dispatch.walked``), where a dead step is skipped and fetches
nothing: the swept operand's index map is clamped to the live range and names
the block already resident. Blocks come from the shape
(``kernel_dispatch.choose_blocks``: 1024 folded query rows a step at any
group, and 512 keys or as many as the queries, where the sequences allow).

Values may be narrower than keys (latent attention as it trains: q and k
``[.., 192]``, v and o ``[.., 128]``): the same kernels with v's, dO's, O's
and dV's blocks at v's width, named ``mla_fwd`` / ``mla_bwd`` (the pair
``mla_bwd_dq`` + ``mla_bwd_dkdv``) where the widths differ, and no operand
padded in HBM.

A third structured mask has kernels of its own, under names of their own:
block-diffusion training's (``block_diffusion_attention``: ``bdattn_fwd``,
``bdattn_bwd``; the section at the end of this file says how its tiles are
found live, interior or dead).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .registry import registry, use_pallas

NEG_INF = -1e30
LSE_MASKED = 1e30  # rows that saw no key: exp(s - LSE_MASKED) == 0


def softcap_scores(s, cap):
    """Gemma-2 logit softcapping: cap * tanh(s / cap), applied AFTER the
    scale and BEFORE any mask/bias — the single definition every attention
    path (flash fwd/bwd kernels, paged kernel, XLA fallbacks, model dense
    branches) shares so kernel and reference numerics cannot drift."""
    return cap * jnp.tanh(s / cap)


def _xla_attention(q, k, v, scale, causal, window=None, softcap=None,
                   mask=None):
    """Reference implementation; q [B, S, H, D], k/v [B, S, KV, D] (GQA ok).
    ``mask`` [Sq, Sk] bool: a structured pattern given literally (True = the
    query sees the key), beside or in place of ``causal`` / ``window``."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k).astype(jnp.float32) * scale
    if softcap is not None:  # Gemma-2: cap BEFORE masking
        s = softcap * jnp.tanh(s / softcap)
    if causal or window is not None:
        n, m = q.shape[1], k.shape[1]
        keep = jnp.ones((n, m), bool)
        if causal:
            keep &= jnp.tril(keep, k=m - n)
        if window is not None:
            qpos = jnp.arange(n)[:, None] + (m - n)
            keep &= qpos - jnp.arange(m)[None, :] < window
        mask = keep if mask is None else keep & mask
    if mask is not None:
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)
    return out.reshape(B, Sq, H, v.shape[-1])


# Row statistics (running max and sum, the saved LSE, delta) are kept in
# VMEM lane-replicated, [rows, STAT_LANES]: a [rows, 1] column costs as many
# vector registers as a [rows, 128] tile with one lane of each in use, and
# every use pays a lane broadcast. Replicated, a load is dense and widening
# to the score tile re-uses the same registers.
STAT_LANES = 128


def _lanes(x, n):
    """A lane-replicated [rows, STAT_LANES] statistic as [rows, n]."""
    if n <= STAT_LANES:
        return x if n == STAT_LANES else x[:, :n]
    if n % STAT_LANES == 0:
        return jnp.concatenate([x] * (n // STAT_LANES), axis=1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _mask_scores(s, q_pos, k_pos, causal, window):
    if causal:
        s = jnp.where(k_pos > q_pos, NEG_INF, s)
    if window is not None:  # local attention: drop keys out of window
        s = jnp.where(q_pos - k_pos >= window, NEG_INF, s)
    return s


def _when_live(qi, ki, block_q, block_k, causal, window, compute, flags=None):
    """Run ``compute(masked)`` on the [q block qi] x [kv block ki] tile if it
    is live (some (q, k) pair unmasked), with ``masked=False`` if it is
    interior (every pair unmasked), so that it skips the iota + select mask
    chain (splash-style full/edge specialization: at seq >> block most live
    tiles are interior). ``flags``: the step's entry of a walk's flags table,
    which says both (``kernel_dispatch.flash_walk``); on the rectangle they
    are worked out from the tile's place."""
    if not causal and window is None:
        compute(masked=False)
        return
    if flags is not None:
        from .kernel_dispatch import EDGE, LIVE
        edge = _flag(flags, EDGE)
        pl.when(_flag(flags, LIVE) & jnp.logical_not(edge))(
            lambda: compute(masked=False))
        pl.when(edge)(lambda: compute(masked=True))
        return
    live = interior = True
    if causal:
        live = ki * block_k <= qi * block_q + block_q - 1
        interior = ki * block_k + block_k - 1 <= qi * block_q
    if window is not None:
        live = live & (ki * block_k + block_k - 1
                       >= qi * block_q - (window - 1))
        interior = interior & (
            qi * block_q + block_q - 1 - ki * block_k <= window - 1)

    @pl.when(live & interior)
    def _():
        compute(masked=False)

    @pl.when(live & jnp.logical_not(interior))
    def _():
        compute(masked=True)


def _flag(flags, bit):
    """Whether ``bit`` is set in a step's entry of a walk's flags table."""
    return (flags & bit) != 0


def _live_kv_block(i, j, block_q, block_k, num_kv, causal, window):
    """Index-map clamp of the swept kv block ``j`` to the live range of q
    block ``i``: a dead step then names the block its neighbour already
    holds and the pipeline copies nothing (``_when_live`` skips its
    arithmetic)."""
    if not causal and window is None:
        return j
    if window is not None:
        j = jnp.maximum(j, jnp.maximum(i * block_q - (window - 1), 0) // block_k)
    if causal:
        j = jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
    return jnp.minimum(j, num_kv - 1)


def _live_q_block(j, i, block_q, block_k, num_q, causal, window):
    """The same for the dk/dv kernel, which sweeps q blocks ``i`` per kv
    block ``j``."""
    if not causal and window is None:
        return i
    if causal:
        i = jnp.maximum(i, (j * block_k) // block_q)
    if window is not None:
        i = jnp.minimum(i, (j * block_k + block_k - 1 + window - 1) // block_q)
    return jnp.minimum(i, num_q - 1)


def _compiler_params(vmem_bytes):
    """A tile set whose estimate passes the compiler's scoped-VMEM default
    asks for what it needs (the chip has several times the default); the
    blocks the dispatcher picks stay under it and take the default."""
    from .kernel_dispatch import vmem_limit_bytes
    limit = vmem_limit_bytes(vmem_bytes)
    return None if limit is None else pltpu.CompilerParams(
        vmem_limit_bytes=limit)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s,
                *, scale, causal, block_q, block_k, num_kv, window=None,
                softcap=None, walk=None):
    """``walk``: None on the rectangle, where the step's tile and the ends
    of its query block's sweep are read off the grid; else (qi, ki, flags) as
    ``_fwd_table_kernel`` reads them off its tables."""
    from .kernel_dispatch import CLOSES, OPENS
    if walk is None:
        qi = pl.program_id(1)
        ki = pl.program_id(2)
        flags = None
        opens, closes = (lambda: ki == 0), (lambda: ki == num_kv - 1)
    else:
        qi, ki, flags = walk
        opens, closes = (functools.partial(_flag, flags, bit)
                         for bit in (OPENS, CLOSES))

    @pl.when(opens())
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    def _compute(masked):
        g, bq, d = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
        # matmul operands stay in the INPUT dtype (bf16 on the training
        # path): the MXU's fast path is bf16 x bf16 with fp32 accumulation
        # (preferred_element_type) — casting operands to fp32 first would
        # run every dot at the several-fold-slower fp32 rate. All softmax
        # arithmetic happens on the fp32 accumulator outputs.
        q = q_ref[0].reshape(g * bq, d)
        k = k_ref[0]  # [BK, D]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:  # Gemma-2: cap BEFORE masking
            s = softcap_scores(s, softcap)
        if masked:
            # rows are g-major: row = g * BQ + pos
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0) % block_q
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = _mask_scores(s, q_pos, k_pos, causal, window)
        # m, l and the rescale factor are [G*BQ, STAT_LANES], lane-replicated
        # (no 1D intermediates: Mosaic cannot shape-cast a lane-dim vector
        # into a sublane column)
        m_prev, l_prev = m_s[:], l_s[:]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        m_safe = jnp.where(m_cur <= NEG_INF, 0.0, m_cur)
        p = jnp.exp(s - _lanes(m_safe, block_k))
        if masked:
            # an INTERIOR block's scores are real numbers — only edge
            # blocks can carry NEG_INF rows that must zero out
            p = jnp.where(s <= NEG_INF, 0.0, p)
        corr = jnp.exp(jnp.where(m_prev <= NEG_INF, NEG_INF, m_prev - m_safe))
        l_s[:] = l_prev * corr + p.sum(axis=-1, keepdims=True)
        # p back to the input dtype for the MXU (standard flash practice —
        # GPU flash uses fp16/bf16 P too); the accumulator stays fp32
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1, ), (0, )), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc[:] = acc[:] * _lanes(corr, acc.shape[1]) + pv
        m_s[:] = m_cur

    _when_live(qi, ki, block_q, block_k, causal, window, _compute, flags)

    @pl.when(closes())
    def _finalize():
        g, bq, d = o_ref.shape[1], o_ref.shape[2], o_ref.shape[3]
        l = l_s[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[:] / _lanes(safe_l, d)).reshape(g, bq, d).astype(
            o_ref.dtype)
        m_safe = jnp.where(m_s[:] <= NEG_INF, 0.0, m_s[:])
        lse = jnp.where(l == 0.0, LSE_MASKED, m_safe + jnp.log(safe_l))
        lse_ref[0] = lse[:, :1].reshape(g, bq, 1)


def _fwd_table_kernel(q_tiles, k_tiles, flags, *refs, **static):
    """``_fwd_kernel`` on grid ``(B*KV, live tiles)``: the step's tile and
    what it opens and closes come from the tables
    (``kernel_dispatch.flash_walk``, query-major: a query block's sums open
    on its first listed tile and close on its last)."""
    step = pl.program_id(1)
    _fwd_kernel(*refs, walk=(q_tiles[step], k_tiles[step], flags[step]), **static)


def _grid_spec(tables, **spec):
    """The call's grid and blocks: with ``tables`` (scalar-prefetched, every
    index map and the kernel read them after the grid's indices) or plain."""
    if tables:
        return pltpu.PrefetchScalarGridSpec(num_scalar_prefetch=len(tables), **spec)
    return pl.GridSpec(**spec)


def _regroup(q, k, v):
    """[B,S,H,D]/[B,S,KV,D] -> qg [B*KV, G, Sq, D], kt/vt [B*KV, Sk, D],
    each operand at its own width (latent attention's v is narrower)."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = (q.reshape(B, Sq, KV, G, D).transpose(0, 2, 3, 1, 4)
          .reshape(B * KV, G, Sq, D))
    kt = k.transpose(0, 2, 1, 3).reshape(B * KV, k.shape[1], k.shape[3])
    vt = v.transpose(0, 2, 1, 3).reshape(B * KV, v.shape[1], v.shape[3])
    return qg, kt, vt


def _kernel_name(flash: str, latent: str, D: int, Dv: int) -> str:
    """A call whose values are as wide as its keys is ``flash_*``; one whose
    widths differ (latent attention: 192-wide q and k, 128-wide v and o) is
    ``mla_*``, so that a reader of the device trace, which counts a call's
    work from ONE width off its first result, tells them apart by name."""
    return flash if Dv == D else latent


def _blocked(Sq, Sk, block_q, block_k):
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (
        f"seq lens ({Sq},{Sk}) must be divisible by blocks ({block_q},{block_k})")
    return block_q, block_k, Sq // block_q, Sk // block_k


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret, window=None,
               softcap=None, table=False):
    """Per-head Pallas forward → (o, lse[B*KV, G, Sq, 1]). ``table``: the
    grid is ``(B*KV, live tiles)``, a query block's key blocks ascending as
    on the rectangle ``(B*KV, q blocks, kv blocks)``, whose dead steps it
    leaves out."""
    from . import kernel_dispatch as kd
    B, Sq, H, D = q.shape
    Sk, KV, Dv = k.shape[1], k.shape[2], v.shape[3]
    assert H % KV == 0, (H, KV)
    G = H // KV
    block_q, block_k, num_q, num_kv = _blocked(Sq, Sk, block_q, block_k)

    qg, kt, vt = _regroup(q, k, v)
    static = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k,
                  num_kv=num_kv, window=window, softcap=softcap)
    if table:
        tables = kd.flash_walk("fwd", num_q, num_kv, block_q, block_k, causal,
                               window)
        kernel = functools.partial(_fwd_table_kernel, **static)
        grid = (B * KV, len(tables[0]))

        def q_blk(s, q_tiles, k_tiles, flags):
            return q_tiles[s]

        def kv_blk(s, q_tiles, k_tiles, flags):
            return k_tiles[s]
    else:
        tables = ()
        kernel = functools.partial(_fwd_kernel, **static)
        grid = (B * KV, num_q, num_kv)

        def q_blk(i, j):
            return i

        def kv_blk(i, j):
            return _live_kv_block(i, j, block_q, block_k, num_kv, causal, window)

    def q_map(b, *at):
        return (b, 0, q_blk(*at), 0)

    def kv_map(b, *at):
        return (b, kv_blk(*at), 0)

    out, lse = pl.pallas_call(
        kernel,
        grid_spec=_grid_spec(
            tables,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, G, block_q, D), q_map),
                pl.BlockSpec((1, block_k, D), kv_map),
                pl.BlockSpec((1, block_k, Dv), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((1, G, block_q, Dv), q_map),
                # trailing unit lane dim: every reshape of the LSE then keeps
                # the minormost dim intact (a supported Mosaic shape cast),
                # unlike (1,G,BQ)->(G*BQ,1) which fails to lower for G > 1
                pl.BlockSpec((1, G, block_q, 1), q_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((G * block_q, Dv), jnp.float32),
                pltpu.VMEM((G * block_q, STAT_LANES), jnp.float32),
                pltpu.VMEM((G * block_q, STAT_LANES), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((B * KV, G, Sq, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * KV, G, Sq, 1), jnp.float32),
        ],
        compiler_params=_compiler_params(kd.flash_vmem_bytes(
            "fwd", G, kd.vmem_width(D, Dv), q.dtype.itemsize, block_q, block_k)),
        interpret=interpret,
        name=_kernel_name("flash_fwd", "mla_fwd", D, Dv),
    )(*tables, qg, kt, vt)
    o = (out.reshape(B, KV, G, Sq, Dv).transpose(0, 3, 1, 2, 4)
         .reshape(B, Sq, H, Dv))
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
               *, scale, causal, block_q, block_k, num_kv, window=None,
               softcap=None):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute(masked):
        g, bq, d = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
        # operands stay in the input dtype for the MXU fast path (see
        # _fwd_kernel); fp32 only on accumulator outputs + softmax math
        q = q_ref[0].reshape(g * bq, d)
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].reshape(g * bq, do_ref.shape[3])
        # lse/delta carry a trailing unit lane dim so this reshape is a
        # supported Mosaic cast (minormost dim preserved); no 1D
        # intermediates. Read once a step, the columns cost less here than
        # a lane-replicated copy in scratch (measured, PERF.md §6 PR 25)
        lse = lse_ref[0].reshape(g * bq, 1)
        delta = delta_ref[0].reshape(g * bq, 1)

        s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            t = jnp.tanh(s / softcap)
            s = softcap * t  # == softcap_scores; t reused for d/ds = 1 - t^2
        if masked:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0) % block_q
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = _mask_scores(s, q_pos, k_pos, causal, window)
        p = jnp.exp(s - lse)
        if masked:  # interior blocks never carry NEG_INF scores
            p = jnp.where(s <= NEG_INF, 0.0, p)
        dp = jax.lax.dot_general(do, v, (((1, ), (1, )), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        if softcap is not None:  # chain through d/ds cap*tanh(s/cap) = 1 - t^2
            ds = ds * (1.0 - t * t)
        dq_acc[:] += jax.lax.dot_general(ds.astype(k.dtype), k,
                                         (((1, ), (0, )), ((), ())),
                                         preferred_element_type=jnp.float32)

    _when_live(qi, ki, block_q, block_k, causal, window, _compute)

    @pl.when(ki == num_kv - 1)
    def _finalize():
        g, bq = dq_ref.shape[1], dq_ref.shape[2]
        dq_ref[0] = dq_acc[:].reshape(g, bq, -1).astype(dq_ref.dtype)


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                 scale, causal, block_q, block_k, num_q, num_kv, fused,
                 ranges=1, window=None, softcap=None, walk=None):
    """dK and dV of one kv block over a sweep of the q blocks (innermost)
    and, ``fused``, dQ from the same score tiles: the dQ of this KV head's
    queries accumulates in float32 in ``dq_acc`` [q blocks, G*BQ, D] over
    the kv sweep, ascending as the dq kernel sums it, and the last live kv
    block's sweep writes it out, one q block a step. ``refs``: the results
    dk, dv (, dq), then their float32 accumulators.

    ``ranges`` > 1: the walk is made a RANGE of ``num_q / ranges`` q blocks
    at a time (grid ``(B*KV, range, kv block, q block of the range)``), so
    that ``dq_acc`` holds one range's dQ; a kv block's dK and dV then leave
    as one float32 partial a range, for the caller to sum, and the results
    come dq first (``benchmark/flash_cost.py`` reads a call's heads,
    sequence and head size off its first result).

    ``walk``: None on the rectangle, where the step's tile and the ends of
    its sweeps are read off the grid; else (qi, ki, flags) as
    ``_dkdv_table_kernel`` reads them off its tables. There a q block's dQ
    opens on its first live kv block and leaves on its last, the diagonal's,
    which is where its sum is whole."""
    from .kernel_dispatch import CLOSES, DQ_CLOSES, DQ_OPENS, OPENS
    if ranges == 1:
        if fused:
            dk_ref, dv_ref, dq_ref, dk_acc, dv_acc, dq_acc = refs
        else:
            dk_ref, dv_ref, dk_acc, dv_acc = refs
    else:
        dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, dq_acc = refs
    if walk is not None:
        qi, ki, flags = walk
        qr = qi if ranges == 1 else qi % (num_q // ranges)
        opens, closes, dq_opens, dq_closes = (
            functools.partial(_flag, flags, bit)
            for bit in (OPENS, CLOSES, DQ_OPENS, DQ_CLOSES))
    else:
        flags = None
        if ranges == 1:
            ki, qr = pl.program_id(1), pl.program_id(2)
            qi, last_kv = qr, num_kv - 1
        else:
            # qr: the q block within the range; qi: within the sequence
            r, ki, qr = pl.program_id(1), pl.program_id(2), pl.program_id(3)
            qi = r * (num_q // ranges) + qr
            last_kv = _live_kv_block(r, num_kv - 1, block_q * (num_q // ranges),
                                     block_k, num_kv, causal, window)
        opens, closes = (lambda: qr == 0), (lambda: qr == num_q // ranges - 1)
        dq_opens, dq_closes = (lambda: ki == 0), (lambda: ki == last_kv)

    @pl.when(opens())
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if fused:
        @pl.when(dq_opens())
        def _init_dq():
            dq_acc[qr] = jnp.zeros(dq_acc.shape[1:], dq_acc.dtype)

    def _compute(masked):
        g, bq, d = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]
        # operands stay in the input dtype for the MXU fast path (see
        # _fwd_kernel); fp32 only on accumulator outputs + softmax math
        q = q_ref[0].reshape(g * bq, d)
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].reshape(g * bq, do_ref.shape[3])
        lse = lse_ref[0, 0]      # [1, G*BQ]: rows, g-major like q's
        delta = delta_ref[0, 0]

        # the scores TRANSPOSED, [BK, G*BQ] = k . q^T: dv and dk below are
        # then plain [BK, G*BQ] x [G*BQ, D] products. Contracting p and ds
        # over their first dimension instead makes Mosaic transpose both
        # tiles on every step (that was half of this kernel's time).
        s = jax.lax.dot_general(k, q, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            t = jnp.tanh(s / softcap)
            s = softcap * t  # == softcap_scores; t reused for d/ds = 1 - t^2
        if masked:
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1) % block_q
            s = _mask_scores(s, q_pos, k_pos, causal, window)
        p = jnp.exp(s - lse)
        if masked:  # interior blocks never carry NEG_INF scores
            p = jnp.where(s <= NEG_INF, 0.0, p)
        # dv += p^T @ do ; dk += ds^T @ q — over the folded G*BQ rows, which
        # also sums the G query heads sharing this KV head (GQA reduce)
        dv_acc[:] += jax.lax.dot_general(p.astype(do.dtype), do,
                                         (((1, ), (0, )), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, (((1, ), (1, )), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        if softcap is not None:
            ds = ds * (1.0 - t * t)
        ds = ds.astype(q.dtype)
        dk_acc[:] += jax.lax.dot_general(ds, q, (((1, ), (0, )), ((), ())),
                                         preferred_element_type=jnp.float32)
        if fused:
            # dq += ds @ k from the same tile: ds^T contracted over its
            # first dimension, so Mosaic transposes this one tile a step
            dq_acc[qr] += jax.lax.dot_general(
                ds, k, (((0, ), (0, )), ((), ())),
                preferred_element_type=jnp.float32)

    _when_live(qi, ki, block_q, block_k, causal, window, _compute, flags)

    @pl.when(closes())
    def _finalize():
        if ranges == 1:
            dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)
        else:   # this range's partial, in the accumulators' float32
            dk_ref[0, 0] = dk_acc[:]
            dv_ref[0, 0] = dv_acc[:]

    if fused:
        @pl.when(dq_closes())
        def _finalize_dq():
            g, bq = dq_ref.shape[1], dq_ref.shape[2]
            dq_ref[0] = dq_acc[qr].reshape(g, bq, -1).astype(dq_ref.dtype)


def _dkdv_table_kernel(q_tiles, k_tiles, dq_tiles, flags, *refs, **static):
    """The fused ``_dkdv_kernel`` on grid ``(B*KV, live tiles)``: the step's
    tile and what it opens and closes come from the tables
    (``kernel_dispatch.flash_walk``: key-major, a query range after another; a
    kv block's dK and dV open on the first listed tile of its sweep of one
    range and close on the last). ``dq_tiles`` is the dQ result's index
    map's alone."""
    step = pl.program_id(1)
    _dkdv_kernel(*refs, walk=(q_tiles[step], k_tiles[step], flags[step]), **static)


def _flash_bwd(res, g_out, scale, causal, block_q, block_k, interpret, window=None,
               softcap=None, fused=False, ranges=1, table=False):
    """Per-head Pallas backward; ``res`` carries lse in the per-head
    layout, [B*KV, G, Sq] as the forward rule keeps it (or with the kernels'
    trailing unit dimension). ``fused``: one ``flash_dkdv_dq`` call in place
    of ``flash_dq`` and ``flash_dkdv``, its walk made ``ranges`` query ranges
    at a time (``kernel_dispatch`` decides: the float32 dQ of a KV head's
    range has to fit in VMEM). ``table``: the fused call's grid is ``(B*KV,
    live tiles)``, the rectangle's walk with its dead steps left out."""
    from .kernel_dispatch import flash_vmem_bytes, flash_walk, vmem_width
    q, k, v, o, lse = res
    B, Sq, H, D = q.shape
    Sk, KV, Dv = k.shape[1], k.shape[2], v.shape[3]
    G = H // KV
    block_q, block_k, num_q, num_kv = _blocked(Sq, Sk, block_q, block_k)
    assert fused or ranges == 1, "only the fused backward walks by ranges"
    assert fused or not table, "only the fused backward walks a table"
    assert num_q % ranges == 0, (
        f"{ranges} ranges must each hold whole q blocks: {num_q} of {block_q}")
    range_q = num_q // ranges       # q blocks a range
    params = _compiler_params(flash_vmem_bytes(
        "fused" if fused else "bwd", G, vmem_width(D, Dv), q.dtype.itemsize,
        block_q, block_k, seq_q=Sq // ranges))
    static = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k,
                  window=window, softcap=softcap)

    qg, kt, vt = _regroup(q, k, v)
    dog, _, _ = _regroup(g_out, k, v)
    og, _, _ = _regroup(o, k, v)
    delta = jnp.sum(dog.astype(jnp.float32) * og.astype(jnp.float32), axis=-1,
                    keepdims=True)  # [B*KV, G, Sq, 1] — unit lane dim, see lse

    if not fused:
        def kv_map(b, i, j):
            return (b, _live_kv_block(i, j, block_q, block_k, num_kv, causal,
                                      window), 0)

        q_spec = pl.BlockSpec((1, G, block_q, D), lambda b, i, j: (b, 0, i, 0))
        do_spec = pl.BlockSpec((1, G, block_q, Dv), lambda b, i, j: (b, 0, i, 0))
        k_spec = pl.BlockSpec((1, block_k, D), kv_map)
        v_spec = pl.BlockSpec((1, block_k, Dv), kv_map)
        r_spec = pl.BlockSpec((1, G, block_q, 1), lambda b, i, j: (b, 0, i, 0))
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, num_kv=num_kv, **static),
            grid=(B * KV, num_q, num_kv),
            in_specs=[q_spec, k_spec, v_spec, do_spec, r_spec, r_spec],
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct((B * KV, G, Sq, D), q.dtype),
            scratch_shapes=[pltpu.VMEM((G * block_q, D), jnp.float32)],
            compiler_params=params,
            interpret=interpret,
            name=_kernel_name("flash_dq", "mla_bwd_dq", D, Dv),
        )(qg, kt, vt, dog, lse.reshape(delta.shape), delta)

    # kv-major grid for dk/dv: q sweep innermost. lse and delta enter as
    # rows of the transposed score tile: [B*KV, q blocks, 1, G*BQ], g-major
    # inside a block like the folded q rows
    def rows(x):
        return (x.reshape(B * KV, G, num_q, block_q).transpose(0, 2, 1, 3)
                .reshape(B * KV, num_q, 1, G * block_q))

    tables = ()
    if table:
        # (KV head, live tile): a kv block's q blocks ascending, a range after
        # another; a (range, kv block) with no live tile keeps one dead step,
        # which writes its zero partial
        tables = flash_walk("bwd", num_q, num_kv, block_q, block_k, causal,
                            window, ranges)
        grid = (B * KV, len(tables[0]))

        def q_blk(s, q_tiles, k_tiles, dq_tiles, flags):
            return q_tiles[s]

        def kv_blk(s, q_tiles, k_tiles, dq_tiles, flags):
            return k_tiles[s]

        def dq_blk(s, q_tiles, k_tiles, dq_tiles, flags):
            # the q block whose dQ is completed next: held until the step
            # that completes it, so each is written once, whole
            return dq_tiles[s]

        def dkv_at(s, q_tiles, k_tiles, dq_tiles, flags):
            if ranges == 1:
                return (k_tiles[s], )
            return q_tiles[s] // range_q, k_tiles[s]
    elif ranges == 1:
        grid = (B * KV, num_kv, num_q)

        def q_blk(j, i):
            return _live_q_block(j, i, block_q, block_k, num_q, causal, window)

        def kv_blk(j, i):
            return j

        def dq_blk(j, i):
            # a q block of dQ is complete, and written, in the last kv
            # block's sweep; until then the map names block 0, which that
            # sweep writes first, so nothing leaves VMEM before it holds a
            # result
            return jnp.where(j == num_kv - 1, i, 0)

        def dkv_at(j, i):
            return (j, )
    else:
        # (KV head, range, kv block, q block of the range): a dead step,
        # and a dead sweep (a kv block past a causal range, or before its
        # window), names the blocks its neighbour holds, inside the range
        grid = (B * KV, ranges, num_kv, range_q)

        def live_kv(r, j):
            return _live_kv_block(r, j, range_q * block_q, block_k, num_kv,
                                  causal, window)

        def q_blk(r, j, i):
            first = r * range_q
            return jnp.clip(_live_q_block(j, first + i, block_q, block_k,
                                          num_q, causal, window),
                            first, first + range_q - 1)

        def kv_blk(r, j, i):
            return live_kv(r, j)

        def dq_blk(r, j, i):
            # the range's dQ leaves in its last live kv block's sweep: its
            # first q block named before it, its last one after it
            last = live_kv(r, num_kv - 1)
            return r * range_q + jnp.where(
                j < last, 0, jnp.where(j == last, i, range_q - 1))

        def dkv_at(r, j, i):
            return r, j

    if ranges == 1:
        dkv_shape, dkv_dtype = (B * KV, Sk), (k.dtype, v.dtype)
    else:   # a kv block's dK and dV of one range: float32, summed below
        dkv_shape, dkv_dtype = (B * KV, ranges, Sk), (jnp.float32, ) * 2

    def dkv_map(width):
        return pl.BlockSpec((1, ) * (len(dkv_shape) - 1) + (block_k, width),
                            lambda b, *ids: (b, *dkv_at(*ids), 0))

    q_spec2 = pl.BlockSpec((1, G, block_q, D),
                           lambda b, *ids: (b, 0, q_blk(*ids), 0))
    do_spec2 = pl.BlockSpec((1, G, block_q, Dv),
                            lambda b, *ids: (b, 0, q_blk(*ids), 0))
    k_spec2 = pl.BlockSpec((1, block_k, D), lambda b, *ids: (b, kv_blk(*ids), 0))
    v_spec2 = pl.BlockSpec((1, block_k, Dv), lambda b, *ids: (b, kv_blk(*ids), 0))
    r_spec2 = pl.BlockSpec((1, 1, 1, G * block_q),
                           lambda b, *ids: (b, q_blk(*ids), 0, 0))
    out_specs = [dkv_map(D), dkv_map(Dv)]
    out_shape = [jax.ShapeDtypeStruct(dkv_shape + (D, ), dkv_dtype[0]),
                 jax.ShapeDtypeStruct(dkv_shape + (Dv, ), dkv_dtype[1])]
    scratch_shapes = [pltpu.VMEM((block_k, D), jnp.float32),
                      pltpu.VMEM((block_k, Dv), jnp.float32)]
    if fused:
        dq_spec = pl.BlockSpec((1, G, block_q, D),
                               lambda b, *ids: (b, 0, dq_blk(*ids), 0))
        dq_shape = jax.ShapeDtypeStruct((B * KV, G, Sq, D), q.dtype)
        # dq last, where one range walks; first, where several do
        at = len(out_specs) if ranges == 1 else 0
        out_specs.insert(at, dq_spec)
        out_shape.insert(at, dq_shape)
        scratch_shapes.append(pltpu.VMEM((range_q, G * block_q, D), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_dkdv_table_kernel if table else _dkdv_kernel,
                          num_q=num_q, num_kv=num_kv, fused=fused,
                          ranges=ranges, **static),
        grid_spec=_grid_spec(
            tables, grid=grid,
            in_specs=[q_spec2, k_spec2, v_spec2, do_spec2, r_spec2, r_spec2],
            out_specs=out_specs, scratch_shapes=scratch_shapes),
        out_shape=out_shape,
        compiler_params=params,
        interpret=interpret,
        name=(_kernel_name("flash_dkdv_dq", "mla_bwd", D, Dv) if fused
              else _kernel_name("flash_dkdv", "mla_bwd_dkdv", D, Dv)),
    )(*tables, qg, kt, vt, dog, rows(lse), rows(delta))
    if not fused:
        dk, dv = outs
    elif ranges == 1:
        dk, dv, dq = outs
    else:
        dq, dk, dv = outs
        dk, dv = (jnp.sum(x, axis=1).astype(like.dtype)
                  for x, like in ((dk, k), (dv, v)))

    dq = (dq.reshape(B, KV, G, Sq, D).transpose(0, 3, 1, 2, 4)
          .reshape(B, Sq, H, D))
    dk = dk.reshape(B, KV, Sk, D).transpose(0, 2, 1, 3)
    dv = dv.reshape(B, KV, Sk, Dv).transpose(0, 2, 1, 3)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# the call: ops/kernel_dispatch.py resolves kernels and blocks from the shape
# ---------------------------------------------------------------------------


def _fit_blocks(dec, Sq, Sk):
    """Clamp a Decision's blocks to divide the actual sequence lengths.

    Fit = largest power-of-two divisor of S that is <= the requested block
    (every eligible s % 128 == 0 shape reaches 128; an odd override can't
    silently degrade to block 1 — a degenerate fit keeps the requested
    block so the kernels' divisibility assert fails LOUDLY instead of
    silently running 1-wide blocks)."""

    def _fit(S, b):
        b = min(b, S)
        if S % b == 0:
            return b
        p = 1
        while p * 2 <= b and S % (p * 2) == 0:
            p *= 2
        return p if p >= 32 else b

    return dec._replace(block_q=_fit(Sq, dec.block_q),
                        block_k=_fit(Sk, dec.block_k))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _dispatched_attention(q, k, v, scale, causal, window, softcap, interpret,
                          fwd_dec, bwd_dec):
    """The per-head forward at ``fwd_dec``'s blocks; its backward is
    ``bwd_dec``'s (hashable ``kernel_dispatch.Decision`` tuples, resolved at
    trace time): the fused kernel in its ranges, or the dq + dk/dv pair
    where a caller pinned it."""
    return _flash_fwd(q, k, v, scale, causal, fwd_dec.block_q,
                      fwd_dec.block_k, interpret, window, softcap,
                      table=fwd_dec.table)[0]


# What a layer's backward needs of an attention kernel's forward, by the
# names a recomputation's policy can keep them under
# (``jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES)``, the
# default of ``models/llama.py`` under ``remat`` with no policy named): with
# both kept the recomputed layer does not run the kernel again. A name is the
# identity anywhere else.
RESIDUAL_NAMES = ("ds.attn.out", "ds.attn.lse")


def _name_residuals(o, lse):
    """-> (o, lse) under ``RESIDUAL_NAMES``, ``lse`` WITHOUT the kernels'
    trailing unit dimension: a float32 ``[..., seq, 1]`` is tiled to 128
    lanes in HBM, 128 times its bytes, and a kept residual lives from the
    forward to the layer's backward. The backward takes this form: it reads
    ``lse`` as rows of a transposed score tile, a reshape of either form,
    and only ``flash_dq``, which reads it a query block at a time, is handed
    the unit dimension back. With nothing between the two (no
    recomputation) XLA folds the reshapes."""
    o = checkpoint_name(o, RESIDUAL_NAMES[0])
    lse = checkpoint_name(lse[..., 0], RESIDUAL_NAMES[1])
    return o, lse


def _fwd_rule(q, k, v, scale, causal, window, softcap, interpret, fwd_dec,
              bwd_dec):
    o, lse = _name_residuals(*_flash_fwd(
        q, k, v, scale, causal, fwd_dec.block_q, fwd_dec.block_k, interpret,
        window, softcap, table=fwd_dec.table))
    return o, (q, k, v, o, lse)


def _bwd_rule(scale, causal, window, softcap, interpret, fwd_dec, bwd_dec,
              res, g):
    return _flash_bwd(res, g, scale, causal, bwd_dec.block_q,
                      bwd_dec.block_k, interpret, window, softcap,
                      fused=bwd_dec.impl == "fused", ranges=bwd_dec.ranges,
                      table=bwd_dec.table)


_dispatched_attention.defvjp(_fwd_rule, _bwd_rule)

# The device trace names a Pallas call by the innermost name scope of the
# frame that holds it, and a transformation wraps the scopes of its own
# frame (``jvp(flash_fwd)`` reads ``%jvp_flash_fwd_``). A jit boundary
# outside the custom_vjp starts a new frame, so the kernels read
# ``%flash_fwd.N`` / ``%flash_dkdv_dq.N`` (or ``%flash_dq.N`` +
# ``%flash_dkdv.N`` where the pair is pinned) under ``jax.grad``
# on one device as they do inside a ``shard_map``. XLA inlines the call.
_flash_attention_call = jax.jit(_dispatched_attention,
                                static_argnums=(3, 4, 5, 6, 7, 8, 9))


def flash_attention(q,
                    k,
                    v,
                    causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    force_pallas: Optional[bool] = None,
                    interpret: bool = False,
                    impl_bwd: Optional[str] = None,
                    ranges: Optional[int] = None,
                    table: Optional[bool] = None):
    """Blocked attention; q [B, S, H, D], k/v [B, S, KV, D] (GQA native);
    v may be ``[B, S, KV, Dv]`` with ``Dv != D`` (the result is then ``Dv``
    wide and the kernels are the ``mla_*`` calls).

    On a TPU (or with ``interpret=True`` anywhere) the per-head Pallas
    forward runs, and under ``jax.grad`` the backward that
    ``ops/kernel_dispatch.py`` resolves from the shape: the fused kernel,
    walked in as few query ranges as put a range's float32 dQ in VMEM (one
    where the whole sequence's fits). ``impl_bwd`` ("fused" | "pallas", the
    dq + dk/dv pair), ``block_q``/``block_k`` and ``ranges`` pin them
    (tests, the sweep tool); blocks otherwise follow from the shape
    (``kernel_dispatch.choose_blocks``). Off a TPU without ``interpret``,
    ``_xla_attention`` runs both ways.

    A causal or windowed call's grid is a table of its live tiles, in the
    order the rectangle walked them (``kernel_dispatch.walked``: the forward
    and the fused backward, up to its cap); an unmasked call has no dead
    tile and keeps the rectangle. ``table`` pins either walk of a masked call
    (``False``: the clamped rectangle): the results are equal bit for bit.

    The kernels count a causal mask or a window from the first query and
    the first key; ``_xla_attention`` aligns the last query with the last
    key. The two agree where ``seq_q == seq_k``; a masked call with more
    keys than queries, or fewer, is refused on the kernel path.
    """
    from . import kernel_dispatch as kd

    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    if not (use_pallas(force_pallas) or interpret):
        return _xla_attention(q, k, v, scale, causal, window, softcap)
    if (causal or window is not None) and q.shape[1] != k.shape[1]:
        raise ValueError(
            f"flash_attention(causal={causal}, window={window}) with seq_q "
            f"{q.shape[1]} != seq_k {k.shape[1]}: the kernels align the "
            "mask's diagonal with the first query and key, the XLA "
            "reference with the last; pass equal lengths, or no mask")
    sig = kd.make_sig(q.shape, k.shape[2], k.shape[1], q.dtype, causal,
                      window, softcap, v_dim=v.shape[-1])
    blocks = ((block_q, block_k)
              if block_q is not None and block_k is not None else None)
    fwd_dec, bwd_dec = (
        kd.walked(sig, _fit_blocks(dec, q.shape[1], k.shape[1]), leg, table)
        for leg, dec in zip(("fwd", "bwd"), kd.resolve(
            sig, impl_bwd=impl_bwd, blocks=blocks, ranges=ranges)))
    return _flash_attention_call(q, k, v, scale, causal, window,
                                 softcap, interpret, fwd_dec, bwd_dec)


# ---------------------------------------------------------------------------
# block-diffusion attention (BD3-LM / SDAR training): the noisy copy and the
# clean copy of a document in one sequence of 2L positions
# ---------------------------------------------------------------------------
#
# Positions [0, L) hold the noised copy, [L, 2L) the clean one, blocks of
# ``block_length`` tokens. Query i sees key j iff
#   noisy -> noisy:  blk(i) == blk(j)   (its own block, both ways)
#   noisy -> clean:  blk(j) <  blk(i)   (strictly earlier clean blocks)
#   clean -> clean:  blk(j) <= blk(i)   (block-causal)
#   clean -> noisy:  never
# so L^2 + L * block_length of the (2L)^2 pairs are live. The kernels never
# see the noisy keys but on each query tile's own diagonal: a grid step
# holds BOTH copies' query rows of one tile of L (noisy rows first), takes
# the noisy rows against the tile's own noisy keys once (step 0 of its
# sweep, a [G * BQ, BQ] product under the own-block mask), and then sweeps
# the clean key tiles up to the diagonal for all 2 * G * BQ rows at once:
# the two copies share every clean K/V tile they read, tiles past the
# diagonal are skipped with their copies (the index map is clamped, as in
# the causal kernel), tiles wholly before it run without the element mask,
# and the diagonal tile masks noisy rows by ``<`` and clean rows by ``<=``.


def block_diffusion_mask(seq: int, block_length: int) -> np.ndarray:
    """[2 * seq, 2 * seq] bool, True where the query (row) sees the key: the
    four rules above, literally."""
    i = np.arange(2 * seq)[:, None]
    j = np.arange(2 * seq)[None, :]
    q_clean, k_clean = i >= seq, j >= seq
    q_blk, k_blk = (i % seq) // block_length, (j % seq) // block_length
    return np.where(q_clean, k_clean & (k_blk <= q_blk),
                    np.where(k_clean, k_blk < q_blk, k_blk == q_blk))


def block_diffusion_live_tiles(seq: int, block_q: int, block_k: int) -> tuple:
    """(live, interior, all) grid steps of one KV head's sweep over ``seq``
    data tokens: the own-block step of every query tile and the clean key
    tiles at or before its diagonal are live, those wholly before it
    interior (no element mask)."""
    num_q, num_k = seq // block_q, seq // block_k
    live = interior = 0
    for i in range(num_q):
        live += 1 + sum(j * block_k < (i + 1) * block_q for j in range(num_k))
        interior += sum((j + 1) * block_k <= i * block_q for j in range(num_k))
    return live, interior, num_q * (1 + num_k)


def _bd_regroup(q, k, v, seq):
    """q [B, 2L, H, D] -> [B*KV, 2, G, L, D] (the copy, then the head of the
    group, then the position); k, v [B, 2L, KV, D] -> [B*KV, 2L, D]."""
    B, S, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = (q.reshape(B, 2, seq, KV, G, D).transpose(0, 3, 1, 4, 2, 5)
          .reshape(B * KV, 2, G, seq, D))
    kt = k.transpose(0, 2, 1, 3).reshape(B * KV, S, D)
    vt = v.transpose(0, 2, 1, 3).reshape(B * KV, S, D)
    return qg, kt, vt


def _bd_ungroup(x, B, KV):
    """[B*KV, 2, G, L, D] -> [B, 2L, H, D]."""
    _, _, G, seq, D = x.shape
    return (x.reshape(B, KV, 2, G, seq, D).transpose(0, 2, 4, 1, 3, 5)
            .reshape(B, 2 * seq, KV * G, D))


def _bd_own_block(q_loc, k_loc, block_length):
    """True where the key's tile-local position lies in the query's block."""
    own = q_loc - q_loc % block_length
    return (k_loc >= own) & (k_loc < own + block_length)


def _bd_clean_limit(row, q0, block_q, half, block_length):
    """The first clean key position a query row may NOT see: the start of
    its own block for a noisy row (``row < half``), its end for a clean one.
    Rows are copy-major, then head, then position."""
    q_loc = row % block_q
    return (q0 + q_loc - q_loc % block_length
            + jnp.where(row >= half, block_length, 0))


def _bd_clean_step(t, qi, block_q, block_k, compute):
    """Step ``t >= 1`` of a query tile's sweep is clean key tile ``t - 1``:
    run ``compute(masked)`` if it is live, without the mask if interior."""
    k0, q0 = (t - 1) * block_k, qi * block_q
    live = (t >= 1) & (k0 < q0 + block_q)
    interior = k0 + block_k <= q0

    @pl.when(live & interior)
    def _():
        compute(masked=False)

    @pl.when(live & jnp.logical_not(interior))
    def _():
        compute(masked=True)


def _bd_clean_tile(i, t, block_q, block_k):
    """Index-map clamp of the swept clean key tile to the live range of
    query tile ``i`` (dead steps, and step 0, name a tile already there)."""
    return jnp.minimum(jnp.maximum(t - 1, 0), (i * block_q + block_q - 1) // block_k)


def _bd_fwd_kernel(q_ref, kd_ref, vd_ref, kc_ref, vc_ref, o_ref, lse_ref, acc,
                   m_s, l_s, *, scale, block_length, block_q, block_k, steps):
    qi, t = pl.program_id(1), pl.program_id(2)
    g, bq, d = q_ref.shape[2:]
    half = g * bq

    def update(rows, s, v):
        # every row's first tile holds a key it sees (its own block, or
        # clean block 0), so m is a real number from then on and a masked
        # score's exp underflows to 0: no guards
        m_prev = m_s[rows]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_cur, s.shape[1]))
        corr = jnp.exp(m_prev - m_cur)
        l_s[rows] = l_s[rows] * corr + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1, ), (0, )), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc[rows] = acc[rows] * _lanes(corr, d) + pv
        m_s[rows] = m_cur

    @pl.when(t == 0)
    def _own_block():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)
        qn = q_ref[0, 0].reshape(half, d)
        s = jax.lax.dot_general(qn, kd_ref[0], (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_loc = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % block_q
        k_loc = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(_bd_own_block(q_loc, k_loc, block_length), s, NEG_INF)
        update(slice(0, half), s, vd_ref[0])

    def _clean(masked):
        q = q_ref[0].reshape(2 * half, d)
        s = jax.lax.dot_general(q, kc_ref[0], (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = (t - 1) * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(k_pos < _bd_clean_limit(row, qi * block_q, block_q,
                                                  half, block_length),
                          s, NEG_INF)
        update(slice(None), s, vc_ref[0])

    _bd_clean_step(t, qi, block_q, block_k, _clean)

    @pl.when(t == steps - 1)
    def _finalize():
        l = l_s[:]
        o_ref[0] = (acc[:] / _lanes(l, d)).reshape(2, g, bq, d).astype(o_ref.dtype)
        lse_ref[0] = (m_s[:] + jnp.log(l))[:, :1].reshape(2, g, bq, 1)


def _bd_specs(G, D, seq, block_q, block_k):
    """BlockSpecs of the (KV head, query tile, step) grid: both copies' rows
    of a query tile, the tile's own noisy keys, the swept clean key tile."""
    q_spec = pl.BlockSpec((1, 2, G, block_q, D), lambda b, i, t: (b, 0, 0, i, 0))
    own_spec = pl.BlockSpec((1, block_q, D), lambda b, i, t: (b, i, 0))
    clean_spec = pl.BlockSpec(
        (1, block_k, D),
        lambda b, i, t: (b, seq // block_k + _bd_clean_tile(i, t, block_q, block_k), 0))
    return q_spec, own_spec, clean_spec


def _bd_check(q, k, block_length, block_q, block_k):
    B, S, H, D = q.shape
    KV = k.shape[2]
    seq = S // 2
    if S % 2 or seq % block_length:
        raise ValueError(f"block-diffusion attention wants 2 * L positions, L a "
                         f"multiple of the block length {block_length}: got {S}")
    if (seq % block_q or seq % block_k or block_q % block_length
            or block_k % block_length):
        raise ValueError(
            f"block-diffusion tiles ({block_q} queries, {block_k} keys) must "
            f"divide L = {seq} and be multiples of the block length "
            f"{block_length}: a tile may not straddle the copies' border or "
            f"cut a block")
    return B, seq, H, KV, H // KV, D


def _bd_flash_fwd(q, k, v, scale, block_length, block_q, block_k, interpret):
    """-> (o [B, 2L, H, D], lse [B*KV, 2, G, L, 1])."""
    from .kernel_dispatch import bdattn_vmem_bytes
    B, seq, H, KV, G, D = _bd_check(q, k, block_length, block_q, block_k)
    steps = 1 + seq // block_k
    qg, kt, vt = _bd_regroup(q, k, v, seq)
    q_spec, own_spec, clean_spec = _bd_specs(G, D, seq, block_q, block_k)
    rows = 2 * G * block_q
    out, lse = pl.pallas_call(
        functools.partial(_bd_fwd_kernel, scale=scale, block_length=block_length,
                          block_q=block_q, block_k=block_k, steps=steps),
        grid=(B * KV, seq // block_q, steps),
        in_specs=[q_spec, own_spec, own_spec, clean_spec, clean_spec],
        out_specs=[q_spec,
                   pl.BlockSpec((1, 2, G, block_q, 1),
                                lambda b, i, t: (b, 0, 0, i, 0))],
        out_shape=[jax.ShapeDtypeStruct(qg.shape, q.dtype),
                   jax.ShapeDtypeStruct((B * KV, 2, G, seq, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((rows, D), jnp.float32),
                        pltpu.VMEM((rows, STAT_LANES), jnp.float32),
                        pltpu.VMEM((rows, STAT_LANES), jnp.float32)],
        compiler_params=_compiler_params(bdattn_vmem_bytes(
            "fwd", G, D, q.dtype.itemsize, block_q, block_k, seq)),
        interpret=interpret,
        name="bdattn_fwd",
    )(qg, kt, vt, kt, vt)
    return _bd_ungroup(out, B, KV), lse


def _bd_bwd_kernel(q_ref, kd_ref, vd_ref, kc_ref, vc_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dkn_ref, dvn_ref, dkc_ref, dvc_ref, dq_acc,
                   dkc_acc, dvc_acc, *, scale, block_length, block_q, block_k,
                   steps, num_q):
    """dQ, dK and dV from one walk over the live score tiles, TRANSPOSED
    (``k . q^T``, as ``_dkdv_kernel``): a query tile's dQ accumulates over
    its sweep; its own noisy keys' dK/dV are whole after step 0 (no other
    tile sees them) and written there; the clean keys' accumulate in float32
    in VMEM over all query tiles, [L / BK, BK, D] each, and the last query
    tile's sweep, in which every clean tile is live, writes them out."""
    qi, t = pl.program_id(1), pl.program_id(2)
    g, bq, d = q_ref.shape[2:]
    half = g * bq

    def tile_grads(k, v, q, do, lse, delta, keep):
        """-> (dv, dk, dq) of one transposed tile: s = k q^T masked by
        ``keep`` (None: interior)."""
        s = jax.lax.dot_general(k, q, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if keep is not None:
            s = jnp.where(keep(s.shape), s, NEG_INF)
        p = jnp.exp(s - lse)        # a masked score underflows to 0
        dv = jax.lax.dot_general(p.astype(do.dtype), do, (((1, ), (0, )), ((), ())),
                                 preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(v, do, (((1, ), (1, )), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk = jax.lax.dot_general(ds, q, (((1, ), (0, )), ((), ())),
                                 preferred_element_type=jnp.float32)
        dq = jax.lax.dot_general(ds, k, (((0, ), (0, )), ((), ())),
                                 preferred_element_type=jnp.float32)
        return dv, dk, dq

    @pl.when((qi == 0) & (t == 0))
    def _init_clean():
        dkc_acc[:] = jnp.zeros_like(dkc_acc)
        dvc_acc[:] = jnp.zeros_like(dvc_acc)

    @pl.when(t == 0)
    def _own_block():
        def keep(shape):
            k_loc = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            q_loc = jax.lax.broadcasted_iota(jnp.int32, shape, 1) % block_q
            return _bd_own_block(q_loc, k_loc, block_length)

        dv, dk, dq = tile_grads(
            kd_ref[0], vd_ref[0], q_ref[0, 0].reshape(half, d),
            do_ref[0, 0].reshape(half, d), lse_ref[0, 0][:, :half],
            delta_ref[0, 0][:, :half], keep)
        dvn_ref[0] = dv.astype(dvn_ref.dtype)
        dkn_ref[0] = dk.astype(dkn_ref.dtype)
        dq_acc[:half] = dq
        dq_acc[half:] = jnp.zeros((half, d), dq_acc.dtype)

    def _clean(masked):
        def keep(shape):
            k_pos = (t - 1) * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            row = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            return k_pos < _bd_clean_limit(row, qi * block_q, block_q, half,
                                           block_length)

        dv, dk, dq = tile_grads(
            kc_ref[0], vc_ref[0], q_ref[0].reshape(2 * half, d),
            do_ref[0].reshape(2 * half, d), lse_ref[0, 0], delta_ref[0, 0],
            keep if masked else None)
        dvc_acc[t - 1] += dv
        dkc_acc[t - 1] += dk
        dq_acc[:] += dq

    _bd_clean_step(t, qi, block_q, block_k, _clean)

    @pl.when(t == steps - 1)
    def _finalize_dq():
        dq_ref[0] = dq_acc[:].reshape(2, g, bq, d).astype(dq_ref.dtype)

    @pl.when((qi == num_q - 1) & (t >= 1))
    def _finalize_clean():
        dkc_ref[0] = dkc_acc[t - 1].astype(dkc_ref.dtype)
        dvc_ref[0] = dvc_acc[t - 1].astype(dvc_ref.dtype)


def _bd_flash_bwd(res, g_out, scale, block_length, block_q, block_k, interpret):
    from .kernel_dispatch import bdattn_vmem_bytes
    q, k, v, o, lse = res
    B, seq, H, KV, G, D = _bd_check(q, k, block_length, block_q, block_k)
    num_q, num_k = seq // block_q, seq // block_k
    steps = 1 + num_k
    qg, kt, vt = _bd_regroup(q, k, v, seq)
    dog, _, _ = _bd_regroup(g_out, k, v, seq)
    og, _, _ = _bd_regroup(o, k, v, seq)
    delta = jnp.sum(dog.astype(jnp.float32) * og.astype(jnp.float32), axis=-1,
                    keepdims=True)                      # [B*KV, 2, G, L, 1]

    def rows(x):
        """-> [B*KV, q tiles, 1, 2 * G * BQ]: the columns of a transposed
        score tile, in the order of the folded query rows."""
        return (x.reshape(B * KV, 2, G, num_q, block_q).transpose(0, 3, 1, 2, 4)
                .reshape(B * KV, num_q, 1, 2 * G * block_q))

    q_spec, own_spec, clean_spec = _bd_specs(G, D, seq, block_q, block_k)
    r_spec = pl.BlockSpec((1, 1, 1, 2 * G * block_q), lambda b, i, t: (b, i, 0, 0))
    # a clean tile's dK/dV are whole, and written, in the last query tile's
    # sweep; until then the map names tile 0, which that sweep writes first
    dclean_spec = pl.BlockSpec(
        (1, block_k, D),
        lambda b, i, t: (b, jnp.where(i == num_q - 1, jnp.maximum(t - 1, 0), 0), 0))
    half_kv = jax.ShapeDtypeStruct((B * KV, seq, D), k.dtype)
    dq, dkn, dvn, dkc, dvc = pl.pallas_call(
        functools.partial(_bd_bwd_kernel, scale=scale, block_length=block_length,
                          block_q=block_q, block_k=block_k, steps=steps,
                          num_q=num_q),
        grid=(B * KV, num_q, steps),
        in_specs=[q_spec, own_spec, own_spec, clean_spec, clean_spec, q_spec,
                  r_spec, r_spec],
        out_specs=[q_spec, own_spec, own_spec, dclean_spec, dclean_spec],
        out_shape=[jax.ShapeDtypeStruct(qg.shape, q.dtype), half_kv, half_kv,
                   half_kv, half_kv],
        scratch_shapes=[pltpu.VMEM((2 * G * block_q, D), jnp.float32),
                        pltpu.VMEM((num_k, block_k, D), jnp.float32),
                        pltpu.VMEM((num_k, block_k, D), jnp.float32)],
        compiler_params=_compiler_params(bdattn_vmem_bytes(
            "bwd", G, D, q.dtype.itemsize, block_q, block_k, seq)),
        interpret=interpret,
        name="bdattn_bwd",
    )(qg, kt, vt, kt, vt, dog, rows(lse), rows(delta))

    def natural(noisy, clean):
        return (jnp.concatenate([noisy, clean], axis=1)
                .reshape(B, KV, 2 * seq, D).transpose(0, 2, 1, 3))

    return _bd_ungroup(dq, B, KV), natural(dkn, dkc), natural(dvn, dvc)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _bd_attention(q, k, v, scale, block_length, fwd_blocks, bwd_blocks, interpret):
    return _bd_flash_fwd(q, k, v, scale, block_length, *fwd_blocks, interpret)[0]


def _bd_fwd_rule(q, k, v, scale, block_length, fwd_blocks, bwd_blocks, interpret):
    o, lse = _name_residuals(*_bd_flash_fwd(
        q, k, v, scale, block_length, *fwd_blocks, interpret))
    return o, (q, k, v, o, lse)


def _bd_bwd_rule(scale, block_length, fwd_blocks, bwd_blocks, interpret, res, g):
    return _bd_flash_bwd(res, g, scale, block_length, *bwd_blocks, interpret)


_bd_attention.defvjp(_bd_fwd_rule, _bd_bwd_rule)
# its own jit frame, so that the calls read ``%bdattn_fwd.N`` /
# ``%bdattn_bwd.N`` under ``jax.grad`` (see ``_flash_attention_call``)
_bd_attention_call = jax.jit(_bd_attention, static_argnums=(3, 4, 5, 6, 7))


def block_diffusion_attention(q, k, v, block_length: int,
                              scale: Optional[float] = None,
                              blocks_fwd: Optional[tuple] = None,
                              blocks_bwd: Optional[tuple] = None,
                              force_pallas: Optional[bool] = None,
                              interpret: bool = False):
    """Attention under the block-diffusion training mask; q [B, 2L, H, D],
    k/v [B, 2L, KV, D] (GQA native), the noised copy of each sequence in
    positions [0, L) and the clean copy in [L, 2L).

    On a TPU (or with ``interpret=True`` anywhere) the kernels ``bdattn_fwd``
    and ``bdattn_bwd`` run, with (query, key) tiles from
    ``kernel_dispatch.choose_block_diffusion_blocks`` unless given; a shape
    they cannot tile, or whose clean keys' float32 dK and dV pass the
    backward's VMEM cap, is refused with the reason. Elsewhere the XLA path
    runs both legs under ``block_diffusion_mask``."""
    from . import kernel_dispatch as kd

    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    seq = q.shape[1] // 2
    if not (use_pallas(force_pallas) or interpret):
        return _xla_attention(q, k, v, scale, False,
                              mask=jnp.asarray(block_diffusion_mask(seq, block_length)))
    sig = kd.make_sig(q.shape, k.shape[2], k.shape[1], q.dtype, False, None, None,
                      pattern=f"bd{block_length}")
    fwd = tuple(blocks_fwd or kd.choose_block_diffusion_blocks(sig, "fwd", block_length))
    bwd = tuple(blocks_bwd or kd.choose_block_diffusion_blocks(sig, "bwd", block_length))
    return _bd_attention_call(q, k, v, scale, int(block_length), fwd, bwd, interpret)



registry.register("flash_attention", "pallas", True)
