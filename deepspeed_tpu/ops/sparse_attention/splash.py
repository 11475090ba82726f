"""Pallas splash attention: block-sparse attention that SKIPS masked tiles.

Reference: the Triton block-sparse kernels
(``deepspeed/ops/sparse_attention/matmul.py`` SDD/DSD — compute only the
blocks present in the layout — and ``softmax.py`` operating on the packed
block values). The dense-mask fallback in ``sparse_self_attention.py``
computes all S² scores and throws most away; this kernel's grid is
``(batch*heads, q_blocks, max_active)`` where ``max_active`` is the widest
row of the layout — compute AND HBM traffic scale with the number of ACTIVE
blocks, not S².

Mechanism (same scalar-prefetch idiom as ``ops/paged_attention.py``): the
static [H, nb, nb] layout compiles to a block table ``[H, nb, A]`` of active
k-block indices plus per-row counts; the k/v BlockSpec index_map reads the
table (scalar prefetch) so each grid step streams exactly one ACTIVE k/v
block; trailing padded steps are skipped with ``pl.when``. Online softmax
accumulators live in VMEM scratch across the active sweep.

Backward is sparse too (reference parity: the Triton SDD/DSD matmuls of
``matmul.py:63`` are differentiable through the sparse path): a dq kernel
sweeps the same block table as the forward, and a dk/dv kernel sweeps the
TRANSPOSED table (for each k-block, the q-blocks that attend to it), both
recomputing per-tile probabilities from the forward's saved logsumexp — so
backward compute and HBM traffic also scale with active blocks, not S².
"""

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def build_block_table(layout: np.ndarray):
    """[H, nb, nb] 0/1 layout → (table [H, nb, A] int32, counts [H, nb] int32).

    A = widest active row; padding entries point at block 0 and are skipped
    via the counts.
    """
    layout = np.asarray(layout).astype(bool)
    H, nb, nb2 = layout.shape
    assert nb == nb2, layout.shape
    counts = layout.sum(-1).astype(np.int32)
    A = max(int(counts.max()), 1)
    table = np.zeros((H, nb, A), dtype=np.int32)
    for h in range(H):
        for qb in range(nb):
            idx = np.nonzero(layout[h, qb])[0]
            table[h, qb, :len(idx)] = idx
    return table, counts


def _splash_kernel(table_ref, count_ref, q_ref, k_ref, v_ref, o_ref, *rest,
                   scale, num_active, nheads_layout, with_lse=False):
    if with_lse:
        lse_ref, acc, m_s, l_s = rest
    else:
        acc, m_s, l_s = rest
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ai = pl.program_id(2)
    # bh = batch*H + h; rem by the LAYOUT head count handles both per-head
    # layouts (H) and a single broadcast layout (1)
    h = jax.lax.rem(bh, nheads_layout)

    @pl.when(ai == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    @pl.when(ai < count_ref[h, qi])
    def _compute():
        # operands stay in the input dtype: the MXU fast path is
        # bf16 x bf16 with fp32 accumulation (preferred_element_type);
        # softmax math runs on the fp32 accumulator outputs
        q = q_ref[0]  # [block, D]
        k = k_ref[0]  # [block, D]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        m_prev, l_prev = m_s[:, 0], l_s[:, 0]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_cur[:, None])
        corr = jnp.exp(jnp.where(m_prev <= NEG_INF, NEG_INF, m_prev - m_cur))
        l_s[:, 0] = l_prev * corr + p.sum(axis=-1)
        pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1, ), (0, )), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc[:] = acc[:] * corr[:, None] + pv
        m_s[:, 0] = m_cur

    @pl.when(ai == num_active - 1)
    def _finalize():
        l = l_s[:, 0]
        safe_l = jnp.where(l == 0.0, 1.0, l)  # rows with no visible block → 0
        o_ref[0] = (acc[:] / safe_l[:, None]).astype(o_ref.dtype)
        if with_lse:
            # +BIG for empty rows so backward's exp(s - lse) underflows to
            # exactly 0 (their grads must be 0, not NaN). lse rides a
            # [BH, S, 1] array: Mosaic requires the last two block dims be
            # (mult-of-8, mult-of-128) or equal to the array dims — a 2-D
            # (1, block) spec over [BH, S] is unlowerable.
            lse_ref[0] = jnp.where(l == 0.0, -NEG_INF,
                                   m_s[:, 0] + jnp.log(safe_l))[:, None]


def _splash_fwd(q, k, v, table, counts, block, scale, interpret,
                with_lse=False):
    B, H, S, D = q.shape
    nb = S // block
    A = table.shape[-1]
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, S, D)
    vf = v.reshape(B * H, S, D)

    kernel = functools.partial(_splash_kernel, scale=scale, num_active=A,
                               nheads_layout=table.shape[0],
                               with_lse=with_lse)
    q_spec = pl.BlockSpec((1, block, D), lambda b, qi, ai, tbl, cnt: (b, qi, 0))
    kv_spec = pl.BlockSpec((1, block, D),
                           lambda b, qi, ai, tbl, cnt:
                           (b, tbl[jax.lax.rem(b, tbl.shape[0]), qi, ai], 0))
    out_specs = [q_spec]
    out_shape = [jax.ShapeDtypeStruct((B * H, S, D), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, block, 1),
                                      lambda b, qi, ai, tbl, cnt: (b, qi, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B * H, S, 1), jnp.float32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B * H, nb, A),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=out_specs if with_lse else out_specs[0],
        scratch_shapes=[
            pltpu.VMEM((block, D), jnp.float32),
            pltpu.VMEM((block, 1), jnp.float32),
            pltpu.VMEM((block, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape if with_lse else out_shape[0],
        interpret=interpret,
        name="splash_fwd",
    )(jnp.asarray(table), jnp.asarray(counts), qf, kf, vf)
    if with_lse:
        o, lse = out
        return o.reshape(B, H, S, D), lse
    return out.reshape(B, H, S, D)


def _splash_dq_kernel(table_ref, count_ref, q_ref, k_ref, v_ref, do_ref,
                      lse_ref, delta_ref, dq_ref, acc, *,
                      scale, num_active, nheads_layout):
    """dQ sweep — same block table as forward: for each q-block, iterate its
    active k-blocks; P is recomputed per tile from the saved logsumexp
    (standard flash backward; reference matmul.py SDD backward)."""
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ai = pl.program_id(2)
    h = jax.lax.rem(bh, nheads_layout)

    @pl.when(ai == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)

    @pl.when(ai < count_ref[h, qi])
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse_ref[0])           # lse block is [block, 1]
        dp = jax.lax.dot_general(do, v, (((1, ), (1, )), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale  # delta block is [block, 1]
        acc[:] += jax.lax.dot_general(ds.astype(k.dtype), k,
                                      (((1, ), (0, )), ((), ())),
                                      preferred_element_type=jnp.float32)

    @pl.when(ai == num_active - 1)
    def _finalize():
        dq_ref[0] = acc[:].astype(dq_ref.dtype)


def _splash_dkv_kernel(tableT_ref, countT_ref, q_ref, k_ref, v_ref, do_ref,
                       lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                       scale, num_active, nheads_layout):
    """dK/dV sweep — TRANSPOSED block table: for each k-block, iterate the
    q-blocks that attend to it (reference matmul.py DSD backward's
    transposed layout)."""
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    ai = pl.program_id(2)
    h = jax.lax.rem(bh, nheads_layout)

    @pl.when(ai == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(ai < countT_ref[h, ki])
    def _compute():
        q = q_ref[0]   # [block_q, D] — the ai-th active q-block for this k
        k = k_ref[0]   # [block_k, D]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse_ref[0])                   # [bq, bk]; lse [bq, 1]
        dv_acc[:] += jax.lax.dot_general(p.astype(do.dtype), do,
                                         (((0, ), (0, )), ((), ())),
                                         preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1, ), (1, )), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0]) * scale           # [bq, bk]
        dk_acc[:] += jax.lax.dot_general(ds.astype(q.dtype), q,
                                         (((0, ), (0, )), ((), ())),
                                         preferred_element_type=jnp.float32)

    @pl.when(ai == num_active - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _splash_bwd(q, k, v, o, lse, g, table, counts, tableT, countsT,
                block, scale, interpret):
    """Sparse backward: dq over the forward table, dk/dv over the transposed
    table. delta = rowsum(dO ∘ O) (the flash-backward correction term) is a
    cheap elementwise pass left to XLA."""
    B, H, S, D = q.shape
    BH = B * H
    nb = S // block
    qf, kf, vf = (t.reshape(BH, S, D) for t in (q, k, v))
    dof = g.reshape(BH, S, D)
    # [BH, S, 1]: row-wise scalars ride a trailing singleton so their block
    # spec's last two dims (block, 1) are Mosaic-legal
    delta = (dof.astype(jnp.float32)
             * o.reshape(BH, S, D).astype(jnp.float32)).sum(-1, keepdims=True)

    nheads_layout = table.shape[0]
    q_at = lambda b, i, ai, tbl, cnt: (b, i, 0)
    row_at = q_at
    tbl_at = lambda b, i, ai, tbl, cnt: (
        b, tbl[jax.lax.rem(b, tbl.shape[0]), i, ai], 0)
    tbl_row_at = tbl_at

    # ---- dq: grid (BH, q_block, active-k) ----
    A = table.shape[-1]
    dq = pl.pallas_call(
        functools.partial(_splash_dq_kernel, scale=scale, num_active=A,
                          nheads_layout=nheads_layout),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BH, nb, A),
            in_specs=[
                pl.BlockSpec((1, block, D), q_at),      # q
                pl.BlockSpec((1, block, D), tbl_at),    # k
                pl.BlockSpec((1, block, D), tbl_at),    # v
                pl.BlockSpec((1, block, D), q_at),      # do
                pl.BlockSpec((1, block, 1), row_at),    # lse
                pl.BlockSpec((1, block, 1), row_at),    # delta
            ],
            out_specs=pl.BlockSpec((1, block, D), q_at),
            scratch_shapes=[pltpu.VMEM((block, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        interpret=interpret,
        name="splash_dq",
    )(jnp.asarray(table), jnp.asarray(counts), qf, kf, vf, dof, lse, delta)

    # ---- dk/dv: grid (BH, k_block, active-q), transposed table ----
    At = tableT.shape[-1]
    dk, dv = pl.pallas_call(
        functools.partial(_splash_dkv_kernel, scale=scale, num_active=At,
                          nheads_layout=nheads_layout),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(BH, nb, At),
            in_specs=[
                pl.BlockSpec((1, block, D), tbl_at),    # q (active q-block)
                pl.BlockSpec((1, block, D), q_at),      # k (this k-block)
                pl.BlockSpec((1, block, D), q_at),      # v
                pl.BlockSpec((1, block, D), tbl_at),    # do
                pl.BlockSpec((1, block, 1), tbl_row_at),  # lse (per q row)
                pl.BlockSpec((1, block, 1), tbl_row_at),  # delta
            ],
            out_specs=[pl.BlockSpec((1, block, D), q_at),
                       pl.BlockSpec((1, block, D), q_at)],
            scratch_shapes=[pltpu.VMEM((block, D), jnp.float32),
                            pltpu.VMEM((block, D), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((BH, S, D), k.dtype),
                   jax.ShapeDtypeStruct((BH, S, D), v.dtype)],
        interpret=interpret,
        name="splash_dkdv",
    )(jnp.asarray(tableT), jnp.asarray(countsT), qf, kf, vf, dof, lse, delta)

    return (dq.reshape(B, H, S, D), dk.reshape(B, H, S, D),
            dv.reshape(B, H, S, D))


@functools.lru_cache(maxsize=64)
def _cached_splash_fn(layout_bytes: bytes, layout_shape, block: int,
                      scale: float, interpret: bool):
    """The block table build (host Python loop) and the custom_vjp closure
    are cached per (layout, block, scale) — eager serving loops must not
    rebuild them every call."""
    layout = np.frombuffer(layout_bytes, dtype=np.bool_).reshape(layout_shape)
    table, counts = build_block_table(layout)
    # transposed layout: which q-blocks touch each k-block (dk/dv sweep)
    tableT, countsT = build_block_table(layout.transpose(0, 2, 1))

    @jax.custom_vjp
    def _f(q, k, v):
        return _splash_fwd(q, k, v, table, counts, block, scale, interpret)

    def _f_fwd(q, k, v):
        o, lse = _splash_fwd(q, k, v, table, counts, block, scale, interpret,
                             with_lse=True)
        return o, (q, k, v, o, lse)

    def _f_bwd(res, g):
        q, k, v, o, lse = res
        return _splash_bwd(q, k, v, o, lse, g, table, counts, tableT, countsT,
                           block, scale, interpret)

    _f.defvjp(_f_fwd, _f_bwd)
    return _f


def splash_sparse_attention(q, k, v, layout: np.ndarray, block: int,
                            scale: Optional[float] = None,
                            interpret: bool = False):
    """Block-sparse attention via the splash kernel; differentiable through
    sparse Pallas backward kernels (dq over the forward block table, dk/dv
    over the transposed table).

    q,k,v: [batch, heads, seq, head_dim]; layout: [heads or 1, nb, nb]
    static (a 1-head layout broadcasts over heads, dense-path parity).
    """
    B, H, S, D = q.shape
    lay = np.ascontiguousarray(np.asarray(layout).astype(bool))
    if S % block != 0:
        raise ValueError(f"seq {S} not divisible by block {block}")
    if H % lay.shape[0] != 0:
        raise ValueError(f"q heads {H} not a multiple of layout heads {lay.shape[0]}")
    scale = scale if scale is not None else 1.0 / float(np.sqrt(D))
    f = _cached_splash_fn(lay.tobytes(), lay.shape, int(block), float(scale),
                          bool(interpret))
    return f(q, k, v)


def splash_flops(layout: np.ndarray, block: int, head_dim: int,
                 batch: int = 1) -> dict:
    """Analytic fwd FLOP accounting: the kernel's work is structurally
    proportional to ACTIVE blocks (grid × per-tile matmuls), vs nb² for the
    dense-mask path — the reduction the reference's Triton SDD/DSD delivers."""
    layout = np.asarray(layout).astype(bool)
    H, nb, _ = layout.shape
    active = int(layout.sum())
    per_block = 4 * block * block * head_dim  # QK^T + PV
    return {
        "active_blocks": active,
        "total_blocks": H * nb * nb,
        "sparse_flops": batch * active * per_block,
        "dense_flops": batch * H * nb * nb * per_block,
        "reduction": 1.0 - active / (H * nb * nb),
    }
