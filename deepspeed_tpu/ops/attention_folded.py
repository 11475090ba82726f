"""Head-folded flash attention (flag-gated experiment, ``DS_TPU_FLASH_FOLDED=1``).

Same math as ``ops/attention.py``'s kernels, restructured the way the 8/1
xprof trace demands: that trace showed the per-head flash kernels at 70% of
train-step device time for ~6% of model FLOPs — per-grid-step fixed cost
(~50us) over ``B*KV x num_q x num_kv`` tiny steps. Here ONE grid step
processes ALL kv heads (static in-kernel unroll, the restructure that fixed
the paged decode kernel):

- grid ``(B, num_q, num_kv)`` — KV leaves the grid entirely;
- q/o/do stay in their NATURAL ``[B, S, H, D]`` layout (block minor dims
  (H, D): sublane mult-of-8-or-equal, lane == array dim — Mosaic-legal; the
  per-head path also paid 6 host-side transposes per call in ``_regroup``,
  which all disappear);
- k/v fold to ``[B, S, KV*D]`` (free reshape; lane == array dim blocks),
  per-head slices are STATIC lane offsets inside the kernel;
- positional masks build once per step and are shared across heads; the
  interior/edge specialization (full blocks skip the mask chain) carries
  over.

The proven per-head kernels stay the default until this variant has run on
real silicon (a chip-session rung A/Bs them); interpret-mode fuzz pins
numerics equality either way.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LSE_MASKED = -1e30  # matches attention.py's fully-masked-row marker


def _positions(ng_shape, block_q, block_k, qi, ki, groups):
    """(q_pos, k_pos) [NG, BK] grids for one tile; rows are q-major
    (row = q_row * G + g)."""
    r = jax.lax.broadcasted_iota(jnp.int32, ng_shape, 0)
    q_pos = qi * block_q + r // groups
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, ng_shape, 1)
    return q_pos, k_pos


def _tile_conds(causal, window, block_q, block_k, qi, ki):
    """(live, interior) for the (qi, ki) tile — live: any pair unmasked;
    interior: every pair unmasked (skip the mask chain)."""
    live = True
    interior = True
    if causal:
        live = ki * block_k <= qi * block_q + block_q - 1
        interior = ki * block_k + block_k - 1 <= qi * block_q
    if window is not None:
        live = live & (ki * block_k + block_k - 1
                       >= qi * block_q - (window - 1))
        interior = interior & (
            qi * block_q + block_q - 1 - ki * block_k <= window - 1)
    return live, interior


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_s, l_s,
                *, scale, causal, block_q, block_k, num_kv, num_heads: int,
                groups: int, window=None, softcap=None):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    D = q_ref.shape[-1]
    bq = q_ref.shape[1]
    G = groups
    KV = num_heads // G
    ng = bq * G

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    def _compute(masked):
        if masked and (causal or window is not None):
            q_pos, k_pos = _positions((ng, block_k), block_q, block_k,
                                      qi, ki, G)
            kill = k_pos > q_pos if causal else jnp.zeros((ng, block_k), bool)
            if window is not None:
                kill = kill | (q_pos - k_pos >= window)
        for h in range(KV):  # static unroll: one k/v DMA, all heads
            q = q_ref[0, :, h * G:(h + 1) * G, :].reshape(ng, D)
            k = k_ref[0, :, h * D:(h + 1) * D]  # [BK, D] static lane slice
            v = v_ref[0, :, h * D:(h + 1) * D]
            s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if softcap is not None:
                from .attention import softcap_scores
                s = softcap_scores(s, softcap)
            if masked and (causal or window is not None):
                s = jnp.where(kill, NEG_INF, s)
            r = slice(h * ng, (h + 1) * ng)
            m_prev, l_prev = m_s[r], l_s[r]
            m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            m_safe = jnp.where(m_cur <= NEG_INF, 0.0, m_cur)
            p = jnp.exp(s - m_safe)
            if masked:
                p = jnp.where(s <= NEG_INF, 0.0, p)
            corr = jnp.exp(jnp.where(m_prev <= NEG_INF, NEG_INF,
                                     m_prev - m_safe))
            l_cur = l_prev * corr + p.sum(axis=-1, keepdims=True)
            pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                     (((1, ), (0, )), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc[r] = acc[r] * corr + pv
            m_s[r] = m_cur
            l_s[r] = l_cur

    live, interior = _tile_conds(causal, window, block_q, block_k, qi, ki)
    if live is True:
        _compute(masked=False)
    else:
        @pl.when(live & interior)
        def _():
            _compute(masked=False)

        @pl.when(live & jnp.logical_not(interior))
        def _():
            _compute(masked=True)

    @pl.when(ki == num_kv - 1)
    def _finalize():
        for h in range(KV):
            r = slice(h * ng, (h + 1) * ng)
            l = l_s[r]
            safe_l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, :, h * G:(h + 1) * G, :] = \
                (acc[r] / safe_l).reshape(bq, G, D).astype(o_ref.dtype)
            m_safe = jnp.where(m_s[r] <= NEG_INF, 0.0, m_s[r])
            lse = jnp.where(l == 0.0, LSE_MASKED, m_safe + jnp.log(safe_l))
            lse_ref[0, :, h * G:(h + 1) * G, :] = lse.reshape(bq, G, 1)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, scale, causal, block_q, block_k, num_kv,
               num_heads: int, groups: int, window=None, softcap=None):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    D = q_ref.shape[-1]
    bq = q_ref.shape[1]
    G = groups
    KV = num_heads // G
    ng = bq * G

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute(masked):
        if masked and (causal or window is not None):
            q_pos, k_pos = _positions((ng, block_k), block_q, block_k,
                                      qi, ki, G)
            kill = k_pos > q_pos if causal else jnp.zeros((ng, block_k), bool)
            if window is not None:
                kill = kill | (q_pos - k_pos >= window)
        for h in range(KV):
            q = q_ref[0, :, h * G:(h + 1) * G, :].reshape(ng, D)
            do = do_ref[0, :, h * G:(h + 1) * G, :].reshape(ng, D)
            lse = lse_ref[0, :, h * G:(h + 1) * G, :].reshape(ng, 1)
            delta = delta_ref[0, :, h * G:(h + 1) * G, :].reshape(ng, 1)
            k = k_ref[0, :, h * D:(h + 1) * D]
            v = v_ref[0, :, h * D:(h + 1) * D]
            s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if softcap is not None:
                t = jnp.tanh(s / softcap)
                s = softcap * t
            if masked and (causal or window is not None):
                s = jnp.where(kill, NEG_INF, s)
            p = jnp.exp(s - lse)
            if masked:
                p = jnp.where(s <= NEG_INF, 0.0, p)
            dp = jax.lax.dot_general(do, v, (((1, ), (1, )), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * scale
            if softcap is not None:
                ds = ds * (1.0 - t * t)
            r = slice(h * ng, (h + 1) * ng)
            dq_acc[r] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1, ), (0, )), ((), ())),
                preferred_element_type=jnp.float32)

    live, interior = _tile_conds(causal, window, block_q, block_k, qi, ki)
    if live is True:
        _compute(masked=False)
    else:
        @pl.when(live & interior)
        def _():
            _compute(masked=False)

        @pl.when(live & jnp.logical_not(interior))
        def _():
            _compute(masked=True)

    @pl.when(ki == num_kv - 1)
    def _finalize():
        for h in range(KV):
            r = slice(h * ng, (h + 1) * ng)
            dq_ref[0, :, h * G:(h + 1) * G, :] = \
                dq_acc[r].reshape(bq, G, D).astype(dq_ref.dtype)


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dk_acc, dv_acc,
                 *, scale, causal, block_q, block_k, num_q,
                 num_heads: int, groups: int, window=None, softcap=None):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    D = q_ref.shape[-1]
    bq = q_ref.shape[1]
    G = groups
    KV = num_heads // G
    ng = bq * G

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute(masked):
        if masked and (causal or window is not None):
            q_pos, k_pos = _positions((ng, block_k), block_q, block_k,
                                      qi, ki, G)
            kill = k_pos > q_pos if causal else jnp.zeros((ng, block_k), bool)
            if window is not None:
                kill = kill | (q_pos - k_pos >= window)
        for h in range(KV):
            q = q_ref[0, :, h * G:(h + 1) * G, :].reshape(ng, D)
            do = do_ref[0, :, h * G:(h + 1) * G, :].reshape(ng, D)
            lse = lse_ref[0, :, h * G:(h + 1) * G, :].reshape(ng, 1)
            delta = delta_ref[0, :, h * G:(h + 1) * G, :].reshape(ng, 1)
            k = k_ref[0, :, h * D:(h + 1) * D]
            v = v_ref[0, :, h * D:(h + 1) * D]
            s = jax.lax.dot_general(q, k, (((1, ), (1, )), ((), ())),
                                    preferred_element_type=jnp.float32) * scale
            if softcap is not None:
                t = jnp.tanh(s / softcap)
                s = softcap * t
            if masked and (causal or window is not None):
                s = jnp.where(kill, NEG_INF, s)
            p = jnp.exp(s - lse)
            if masked:
                p = jnp.where(s <= NEG_INF, 0.0, p)
            c = slice(h * D, (h + 1) * D)  # this head's lane columns
            # dv += p^T @ do (sums the G query heads: GQA reduce); dk += ds^T @ q
            dv_acc[:, c] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((0, ), (0, )), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, (((1, ), (1, )), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * scale
            if softcap is not None:
                ds = ds * (1.0 - t * t)
            dk_acc[:, c] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0, ), (0, )), ((), ())),
                preferred_element_type=jnp.float32)

    # dk/dv tile liveness mirrors the per-head kernel's kv-major view
    live = True
    interior = True
    if causal:
        live = qi * block_q + block_q - 1 >= ki * block_k
        interior = ki * block_k + block_k - 1 <= qi * block_q
    if window is not None:
        live = live & (qi * block_q
                       <= ki * block_k + block_k - 1 + (window - 1))
        interior = interior & (
            qi * block_q + block_q - 1 - ki * block_k <= window - 1)
    if live is True:
        _compute(masked=False)
    else:
        @pl.when(live & interior)
        def _():
            _compute(masked=False)

        @pl.when(live & jnp.logical_not(interior))
        def _():
            _compute(masked=True)

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _shapes(q, k, block_q, block_k):
    B, Sq, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (
        f"seq lens ({Sq},{Sk}) must divide blocks ({block_q},{block_k})")
    return B, Sq, H, D, Sk, KV, G, block_q, block_k


def flash_fwd_folded(q, k, v, scale, causal, block_q, block_k, interpret,
                     window=None, softcap=None):
    B, Sq, H, D, Sk, KV, G, block_q, block_k = _shapes(q, k, block_q, block_k)
    num_q, num_kv = Sq // block_q, Sk // block_k
    kf = k.reshape(B, Sk, KV * D)
    vf = v.reshape(B, Sk, KV * D)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, num_kv=num_kv, num_heads=H, groups=G,
        window=window, softcap=softcap)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, num_q, num_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, H, D), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((1, block_k, KV * D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, KV * D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, H, D), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((1, block_q, H, 1), lambda b, i, j: (b, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sq, H, D), q.dtype),
            jax.ShapeDtypeStruct((B, Sq, H, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((H * block_q, D), jnp.float32),
            pltpu.VMEM((H * block_q, 1), jnp.float32),
            pltpu.VMEM((H * block_q, 1), jnp.float32),
        ],
        interpret=interpret,
        name="folded_flash_fwd",
    )(q, kf, vf)
    return out, lse


def flash_bwd_folded(q, k, v, lse, o, g_out, scale, causal, block_q, block_k,
                     interpret, window=None, softcap=None):
    B, Sq, H, D, Sk, KV, G, block_q, block_k = _shapes(q, k, block_q, block_k)
    num_q, num_kv = Sq // block_q, Sk // block_k
    kf = k.reshape(B, Sk, KV * D)
    vf = v.reshape(B, Sk, KV * D)
    delta = jnp.sum(g_out.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [B, Sq, H, 1]

    q_spec = pl.BlockSpec((1, block_q, H, D), lambda b, i, j: (b, i, 0, 0))
    k_spec = pl.BlockSpec((1, block_k, KV * D), lambda b, i, j: (b, j, 0))
    r_spec = pl.BlockSpec((1, block_q, H, 1), lambda b, i, j: (b, i, 0, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_kv=num_kv,
                          num_heads=H, groups=G, window=window,
                          softcap=softcap),
        grid=(B, num_q, num_kv),
        in_specs=[q_spec, k_spec, k_spec, q_spec, r_spec, r_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sq, H, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((H * block_q, D), jnp.float32)],
        interpret=interpret,
        name="folded_flash_dq",
    )(q, kf, vf, g_out, lse, delta)

    q_spec2 = pl.BlockSpec((1, block_q, H, D), lambda b, j, i: (b, i, 0, 0))
    k_spec2 = pl.BlockSpec((1, block_k, KV * D), lambda b, j, i: (b, j, 0))
    r_spec2 = pl.BlockSpec((1, block_q, H, 1), lambda b, j, i: (b, i, 0, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkdv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_q=num_q,
                          num_heads=H, groups=G, window=window,
                          softcap=softcap),
        grid=(B, num_kv, num_q),
        in_specs=[q_spec2, k_spec2, k_spec2, q_spec2, r_spec2, r_spec2],
        out_specs=[k_spec2, k_spec2],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sk, KV * D), k.dtype),
            jax.ShapeDtypeStruct((B, Sk, KV * D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, KV * D), jnp.float32),
            pltpu.VMEM((block_k, KV * D), jnp.float32),
        ],
        interpret=interpret,
        name="folded_flash_dkdv",
    )(q, kf, vf, g_out, lse, delta)
    return dq, dk.reshape(B, Sk, KV, D), dv.reshape(B, Sk, KV, D)


from .registry import registry  # noqa: E402

registry.register("flash_attention_folded", "pallas",
                  True, "head-folded flash variant (DS_TPU_FLASH_FOLDED=1): "
                  "all KV heads per grid step, natural [B,S,H,D] layouts")
