"""Fused optimizer step kernels.

TPU-native equivalent of reference ``csrc/adam/multi_tensor_adam.cu`` (+
``fused_adam_frontend.cpp``): the whole Adam update — bias-corrected moments,
parameter write — in one pass over memory. Under XLA the optax chain already
fuses into a couple of loops, so the Pallas kernel's value is guaranteeing
the single-pass HBM traffic pattern (one read of p/m/v/g, one write of
p/m/v) regardless of surrounding graph.

The "multi-tensor" aspect of the reference (kernel launch amortization over
many small tensors) is native here: the caller flattens the param pytree into
one ravelled buffer per state (jnp.concatenate), the kernel runs over blocks.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .registry import registry, use_pallas


def _launch_flat(kernel, tensors, scalars, out_dtypes, interpret):
    """Run `kernel` over flat [N] buffers reshaped to a (rows, 2048) layout.

    The Mosaic tiling contract wants the last two block dims ÷(8, 128):
    lanes=2048 (16×128), row tiles of up to 64 — 7 live (tile, 2048) fp32
    buffers × double buffering fit ~16MB VMEM. Scalars ride in SMEM.
    Returns the outputs as flat [N] buffers.
    """
    n = tensors[0].shape[0]
    lanes = 2048
    pad = (-n) % lanes
    def _pad(x):
        return jnp.pad(x, (0, pad)) if pad else x
    t2 = [_pad(t).reshape(-1, lanes) for t in tensors]
    rows = t2[0].shape[0]
    tile = min(64, rows) if rows % 8 == 0 else rows
    while rows % tile != 0:
        tile //= 2
    tile = max(tile, 1)

    blk = lambda i: (i, 0)
    tile_spec = pl.BlockSpec((tile, lanes), blk)
    scalar_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    outs = pl.pallas_call(
        kernel,
        grid=(rows // tile, ),
        in_specs=[tile_spec] * len(t2) + [scalar_spec] * len(scalars),
        out_specs=[tile_spec] * len(out_dtypes),
        out_shape=[jax.ShapeDtypeStruct(t2[0].shape, dt) for dt in out_dtypes],
        interpret=interpret,
    )(*t2, *scalars)
    return tuple(o.reshape(-1)[:n] for o in outs)


def _adam_kernel(p_ref, g_ref, m_ref, v_ref, lr_ref, step_ref,
                 po_ref, mo_ref, vo_ref, *, b1, b2, eps, wd):
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    m = b1 * m_ref[:] + (1 - b1) * g
    v = b2 * v_ref[:] + (1 - b2) * g * g
    step = step_ref[0]
    # b**step as exp(step*log(b)): Mosaic has no powf lowering
    bc1 = 1 - jnp.exp(step * np.log(b1))
    bc2 = 1 - jnp.exp(step * np.log(b2))
    update = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    if wd:
        update = update + wd * p
    lr = lr_ref[0]
    po_ref[:] = (p - lr * update).astype(po_ref.dtype)
    mo_ref[:] = m
    vo_ref[:] = v


def fused_adam_step(params, grads, m, v, lr, step,
                    b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
                    weight_decay: float = 0.0, block: int = 8 * 2048,
                    force_pallas: Optional[bool] = None,
                    interpret: bool = False) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One AdamW step over flat fp32 buffers [N]. Returns (params, m, v).

    `lr` scalar, `step` the 1-based step count (bias correction).
    """
    n = params.shape[0]
    lr_arr = jnp.asarray([lr], jnp.float32).reshape(1)
    step_arr = jnp.asarray([step], jnp.float32).reshape(1)

    if not (use_pallas(force_pallas) or interpret):
        g = grads.astype(jnp.float32)
        m_n = b1 * m + (1 - b1) * g
        v_n = b2 * v + (1 - b2) * g * g
        bc1 = 1 - b1 ** step_arr[0]
        bc2 = 1 - b2 ** step_arr[0]
        upd = (m_n / bc1) / (jnp.sqrt(v_n / bc2) + eps)
        if weight_decay:
            upd = upd + weight_decay * params.astype(jnp.float32)
        return (params - lr_arr[0] * upd).astype(params.dtype), m_n, v_n

    kernel = functools.partial(_adam_kernel, b1=b1, b2=b2, eps=eps, wd=weight_decay)
    return _launch_flat(kernel, (params, grads, m, v), (lr_arr, step_arr),
                        (params.dtype, jnp.float32, jnp.float32), interpret)


def _lion_kernel(p_ref, g_ref, m_ref, lr_ref, po_ref, mo_ref, *, b1, b2, wd):
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    m = m_ref[:]
    u = jnp.sign(b1 * m + (1 - b1) * g)
    if wd:
        u = u + wd * p
    po_ref[:] = (p - lr_ref[0] * u).astype(po_ref.dtype)
    mo_ref[:] = b2 * m + (1 - b2) * g


def fused_lion_step(params, grads, m, lr,
                    b1: float = 0.9, b2: float = 0.99,
                    weight_decay: float = 0.0,
                    force_pallas: Optional[bool] = None,
                    interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """One Lion step over flat buffers [N]. Returns (params, m).

    optax.lion semantics (sign of the b1-interpolated momentum, decoupled
    weight decay); single-pass HBM traffic like the reference's
    ``csrc/lion/multi_tensor_lion.cu``.
    """
    n = params.shape[0]
    lr_arr = jnp.asarray([lr], jnp.float32).reshape(1)

    if not (use_pallas(force_pallas) or interpret):
        g = grads.astype(jnp.float32)
        u = jnp.sign(b1 * m + (1 - b1) * g)
        if weight_decay:
            u = u + weight_decay * params.astype(jnp.float32)
        return (params - lr_arr[0] * u).astype(params.dtype), b2 * m + (1 - b2) * g

    kernel = functools.partial(_lion_kernel, b1=b1, b2=b2, wd=weight_decay)
    return _launch_flat(kernel, (params, grads, m), (lr_arr, ),
                        (params.dtype, jnp.float32), interpret)


def _lamb_update_kernel(g_ref, m_ref, v_ref, step_ref,
                        uo_ref, mo_ref, vo_ref, *, b1, b2, eps):
    g = g_ref[:].astype(jnp.float32)
    m = b1 * m_ref[:] + (1 - b1) * g
    v = b2 * v_ref[:] + (1 - b2) * g * g
    step = step_ref[0]
    bc1 = 1 - jnp.exp(step * np.log(b1))
    bc2 = 1 - jnp.exp(step * np.log(b2))
    uo_ref[:] = (m / bc1) / (jnp.sqrt(v / bc2) + eps)
    mo_ref[:] = m
    vo_ref[:] = v


def fused_lamb_step(params, grads, m, v, lr, step,
                    b1: float = 0.9, b2: float = 0.999, eps: float = 1e-6,
                    weight_decay: float = 0.0,
                    segments: Optional[Tuple[int, ...]] = None,
                    force_pallas: Optional[bool] = None,
                    interpret: bool = False) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One LAMB step over flat buffers [N]. Returns (params, m, v).

    The reference's ``csrc/lamb/fused_lamb_cuda_kernel.cu`` runs two passes:
    the Adam-shaped update plus per-tensor reduction, then the trust-ratio
    scaled write. Same structure here: a Pallas pass produces the
    bias-corrected update and new moments (one read of g/m/v, one write of
    u/m/v); the per-tensor trust ratio ||p||/||u + wd*p|| is a pair of XLA
    segment reductions fused into the scaled parameter write.

    `segments`: tensor boundary offsets into the flat buffer (e.g.
    (0, n1, n1+n2, ..., N)); trust ratios are computed per segment, matching
    the reference's per-tensor launches. Default: one segment (whole buffer).
    """
    n = params.shape[0]
    step_arr = jnp.asarray([step], jnp.float32).reshape(1)

    if use_pallas(force_pallas) or interpret:
        kernel = functools.partial(_lamb_update_kernel, b1=b1, b2=b2, eps=eps)
        u, m_n, v_n = _launch_flat(kernel, (grads, m, v), (step_arr, ),
                                   (jnp.float32, jnp.float32, jnp.float32), interpret)
    else:
        g = grads.astype(jnp.float32)
        m_n = b1 * m + (1 - b1) * g
        v_n = b2 * v + (1 - b2) * g * g
        bc1 = 1 - b1 ** step_arr[0]
        bc2 = 1 - b2 ** step_arr[0]
        u = (m_n / bc1) / (jnp.sqrt(v_n / bc2) + eps)

    pf = params.astype(jnp.float32)
    if weight_decay:
        u = u + weight_decay * pf

    if segments is None or len(segments) <= 2:
        pn = jnp.sqrt(jnp.sum(pf * pf))
        un = jnp.sqrt(jnp.sum(u * u))
        trust = jnp.where((pn > 0) & (un > 0), pn / jnp.maximum(un, 1e-30), 1.0)
    else:
        seg_ids = np.zeros(n, np.int32)
        for i in range(1, len(segments) - 1):
            seg_ids[segments[i]:] += 1
        nseg = len(segments) - 1
        seg_ids = jnp.asarray(seg_ids)
        pn = jnp.sqrt(jax.ops.segment_sum(pf * pf, seg_ids, num_segments=nseg))
        un = jnp.sqrt(jax.ops.segment_sum(u * u, seg_ids, num_segments=nseg))
        trust_seg = jnp.where((pn > 0) & (un > 0), pn / jnp.maximum(un, 1e-30), 1.0)
        trust = trust_seg[seg_ids]

    lr_arr = jnp.asarray([lr], jnp.float32).reshape(1)
    return (pf - lr_arr[0] * trust * u).astype(params.dtype), m_n, v_n


registry.register("fused_adam", "pallas", True)
registry.register("fused_lion", "pallas", True)
registry.register("fused_lamb", "pallas", True)
