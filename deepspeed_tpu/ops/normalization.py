"""RMSNorm / LayerNorm — Pallas kernels with XLA fallback.

TPU-native equivalents of reference ``csrc/transformer/inference/csrc/
{rms_norm.cu, layer_norm.cu}`` (fused residual-add variants included). The
row reduction + scale fits one VMEM block per row tile; XLA fuses the
fallback fine, so the kernels mostly matter as fusion anchors for larger
Pallas pipelines.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

from .registry import registry, use_pallas


def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[:] = (x * jax.lax.rsqrt(var + eps) * w_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _ln_kernel(x_ref, w_ref, b_ref, o_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    o_ref[:] = (y * w_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)


def _row_call(kernel, x, weights, block_rows=256, interpret=False):
    orig_shape = x.shape
    d = orig_shape[-1]
    x2 = x.reshape(-1, d)
    n = x2.shape[0]
    br = min(block_rows, n)
    pad = (-n) % br
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    rows = x2.shape[0]
    out = pl.pallas_call(
        kernel,
        grid=(rows // br, ),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0))] +
        [pl.BlockSpec((d, ), lambda i: (0, )) for _ in weights],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=interpret,
    )(x2, *weights)
    if pad:
        out = out[:n]
    return out.reshape(orig_shape)


def rms_norm(x, weight, eps: float = 1e-6, force_pallas: Optional[bool] = None,
             interpret: bool = False):
    """y = x / rms(x) * weight (reference rms_norm.cu)."""
    if use_pallas(force_pallas) or interpret:
        return _row_call(functools.partial(_rms_kernel, eps=eps), x, (weight, ),
                         interpret=interpret)
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * weight.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5, force_pallas: Optional[bool] = None,
               interpret: bool = False):
    """Standard layernorm (reference layer_norm.cu)."""
    if use_pallas(force_pallas) or interpret:
        return _row_call(functools.partial(_ln_kernel, eps=eps), x, (weight, bias),
                         interpret=interpret)
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


registry.register("rms_norm", "pallas", True)
registry.register("layer_norm", "pallas", True)
