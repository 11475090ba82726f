"""ZeRO++ — quantized collectives (qwZ, qgZ) and hpZ wiring.

Rebuild of the reference's ZeRO++ paths (SURVEY.md §2.3):
- qwZ  (``zero_quantized_weights``  zero/config.py:287): the stage-3 weight
  allgather moves int8 blocks + fp32 scales instead of fp16 — half the
  allgather bytes (reference quantizes via ``csrc/quantization/
  swizzled_quantize.cu``; here via ``ops.quantizer`` Pallas/XLA kernels).
- qgZ  (``zero_quantized_gradients`` config.py:299 ->
  ``runtime/comm/coalesced_collectives.py:31 all_to_all_quant_reduce``): the
  gradient reduce-scatter becomes quantize -> all-to-all -> local dequant+sum.
- hpZ  (``zero_hpz_partition_size`` config.py:283): secondary intra-node
  param shard so backward allgathers stay in the fast ICI domain — on TPU
  this is purely a mesh shape choice: split dp into (data, fsdp=hpz_size)
  with fsdp innermost (the ICI-contiguous axis); ``zero_axes_for`` then
  partitions over fsdp only. `hpz_mesh_axes` computes that split.

The wire format is a straight-through estimator: forward gathers
dequantize(all_gather(quantize(w))); backward reduce-scatters
dequant+sum(all_to_all(quantize(g))). XLA sees int8 collectives on the hot
path, autodiff sees the exact math.
"""

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..ops.quantizer import quantize_int8_blockwise, dequantize_int8_blockwise


def _smap(f, mesh, in_specs, out_specs, manual):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         axis_names=set(manual), check_vma=False)


def _axis_size(axis_name):
    return lax.psum(1, axis_name)


def _quant_blocks(flat, block):
    """Quantize a flat [n] vector with scales every `block` elems (n%block==0)."""
    rows = flat.reshape(-1, block).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(rows), axis=-1, keepdims=True)
    scale = jnp.where(absmax == 0, 1.0, absmax / 127.0)
    q = jnp.clip(jnp.round(rows / scale), -127, 127).astype(jnp.int8)
    return q.reshape(-1), scale.reshape(-1)


def _dequant_blocks(values, scales, block):
    return (values.reshape(-1, block).astype(jnp.float32) *
            scales.reshape(-1, 1)).reshape(-1)


def _pick_block(n, block):
    b = min(block, n)
    while n % b != 0:
        b -= 1
    return max(b, 1)


def quantized_all_gather(x, axis_name: str, block: int = 2048):
    """qwZ wire op: int8-quantize the local shard, all-gather values+scales,
    dequantize. Per-shard view (inside shard_map); gathers dim 0."""
    shape = x.shape
    flat = x.reshape(-1)
    b = _pick_block(flat.shape[0], block)
    v, s = _quant_blocks(flat, b)
    v_all = lax.all_gather(v, axis_name, axis=0, tiled=True)
    s_all = lax.all_gather(s, axis_name, axis=0, tiled=True)
    full = _dequant_blocks(v_all, s_all, b)
    p = _axis_size(axis_name)
    return full.reshape((p * shape[0], ) + shape[1:]).astype(x.dtype)


def all_to_all_quant_reduce(g, axis_name: str, block: int = 2048):
    """qgZ wire op (reference ``coalesced_collectives.py:31``): reduce-scatter
    of `g` along dim 0 carried as int8: split into P chunks, quantize each,
    all-to-all, dequantize + sum. Per-shard view; returns this rank's chunk
    ([dim0/P, ...]) of the SUM over ranks."""
    p = _axis_size(axis_name)
    shape = g.shape
    assert shape[0] % p == 0, f"dim0 {shape[0]} not divisible by group size {p}"
    chunk = shape[0] // p
    n_local = chunk * int(np.prod(shape[1:])) if len(shape) > 1 else chunk
    flat = g.reshape(p, n_local)
    b = _pick_block(n_local, block)
    v, s = jax.vmap(lambda row: _quant_blocks(row, b))(flat)  # [p, n], [p, n/b]
    v_x = lax.all_to_all(v, axis_name, split_axis=0, concat_axis=0, tiled=False)
    s_x = lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0, tiled=False)
    parts = jax.vmap(lambda vv, ss: _dequant_blocks(vv, ss, b))(v_x, s_x)
    return parts.sum(axis=0).reshape((chunk, ) + shape[1:]).astype(g.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def quantized_gather_param(x, axis_name: str, qgz: bool, block: int):
    """Straight-through qwZ gather with qgZ backward (see module docstring)."""
    return quantized_all_gather(x, axis_name, block)


def _qgp_fwd(x, axis_name, qgz, block):
    return quantized_all_gather(x, axis_name, block), None


def _qgp_bwd(axis_name, qgz, block, _, g):
    if qgz:
        return (all_to_all_quant_reduce(g, axis_name, block), )
    # exact reduce-scatter fallback
    return (lax.psum_scatter(g, axis_name, scatter_dimension=0, tiled=True), )


quantized_gather_param.defvjp(_qgp_fwd, _qgp_bwd)


def hpz_mesh_axes(n_devices: int, hpz_partition_size: int) -> Dict[str, int]:
    """hpZ: dp split into (data=n/hpz, fsdp=hpz) so ZeRO partitions over the
    innermost (ICI-local) fsdp axis only — params replicate across nodes,
    shard within, exactly the reference's secondary partition."""
    if hpz_partition_size <= 1 or n_devices % hpz_partition_size != 0:
        return {"data": -1}
    return {"data": n_devices // hpz_partition_size, "fsdp": hpz_partition_size}


def make_qwz_param_gather(mesh_ctx, param_shardings, qgz: bool = False,
                          block: int = 2048,
                          zero_axes: tuple = ("data", "fsdp")):
    """Build `gather(params) -> full params` for use inside jit: every leaf
    sharded over the ZeRO axes is explicitly gathered through the int8 wire
    (fwd) and its gradient reduce-scattered through int8 (bwd, if qgz).

    Engine wiring for zero_quantized_weights: wraps the apply closure so XLA
    emits int8 collectives instead of implicit bf16 resharding.

    Only the dim sharded purely by ``zero_axes`` goes through the wire:
    under composed TP (``tensor_parallel``) a weight's model-axis dim is
    consumed sharded — there is no TP weight allgather to replace, and
    routing it through lossy int8 would change TP numerics. The shard_map
    is partial-manual over the ZeRO axes only, so a leaf's model-axis
    sharding rides through the wire gather untouched.
    """
    mesh = mesh_ctx.mesh

    def _leaf_gather(leaf, sharding):
        spec = sharding.spec if isinstance(sharding, NamedSharding) else P()
        # find the first dim sharded purely by ZeRO axes
        dim, axes = None, None
        for d, entry in enumerate(spec):
            if entry is None:
                continue
            entry_t = entry if isinstance(entry, tuple) else (entry, )
            if all(a in zero_axes for a in entry_t):
                dim, axes = d, entry_t
                break
        if dim is None:
            return leaf
        axis_name = axes[0] if len(axes) == 1 else axes

        def per_shard(x):
            moved = jnp.moveaxis(x, dim, 0)
            full = quantized_gather_param(moved, axis_name, qgz, block)
            return jnp.moveaxis(full, 0, dim)

        # specs name ONLY the manual (ZeRO) axes: non-manual sharding (a TP
        # model axis on another dim) stays outside the manual region and is
        # preserved by the partial-manual shard_map
        in_spec = P(*(e if d == dim else None for d, e in enumerate(spec)))
        out_spec = P(*([None] * len(spec)))
        manual = set(axes)
        return _smap(per_shard, mesh, (in_spec, ), out_spec, manual)(leaf)

    def gather(params):
        return jax.tree_util.tree_map(_leaf_gather, params, param_shardings)

    return gather
