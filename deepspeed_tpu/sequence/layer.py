"""DeepSpeed-Ulysses sequence parallelism, TPU-native.

Rebuild of reference ``deepspeed/sequence/layer.py`` (``_SeqAllToAll :90``,
``single_all_to_all :41``, ``DistributedAttention :145``): shard the sequence
dim across the ``seq`` mesh axis; before attention, all-to-all swaps the
sharding from [b, s/P, h, d] to [b, s, h/P, d] so each device holds full
sequences for a head subset; after attention the inverse all-to-all restores
sequence sharding.

Two implementations, matching the two JAX programming styles:

1. `seq_all_to_all` / `DistributedAttention` — explicit ``lax.all_to_all``
   for use inside ``shard_map`` (per-shard view). This is the direct analog of
   the reference's torch `dist.all_to_all_single` path; on TPU the all-to-all
   rides ICI.
2. `ulysses_spmd` — GSPMD style for use under plain ``jit``: resharding via
   ``with_sharding_constraint`` makes XLA insert the same all-to-alls, with
   the compiler free to overlap them with the qkv projections.
3. `ulysses_flash` — the long-context fast path: explicit all-to-alls
   around the Pallas flash kernel inside a partial-manual ``shard_map``
   (the seq AND model axes are manual when nontrivial; every other axis
   stays GSPMD). The pure-GSPMD form can't use a pallas_call (it doesn't
   auto-partition), so its local attention falls back to XLA, which
   materializes O(S²) logits per head — at the 32k-seq Ulysses operating
   point (blogs/deepspeed-ulysses: 54%-of-peak bar) that is the difference
   between flash-bounded HBM and OOM. The model axis alone also routes
   here: per-head-block kernel, no collectives.
"""

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..comm.mesh import get_mesh_context


def seq_all_to_all(x, axis_name: str = "seq", scatter_idx: int = 2, gather_idx: int = 1):
    """All-to-all swapping shard dim, per-shard view (inside shard_map).

    Reference ``sequence/layer.py:41 single_all_to_all``. `scatter_idx` is the
    dim to split across the group (becomes 1/P per device), `gather_idx` the
    dim to concatenate (becomes full). For [b, s/P, h, d] inputs,
    (scatter=2, gather=1) yields [b, s, h/P, d].

    The reference asserts heads % P == 0 (layer.py:53); we do the same at
    trace time.
    """
    p = lax.psum(1, axis_name)
    if x.shape[scatter_idx] % p != 0:
        raise ValueError(
            f"dim {scatter_idx} of shape {x.shape} not divisible by sequence-parallel size {p}")
    return lax.all_to_all(x, axis_name, split_axis=scatter_idx, concat_axis=gather_idx, tiled=True)


class DistributedAttention:
    """Ulysses attention wrapper (reference ``sequence/layer.py:145``).

    Wraps any local attention fn `(q, k, v, *args, **kwargs) -> out` whose
    tensors are [b, s, h, d] per-device views. Must be called inside a
    ``shard_map`` (or ``jit``+manual axes) context where `sequence_axis` is a
    bound mesh axis name.
    """

    def __init__(self,
                 local_attention: Callable,
                 sequence_axis: str = "seq",
                 scatter_idx: int = 2,
                 gather_idx: int = 1):
        self.local_attn = local_attention
        self.axis = sequence_axis
        self.scatter_idx = scatter_idx
        self.gather_idx = gather_idx

    def __call__(self, query, key, value, *args, **kwargs):
        # [b, s/P, h, d] -> [b, s, h/P, d]
        q = seq_all_to_all(query, self.axis, self.scatter_idx, self.gather_idx)
        k = seq_all_to_all(key, self.axis, self.scatter_idx, self.gather_idx)
        v = seq_all_to_all(value, self.axis, self.scatter_idx, self.gather_idx)
        out = self.local_attn(q, k, v, *args, **kwargs)
        # [b, s, h/P, d] -> [b, s/P, h, d]
        return seq_all_to_all(out, self.axis, self.gather_idx, self.scatter_idx)


def ulysses_spmd(local_attention: Callable,
                 query,
                 key,
                 value,
                 *args,
                 sequence_axis: str = "seq",
                 mesh_ctx=None,
                 **kwargs):
    """GSPMD Ulysses: express the seq<->head reshard as sharding constraints.

    Under ``jit`` over the global mesh, annotating [b, s@seq, h, d] ->
    [b, s, h@seq, d] makes XLA emit the identical ICI all-to-all the explicit
    path does, but leaves scheduling/overlap to the compiler — the idiomatic
    pjit formulation of reference ``DistributedAttention.forward :181``.
    """
    ctx = mesh_ctx or get_mesh_context()
    sp = ctx.axis_size(sequence_axis)
    if sp == 1:
        return local_attention(query, key, value, *args, **kwargs)
    csr = jax.lax.with_sharding_constraint
    head_spec = ctx.sharding(None, None, sequence_axis, None)
    seq_spec = ctx.sharding(None, sequence_axis, None, None)

    def to_heads(x):
        # GQA: a KV head count not divisible by sp (e.g. 2 kv heads, sp=4)
        # cannot ride the head all-to-all — replicate those instead of
        # forcing the partitioner into a full rematerialization
        if x.shape[2] % sp != 0:
            return csr(x, ctx.sharding(None, None, None, None))
        return csr(x, head_spec)

    q = to_heads(query)
    k = to_heads(key)
    v = to_heads(value)
    out = local_attention(q, k, v, *args, **kwargs)
    return csr(out, seq_spec)


def ulysses_flash(q, k, v, *, window: Optional[int] = None,
                  scale: Optional[float] = None,
                  softcap: Optional[float] = None,
                  sequence_axis: str = "seq", model_axis: str = "model",
                  mesh_ctx=None, interpret: bool = False):
    """The Pallas flash kernel per device under any mesh (module doc §3).

    A Mosaic kernel cannot be partitioned by GSPMD: its lowering refuses
    ("Mosaic kernels cannot be automatically partitioned") unless EVERY
    axis name of the mesh is manual, axes of size one included. So on every
    mesh of more than one device — data parallel and ZeRO included — the
    kernel runs inside a shard_map over all the mesh's axes (less those an
    enclosing shard_map, the pipeline's, already made manual). The batch
    dim rides the data-parallel axes (``data``, ``fsdp``: each device
    attends its own rows, no collective); the seq axis is Ulysses:
    [b, S/sp, h/mp, d] → all-to-all to [b, S, h/(sp·mp), d] → causal flash
    over the full sequence on the local head block → all-to-all back; the
    model axis needs NO collectives (attention is embarrassingly parallel
    over heads).
    Requires heads divisible by sp·mp so the GQA group mapping survives the
    split (any misaligned layout provably reduces to empty per-device KV
    slices, so there is no third layout to fall back to). Returns ``None``
    when ineligible — the caller falls back to the GSPMD formulation.
    """
    ctx = mesh_ctx or get_mesh_context()
    sp = ctx.axis_size(sequence_axis)
    mp = ctx.axis_size(model_axis)
    if ctx.mesh.size == 1:
        return None
    nq, nkv = q.shape[2], k.shape[2]
    if nq % (sp * mp) or nkv % (sp * mp) or q.shape[1] % sp:
        return None  # heads/sequence must divide the manual axes
    dp = tuple(a for a in ("data", "fsdp") if ctx.axis_size(a) > 1)
    if dp and q.shape[0] % ctx.axis_size(dp):
        dp = ()  # rows do not divide: every device attends the whole batch

    from ..ops.attention import flash_attention

    def body(q_l, k_l, v_l):
        if sp > 1:
            q_l = seq_all_to_all(q_l, sequence_axis, 2, 1)  # [b,S,h/(sp·mp),d]
            k_l = seq_all_to_all(k_l, sequence_axis, 2, 1)
            v_l = seq_all_to_all(v_l, sequence_axis, 2, 1)
        out = flash_attention(q_l, k_l, v_l, causal=True, scale=scale,
                              window=window, softcap=softcap,
                              interpret=interpret)
        if sp > 1:
            out = seq_all_to_all(out, sequence_axis, 1, 2)  # [b,S/sp,h/mp,d]
        return out

    outer = frozenset(jax.sharding.get_abstract_mesh().manual_axes)
    names = frozenset(ctx.mesh.axis_names) - outer
    if not names:
        return body(q, k, v)  # already manual over the whole mesh
    spec = P(tuple(a for a in dp if a in names) or None,
             sequence_axis if sp > 1 and sequence_axis in names else None,
             model_axis if mp > 1 and model_axis in names else None, None)
    return jax.shard_map(body, mesh=ctx.mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, axis_names=names,
                         check_vma=False)(q, k, v)
