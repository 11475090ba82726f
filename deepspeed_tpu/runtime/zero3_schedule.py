"""Compiler-scheduled ZeRO-3: traced param prefetch/release in the scan.

Reference: ``runtime/zero/stage3.py`` + ``partitioned_param_coordinator.py``
— params live reduce-scattered (1/dp per chip), a coordinator traces module
execution order and issues each parameter's all-gather ahead of first use
(``stage3_prefetch_bucket_size``), releasing it after last use unless it will
be reused within ``stage3_max_reuse_distance``, never holding more than
``stage3_max_live_parameters`` gathered elements. DeepCompile and T3
(PAPERS.md) make the same argument at the compiler level: derive the schedule
from a *trace* of the step, don't hand-order it.

TPU shape of that machinery:

1. **Param store** — the fp32 masters live as the comm planner's
   dtype-homogeneous flat buckets (``comm/bucketing.py``), each 1-D bucket
   sharded over the ZeRO axes so every chip holds exactly 1/dp of the
   elements. Leaves at or under ``stage3_param_persistence_threshold``
   elements stay replicated (the reference's persistent parameters). The
   optimizer state is built OVER the store, so moments are bucket-sharded
   too — per-chip param+optimizer bytes drop ~dp×.

2. **Schedule pass** — ``jax.make_jaxpr`` traces the per-microbatch loss as
   a function of the compute-dtype param leaves; first/last-use equation
   indices per leaf induce per-bucket *gather epochs* (a bucket re-gathers
   when the elements touched between two of its uses exceed
   ``max_reuse_distance`` — releasing in between). Epochs are issued one
   ahead of use (T3 overlap: bucket k+1's all-gather overlaps bucket k's
   compute) unless prefetching would push the gathered-element peak past
   ``max_live_parameters``.

3. **Scheduled interpreter** — the loss jaxpr is re-evaluated equation by
   equation inside the microbatch ``lax.scan``; at each epoch's issue point
   the bucket shard is all-gathered through ``param_gather_bucket`` (int8
   wire when ``zero_quantized_weights``), cast to compute dtype, and sliced
   into its leaves; rebinding at a later epoch is the structural release
   (XLA's liveness ends at the previous binding's last consumer).
   ``param_gather_bucket``'s backward is the bucket reduce-scatter — the
   exact transpose of a tiled all-gather for the fp32 wire — so gradients
   exit 1/dp-sharded with bitwise stage-2 numerics, and the optimizer steps
   on the owned shard only (cross-replica weight-update sharding,
   arxiv 2004.13336).

The schedule governs FORWARD gather placement. Backward re-gathers come from
autodiff: without rematerialization XLA keeps a gathered bucket's residuals
live into backward — combine with ``activation_checkpointing.remat_policy``
or ``zero_governor.governed_layer_scan`` to bound backward liveness too
(docs/zero3.md).
"""

from dataclasses import dataclass, replace
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from jax.extend.core import Literal

from ..comm.bucketing import flatten_buckets, param_gather_bucket, plan_buckets
from ..utils.logging import log_dist, logger


# ---------------------------------------------------------------------------
# param store: fp32 masters as ZeRO-sharded flat buckets
# ---------------------------------------------------------------------------


class Zero3StoreMeta:
    """Static description of a bucketed parameter store.

    The store pytree is ``{"buckets": [1-D fp32 arrays, ZeRO-sharded],
    "persistent": [replicated full leaves]}``; this meta maps it back to the
    original param tree: ``layout`` indexes the NON-persistent leaf list
    (``np_idx[k]`` = original leaf index of that list's k-th entry),
    ``p_idx`` the persistent ones.
    """

    def __init__(self, layout, np_idx: Tuple[int, ...], p_idx: Tuple[int, ...],
                 treedef, leaf_structs: Tuple[Any, ...], bucket_size_mb: float,
                 pad_multiple: int):
        self.layout = layout
        self.np_idx = np_idx
        self.p_idx = p_idx
        self.treedef = treedef
        self.leaf_structs = leaf_structs
        self.bucket_size_mb = bucket_size_mb
        self.pad_multiple = pad_multiple

    @property
    def n_leaves(self) -> int:
        return len(self.leaf_structs)

    @property
    def persistent_elements(self) -> int:
        return sum(int(np.prod(self.leaf_structs[i].shape or (1, )))
                   for i in self.p_idx)


def build_store_meta(params, persistent_idx, bucket_size_mb: float,
                     pad_multiple: int) -> Zero3StoreMeta:
    """Plan the bucketed store for ``params`` (arrays or ShapeDtypeStructs).
    Masters are fp32, so bucketing is planned against fp32 leaf structs."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    structs = tuple(jax.ShapeDtypeStruct(tuple(getattr(l, "shape", ())),
                                         jnp.float32) for l in leaves)
    p_set = set(int(i) for i in persistent_idx)
    np_idx = tuple(i for i in range(len(leaves)) if i not in p_set)
    p_idx = tuple(sorted(p_set))
    layout = plan_buckets([structs[i] for i in np_idx], bucket_size_mb,
                          pad_multiple=pad_multiple)
    return Zero3StoreMeta(layout, np_idx, p_idx, treedef, structs,
                          bucket_size_mb, pad_multiple)


def store_from_tree(tree, meta: Zero3StoreMeta):
    """Param tree -> store pytree (pure; jit with the store shardings)."""
    leaves = jax.tree_util.tree_leaves(tree)
    return {"buckets": flatten_buckets([leaves[i] for i in meta.np_idx],
                                       meta.layout) if meta.np_idx else [],
            "persistent": [leaves[i] for i in meta.p_idx]}


def materialize_params(store, meta: Zero3StoreMeta):
    """Store pytree -> full param tree (pure slices/reshapes; under jit the
    SPMD partitioner gathers each sharded bucket where it is consumed —
    this is the resilience fallback the non-scheduled programs use)."""
    leaves: List[Optional[jnp.ndarray]] = [None] * meta.n_leaves
    for k, i in enumerate(meta.p_idx):
        leaves[i] = store["persistent"][k]
    for arr, b in zip(store["buckets"], meta.layout.buckets):
        for s in b.slots:
            leaves[meta.np_idx[s.leaf_index]] = lax.dynamic_slice_in_dim(
                arr, s.offset, s.size).reshape(s.shape)
    return jax.tree_util.tree_unflatten(meta.treedef, leaves)


def map_store_subtrees(tree, subtree_def, fn, leaf_fn=lambda x: x):
    """Apply ``fn`` to every subtree of ``tree`` whose structure equals
    ``subtree_def`` (optimizer moments mirror the params-like structure);
    other leaves go through ``leaf_fn``. Used to convert optimizer state
    between store form and tree form, and to build its shardings."""
    def is_sub(x):
        return jax.tree_util.tree_structure(x) == subtree_def

    return jax.tree_util.tree_map(lambda x: fn(x) if is_sub(x) else leaf_fn(x),
                                  tree, is_leaf=is_sub)


def store_opt_state_shardings(opt_state_shape, store_shardings, ctx):
    """Shardings for optimizer state built over the store: params-like
    subtrees get the store shardings (bucket moments stay 1/dp-sharded),
    scalar leaves (step counts) replicate."""
    repl = NamedSharding(ctx.mesh, P())
    store_def = jax.tree_util.tree_structure(store_shardings)
    return map_store_subtrees(opt_state_shape, store_def,
                              lambda _: store_shardings, lambda _: repl)


def zero3_store_supported(engine) -> bool:
    """The scheduled stage-3 program engages when: stage 3, the bucketed
    gradient_comm wire is on, pure-DP mesh whose ZeRO axes ARE the dp world
    (no MiCS/hpZ secondary partition), bf16/fp32 (no fp16 loss scaling),
    device optimizer (no offload), no composed tensor-parallel training."""
    cfg = engine._config
    ctx = engine.mesh_ctx
    zp = engine.zero_plan
    dp_axes = tuple(a for a in ("data", "fsdp") if ctx.axis_size(a) > 1)
    return (zp.stage >= 3
            and cfg.gradient_comm_config.active
            and not cfg.fp16_enabled
            and not engine._tp_training
            and engine._offload_device == "none"
            and len(dp_axes) >= 1
            and tuple(zp.zero_axes) == dp_axes
            and all(ctx.axis_size(a) == 1
                    for a in ("model", "seq", "expert", "pipe")))


def init_param_store(engine, params):
    """Convert ``params`` (fp32 master tree) into the bucketed store and
    install it as ``engine.params`` (+ shardings + meta). Runs in
    ``_init_state`` BEFORE optimizer init so the optimizer state is built
    over the store (bucket-sharded moments — the stage-1 half of ZeRO-3)."""
    cfg = engine._config
    zc = cfg.zero_config
    gcc = cfg.gradient_comm_config
    ctx = engine.mesh_ctx
    dp_axes = tuple(a for a in ("data", "fsdp") if ctx.axis_size(a) > 1)
    w = ctx.axis_size(dp_axes)
    block = int(gcc.quantization_block_size)
    leaves = jax.tree_util.tree_leaves(params)
    thresh = int(zc.param_persistence_threshold or 0)
    persistent_idx = [i for i, l in enumerate(leaves)
                      if int(np.prod(getattr(l, "shape", ()) or (1, ))) <= thresh]
    from .zero_governor import gather_bucket_mb
    eff_mb = gather_bucket_mb(gcc.bucket_size_mb, zc.max_live_parameters,
                              zc.prefetch_bucket_size)
    meta = build_store_meta(params, persistent_idx, eff_mb, w * block)
    store_shardings = engine.zero_plan.param_store_shardings(
        meta.layout, len(meta.p_idx))
    engine.params = jax.jit(lambda t: store_from_tree(t, meta),
                            out_shardings=store_shardings)(params)
    engine.param_shardings = store_shardings
    engine._zero3_store = meta
    total = sum(int(np.prod(s.shape or (1, ))) for s in meta.leaf_structs)
    log_dist(
        f"ZeRO-3 param store: {len(meta.layout.buckets)} buckets "
        f"({sum(b.padded_size for b in meta.layout.buckets)} elements, "
        f"bucket<= {eff_mb:.2f}MB, 1/{w} per chip) + {len(meta.p_idx)} "
        f"persistent leaves ({meta.persistent_elements}/{total} elements "
        f"replicated, threshold {thresh})", ranks=[0])
    return meta


# ---------------------------------------------------------------------------
# schedule pass: trace -> first/last use -> gather epochs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class GatherEpoch:
    """One scheduled all-gather of one bucket: issued before equation
    ``issue_at`` (-1 = program start), landed (sliced into leaves) at
    ``first_use``, releasable after ``last_use``."""
    bucket: int
    issue_at: int
    first_use: int
    last_use: int

    @property
    def prefetched(self) -> bool:
        return self.issue_at < self.first_use


@dataclass(frozen=True)
class Zero3Schedule:
    epochs: Tuple[GatherEpoch, ...]
    n_eqns: int
    peak_live_elements: int
    persistent_elements: int
    prefetch_count: int          # epochs issued ahead of first use
    gather_wire_bytes: int       # per microbatch, per chip, fwd tier


def trace_param_uses(closed_jaxpr, n_param_invars: int):
    """(first_use, last_use) equation index per param invar; ``None`` for
    leaves the traced loss never consumes. Outvar uses count as equation
    index ``len(eqns)``."""
    jaxpr = closed_jaxpr.jaxpr
    first: List[Optional[int]] = [None] * n_param_invars
    last: List[Optional[int]] = [None] * n_param_invars
    pos = {v: i for i, v in enumerate(jaxpr.invars[:n_param_invars])}

    def note(v, t):
        i = pos.get(v)
        if i is not None:
            if first[i] is None:
                first[i] = t
            last[i] = t

    for t, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if not isinstance(v, Literal):
                note(v, t)
    for v in jaxpr.outvars:
        if not isinstance(v, Literal):
            note(v, len(jaxpr.eqns))
    return first, last


def _gather_recv_bytes(elems: int, world: int, tier: str, block: int) -> int:
    """Receive-side wire bytes per chip for one bucket all-gather."""
    recv = elems * (world - 1) // world
    if tier == "int8":
        nb = (elems + block - 1) // block
        return recv + 8 * nb * (world - 1) // world
    if tier == "onebit":
        return recv // 8 + 4 * (world - 1)
    return recv * 4


def _peak_live(epochs, sizes, persistent_elements: int) -> int:
    """Max gathered elements over the program: sweep every issue point; an
    epoch is live on [issue_at, last_use]."""
    peak = 0
    for t in sorted({e.issue_at for e in epochs}):
        live = sum(sizes[e.bucket] for e in epochs
                   if e.issue_at <= t <= e.last_use)
        peak = max(peak, live)
    return peak + persistent_elements


def derive_schedule(layout, np_idx, first, last, n_eqns: int,
                    max_live_parameters: Optional[int],
                    max_reuse_distance: Optional[int],
                    persistent_elements: int, world: int, fwd_tier: str,
                    block: int) -> Zero3Schedule:
    """Per-bucket gather epochs from the traced first/last uses.

    A bucket's use points are the union of its leaves' first/last-use
    equations. The span splits into multiple epochs (release + re-gather)
    wherever the elements of OTHER buckets used strictly between two
    consecutive use points exceed ``max_reuse_distance`` — the reference's
    release rule, measured in the same parameter-element units. Epochs are
    then issued one ahead (epoch j at epoch j-1's first use; the first at
    program start) unless that would push the gathered-element peak past
    ``max_live_parameters`` — the governor budget demotes prefetches
    (latest first) back to gather-at-use."""
    sizes = [b.padded_size for b in layout.buckets]
    bucket_pts = []
    for b in layout.buckets:
        pts = sorted({p for s in b.slots
                      for p in (first[np_idx[s.leaf_index]],
                                last[np_idx[s.leaf_index]]) if p is not None})
        bucket_pts.append(pts)
    reuse = (int(max_reuse_distance)
             if max_reuse_distance and max_reuse_distance > 0 else None)

    def elems_between(bi, lo, hi):
        tot = 0
        for bj, pts in enumerate(bucket_pts):
            if bj != bi and any(lo < p < hi for p in pts):
                tot += sizes[bj]
        return tot

    spans = []  # (bucket, seg_first_use, seg_last_use)
    for bi, pts in enumerate(bucket_pts):
        if not pts:
            continue  # dead bucket: never gathered, grads stay zero
        start = prev = pts[0]
        for p in pts[1:]:
            if reuse is not None and elems_between(bi, prev, p) > reuse:
                spans.append((bi, start, prev))
                start = p
            prev = p
        spans.append((bi, start, prev))
    spans.sort(key=lambda s: (s[1], s[0]))

    epochs = []
    for j, (bi, fu, lu) in enumerate(spans):
        issue = -1 if j == 0 else min(spans[j - 1][1], fu)
        epochs.append(GatherEpoch(bucket=bi, issue_at=issue, first_use=fu,
                                  last_use=lu))
    budget = (int(max_live_parameters)
              if max_live_parameters and max_live_parameters > 0 else None)
    if budget is not None:
        # demote prefetches, latest-issued first, until the peak fits
        for j in range(len(epochs) - 1, -1, -1):
            if _peak_live(epochs, sizes, persistent_elements) <= budget:
                break
            e = epochs[j]
            if e.prefetched:
                epochs[j] = replace(e, issue_at=e.first_use)
        peak = _peak_live(epochs, sizes, persistent_elements)
        if peak > budget:
            logger.warning(
                f"ZeRO-3 schedule: gathered-element peak {peak} exceeds "
                f"stage3_max_live_parameters={budget} even with every "
                f"prefetch demoted — bucket spans overlap structurally; "
                f"lower gradient_comm.bucket_size_mb or scan the layers "
                f"(zero_governor.governed_layer_scan)")
    wire = sum(_gather_recv_bytes(sizes[e.bucket], world, fwd_tier, block)
               for e in epochs)
    return Zero3Schedule(
        epochs=tuple(epochs), n_eqns=n_eqns,
        peak_live_elements=_peak_live(epochs, sizes, persistent_elements),
        persistent_elements=persistent_elements,
        prefetch_count=sum(1 for e in epochs if e.prefetched),
        gather_wire_bytes=wire)


# ---------------------------------------------------------------------------
# scheduled interpreter + step program
# ---------------------------------------------------------------------------


def _eval_scheduled(closed_jaxpr, meta: Zero3StoreMeta,
                    schedule: Zero3Schedule, shards, pers, margs,
                    ax, fwd_tier: str, bwd_tier: str, block: int,
                    compute_dtype):
    """Re-evaluate the traced loss equation by equation, weaving each
    epoch's ``param_gather_bucket`` in at its issue point and slicing the
    gathered bucket into its leaf bindings at its first use. Runs inside
    the microbatch scan inside the manual (shard_map) region."""
    jaxpr = closed_jaxpr.jaxpr
    n_leaves = meta.n_leaves
    env = {}

    def read(v):
        return v.val if isinstance(v, Literal) else env[v]

    for cv, c in zip(jaxpr.constvars, closed_jaxpr.consts):
        env[cv] = c
    param_vars = jaxpr.invars[:n_leaves]
    for v, x in zip(jaxpr.invars[n_leaves:], jax.tree_util.tree_leaves(margs)):
        env[v] = x
    for k, i in enumerate(meta.p_idx):
        env[param_vars[i]] = pers[k].astype(compute_dtype)

    inflight = {}

    def issue(j, e):
        full = param_gather_bucket(shards[e.bucket], ax, fwd_tier, bwd_tier,
                                   block)
        inflight[j] = full.astype(compute_dtype)

    def land(j, e):
        full = inflight.pop(j)
        for s in meta.layout.buckets[e.bucket].slots:
            env[param_vars[meta.np_idx[s.leaf_index]]] = \
                lax.dynamic_slice_in_dim(full, s.offset, s.size).reshape(s.shape)

    issue_at, land_at = {}, {}
    for j, e in enumerate(schedule.epochs):
        issue_at.setdefault(e.issue_at, []).append((j, e))
        land_at.setdefault(e.first_use, []).append((j, e))
    for j, e in issue_at.get(-1, []):
        issue(j, e)
    for t, eqn in enumerate(jaxpr.eqns):
        for j, e in issue_at.get(t, []):
            issue(j, e)
        for j, e in land_at.get(t, []):
            land(j, e)
        subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
        ans = eqn.primitive.bind(*subfuns, *(read(v) for v in eqn.invars),
                                 **bind_params)
        if not eqn.primitive.multiple_results:
            ans = [ans]
        for v, val in zip(eqn.outvars, ans):
            env[v] = val
    for j, e in land_at.get(len(jaxpr.eqns), []):
        land(j, e)  # leaves consumed only by the outvars
    scaled, loss = (read(v) for v in jaxpr.outvars)
    return scaled, loss


def build_zero3_step(engine, apply_step):
    """Compile the scheduled stage-3 train-batch program for ``engine``.

    Same contract as ``grad_comm.build_grad_comm_step`` (the stage<=2
    builder dispatches here for stage 3): returns ``(step_fn, layout)``
    with the fused train-batch signature ``(store, opt_state, scale_state,
    stacked_args, static_kv)``. The program is built lazily on the first
    call — the schedule pass needs the batch shapes to trace the loss."""
    meta = engine._zero3_store
    assert meta is not None, "build_zero3_step requires the ZeRO-3 param store"
    cfg = engine._config
    zc = cfg.zero_config
    gc = cfg.gradient_comm_config
    ctx = engine.mesh_ctx
    mesh = ctx.mesh
    dp_axes = tuple(a for a in ("data", "fsdp") if ctx.axis_size(a) > 1)
    ax = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    w = ctx.axis_size(dp_axes)
    gas = engine.gradient_accumulation_steps()
    compute_dtype = engine.compute_dtype
    apply_fn = engine.apply_fn
    loss_fn = engine._loss_fn
    block = int(gc.quantization_block_size)
    # param gathers quantize with zero_quantized_weights (qwZ); the backward
    # reduce-scatter with zero_quantized_gradients (qgZ). fp32 otherwise —
    # the exact transpose, bitwise-matching stage-2's gradient exchange.
    fwd_tier = "int8" if zc.zero_quantized_weights else "fp32"
    bwd_tier = "int8" if zc.zero_quantized_gradients else "fp32"
    layout = meta.layout
    bucket_shardings = engine.zero_plan.bucket_shardings(layout)
    nb, npers = len(layout.buckets), len(meta.p_idx)

    from .engine import _extract_loss
    from .onebit_wire import _smap

    def scaled_loss_c(cparams, margs):
        # traced in COMPUTE dtype: the fp32->compute cast folds into each
        # gather (an upfront tree cast would make every leaf's first use
        # the program start, degenerating the schedule to gather-everything)
        out = apply_fn(cparams, *margs)
        loss = loss_fn(out) if loss_fn is not None else _extract_loss(out)[0]
        return loss.astype(jnp.float32) / gas, loss

    def _arg_spec(leaf):
        shape = getattr(leaf, "shape", ())
        # dim 0 is the microbatch axis; the batch splits on dim 1 (the
        # stage<=2 program's rule, = batch_sharding(stacked=True))
        if len(shape) < 2 or shape[1] % w != 0:
            return P()
        return P(None, ax)

    def _micro_struct(stacked):
        def one(x):
            shape = tuple(x.shape)
            if len(shape) >= 2 and shape[1] % w == 0:
                return jax.ShapeDtypeStruct((shape[1] // w, ) + shape[2:],
                                            x.dtype)
            return jax.ShapeDtypeStruct(shape[1:], x.dtype)

        return jax.tree_util.tree_map(one, stacked)

    def _compile_for(stacked_args):
        margs_struct = _micro_struct(stacked_args)
        cstructs = [jax.ShapeDtypeStruct(s.shape, compute_dtype)
                    for s in meta.leaf_structs]
        closed = jax.make_jaxpr(
            lambda pl, margs: scaled_loss_c(
                jax.tree_util.tree_unflatten(meta.treedef, pl), margs))(
                    cstructs, margs_struct)
        first, last = trace_param_uses(closed, meta.n_leaves)
        schedule = derive_schedule(
            layout, meta.np_idx, first, last, len(closed.jaxpr.eqns),
            zc.max_live_parameters, zc.max_reuse_distance,
            meta.persistent_elements, w, fwd_tier, block)
        engine._zero3_schedule = schedule

        def scheduled_loss(shards, pers, margs):
            return _eval_scheduled(closed, meta, schedule, shards, pers,
                                   margs, ax, fwd_tier, bwd_tier, block,
                                   compute_dtype)

        def region(shards, pers, stacked):
            def micro(carry, margs):
                acc_s, acc_p, loss_sum = carry
                (_, loss), (g_s, g_p) = jax.value_and_grad(
                    scheduled_loss, argnums=(0, 1), has_aux=True)(
                        shards, pers, margs)
                # forward-order fp32 accumulation, same as the stage<=2
                # scan carry (grad-of-scan would accumulate in reverse)
                acc_s = [a + g.astype(jnp.float32)
                         for a, g in zip(acc_s, g_s)]
                acc_p = [a + g.astype(jnp.float32)
                         for a, g in zip(acc_p, g_p)]
                return (acc_s, acc_p,
                        loss_sum + loss.astype(jnp.float32)), None

            init = ([jnp.zeros((b.padded_size // w, ), jnp.float32)
                     for b in layout.buckets],
                    [jnp.zeros(meta.leaf_structs[i].shape, jnp.float32)
                     for i in meta.p_idx],
                    jnp.float32(0.0))
            (acc_s, acc_p, loss_sum), _ = lax.scan(micro, init, stacked)
            # the gather transpose psum_scatters SUMS over workers; the
            # grad semantic is the mean. Persistent grads are local — one
            # boundary psum.
            acc_s = [a / w for a in acc_s]
            acc_p = [lax.psum(a, ax) / w for a in acc_p]
            loss_mean = lax.pmean(loss_sum / gas, ax)
            return loss_mean, acc_s, acc_p

        def step(store, opt_state, scale_state, stacked, static_kv):
            assert not static_kv, \
                "scheduled ZeRO-3 path takes positional batch arrays only"
            in_specs = ([P(ax)] * nb, [P()] * npers,
                        jax.tree_util.tree_map(_arg_spec, stacked))
            out_specs = (P(), [P(ax)] * nb, [P()] * npers)
            fn = _smap(region, mesh, in_specs, out_specs, dp_axes)
            loss, acc_s, acc_p = fn(store["buckets"], store["persistent"],
                                    stacked)
            acc_s = [lax.with_sharding_constraint(b, s)
                     for b, s in zip(acc_s, bucket_shardings)]
            acc = {"buckets": acc_s, "persistent": list(acc_p)}
            new_store, new_opt, _, new_scale_state, overflow, gnorm = \
                apply_step(store, acc, opt_state, scale_state)
            return loss, new_store, new_opt, new_scale_state, overflow, gnorm

        from .loss_scaler import LossScaleState
        repl = NamedSharding(mesh, P())
        jitted = jax.jit(
            step, donate_argnums=(0, 1), static_argnums=(4, ),
            out_shardings=(None, engine.param_shardings,
                           engine.opt_state_shardings,
                           LossScaleState(*engine.scale_state_shardings),
                           repl, repl))
        obs = getattr(engine, "_train_obs", None)
        if (obs is not None
                and engine._config.observability_config.compile_watch):
            jitted = obs.watch_program(jitted, "zero3_scheduled_step")
        log_dist(
            f"ZeRO-3 scheduled step built: {len(schedule.epochs)} gather "
            f"epochs over {nb} buckets ({schedule.prefetch_count} "
            f"prefetched), wire tiers fwd={fwd_tier}/bwd={bwd_tier}, peak "
            f"live {schedule.peak_live_elements} elements "
            f"(budget {zc.max_live_parameters:.3g}), "
            f"{schedule.gather_wire_bytes} gather B/microbatch/chip",
            ranks=[0])
        return jitted

    compiled = {}

    def step_entry(store, opt_state, scale_state, stacked_args, static_kv):
        key = (jax.tree_util.tree_structure(stacked_args),
               tuple((tuple(x.shape), str(x.dtype))
                     for x in jax.tree_util.tree_leaves(stacked_args)))
        fn = compiled.get(key)
        if fn is None:
            fn = compiled[key] = _compile_for(stacked_args)
        return fn(store, opt_state, scale_state, stacked_args, static_kv)

    # marker: _watch_compiled_fns must not re-wrap this python entry — the
    # inner jit is watched under its own "zero3_scheduled_step" compile key
    step_entry._zero3_scheduled = True
    engine._zero3_schedule = None  # set at first call (per batch shape)
    return step_entry, layout
