"""DeepSpeedTpuEngine — the core training engine.

Rebuild of reference ``runtime/engine.py:182 DeepSpeedEngine`` with the same
contract — ``forward`` (:1838) / ``backward`` (:1977) / ``step`` (:2176) /
``save_checkpoint`` (:3109) / ``load_checkpoint`` (:2763) — over a pure,
jitted SPMD train step.

Design (stateful torch-style API over pure JAX):
- `forward(*args)` runs ONE compiled value-and-grad ("fwd_bwd") and caches
  the pending gradients; the returned loss is a live device scalar.  (In
  torch, backward reuses forward's activations; in JAX the only way to get
  that without recompute is to take the grad at forward time. Pure-inference
  calls should use `eval_batch`/`module_forward`, which compile forward-only.)
- `backward(loss)` commits the cached gradients into the (ZeRO-sharded)
  accumulation buffer — the analog of the reference's grad-hook bucketed
  reduce (stage_1_and_2.py:897): under SPMD the reduce is emitted by XLA from
  the sharding specs rather than driven by hooks.
- `step()` at a gradient-accumulation boundary runs the compiled apply step:
  fp16 unscale + overflow check + global-norm clip + optimizer update +
  loss-scale update, all fused in one XLA program (reference does this across
  several host-driven kernel launches).

ZeRO stages are *sharding plans* (see ``zero_sharding.py``), not subclasses.
"""

import os
import time
from contextlib import nullcontext
from functools import partial, partialmethod
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .. import comm as dist
from ..checkpoint.engine import (OrbaxCheckpointEngine, CheckpointCorruptionError,
                                 find_latest_valid_checkpoint, prune_checkpoints,
                                 read_latest_tag, verify_checkpoint,
                                 write_latest_tag)
from ..utils.fault_injection import get_fault_injector
from ..comm.mesh import get_mesh_context, mesh_is_initialized
from ..config import DeepSpeedTpuConfig
from ..observability.tracing import NO_TRACER, get_tracer
from ..utils.logging import logger, log_dist
from ..utils.timer import (BACKWARD_GLOBAL_TIMER, BACKWARD_MICRO_TIMER, FORWARD_GLOBAL_TIMER,
                           FORWARD_MICRO_TIMER, STEP_GLOBAL_TIMER, STEP_MICRO_TIMER,
                           NoopTimer, SynchronizedWallClockTimer, ThroughputTimer)
from .loss_scaler import LossScalerConfig, has_overflow
from .lr_schedules import get_lr_schedule
from .optimizers import build_optimizer
from .zero_sharding import ZeroShardingPlan

try:
    import flax.linen as nn
    _HAS_FLAX = True
except ImportError:  # pragma: no cover
    _HAS_FLAX = False


def _step_scope(region: str):
    """The name of a region of the compiled step that no module names:
    ``ds.step.<region>`` on the name stack of every op traced under it, which
    is what XProf shows under a device op and what
    ``benchmark/scope_time.py`` reads (docs/observability.md, "Device
    scopes"). Metadata only: no op is added."""
    return jax.named_scope("ds.step." + region)


def _tree_where(cond, a, b):
    return jax.tree_util.tree_map(lambda x, y: jnp.where(cond, x, y), a, b)


def _as_apply_fns(model):
    """Accept a flax Module, (module, method) or raw apply callable. Returns
    ``(apply_fn, apply_with_stats)``: the second also returns what a flax
    model sowed for the host (``{}`` when nothing), and is ``None`` for a
    raw callable."""
    if _HAS_FLAX and isinstance(model, nn.Module):
        # the families of statistics the model's operators declare: their
        # collections, reductions over the layers and names (docs/TRAINING.md,
        # "What an operator sows for the host"); none on a module that has none
        families = tuple(getattr(model, "sown_families", ()))
        mutable = ["aux_loss", *(family.collection for family in families)]

        def apply_with_stats(params, *args, **kwargs):
            # "aux_loss" is the contract for modules that sow auxiliary
            # training losses (MoE router load-balancing — reference
            # sharded_moe.py l_aux): sown scalars are ADDED to a scalar
            # model loss; logits outputs pass through untouched.
            out, mods = model.apply({"params": params}, *args, **kwargs, mutable=mutable)
            aux = jax.tree_util.tree_leaves(mods.get("aux_loss", {}))
            aux_total = sum(jnp.sum(a) for a in aux) if aux else None
            if aux and hasattr(out, "ndim") and out.ndim == 0:
                out = out + aux_total
            stats = {}
            for family in families:
                sown = {}   # by the name it was sown under: every layer's leaf
                for path, leaf in jax.tree_util.tree_flatten_with_path(
                        mods.get(family.collection, {}))[0]:
                    sown.setdefault(path[-1].key, []).append(leaf)
                for name, over_layers in family.stats.items():
                    if name in sown:
                        stats[family.key(name)] = over_layers.across(sown[name])
                if family.aux_loss and sown and aux:
                    stats[family.key("aux_loss")] = aux_total.astype(jnp.float32)
            return out, stats

        def apply_fn(params, *args, **kwargs):
            return apply_with_stats(params, *args, **kwargs)[0]

        return apply_fn, apply_with_stats
    if callable(model):
        return model, None
    raise TypeError(f"model must be a flax Module or callable apply_fn, got {type(model)}")


def _split_static_kwargs(kwargs):
    """Split kwargs into (traced, static): plain Python int/bool/str kwargs are
    treated as *static* jit arguments (one cached compile per value). This is
    the contract that lets schedule-driven shape knobs — random-LTD keep
    counts, curriculum seqlens — flow through the compiled step."""
    traced, static = {}, []
    for k, v in kwargs.items():
        if isinstance(v, (bool, int, str)) and not hasattr(v, "shape"):
            static.append((k, v))
        else:
            traced[k] = v
    return traced, tuple(sorted(static))


def _extract_loss(out):
    """Contract: model returns loss, (loss, aux) or dict with 'loss'."""
    if isinstance(out, tuple):
        return out[0], out[1] if len(out) > 1 else None
    if isinstance(out, dict):
        return out["loss"], out
    return out, None


def host_fetch(x):
    """The engine's ONE device→host fetch point. Every steady-state transfer
    the engine itself initiates (window drains, offload scalars, get_loss)
    routes through here, so the async-pipeline trace test can monkeypatch a
    single seam to count/forbid host syncs — JAX's transfer guard does not
    fire on implicit conversions under the CPU backend, so an
    instrumentation seam is the portable way to prove "zero per-step
    syncs"."""
    return jax.device_get(x)


class _AsyncStepWindow:
    """Bounded in-flight window of un-fetched per-step device scalars
    (async_pipeline tentpole: windowed host sync).

    Each optimizer step pushes its (loss, overflow) as LIVE device values —
    no conversion, no barrier — and every ``interval`` in-flight steps the
    engine drains the window with one batched ``host_fetch`` and reconciles
    the deferred host accounting (skipped-step counts, lr-scheduler
    advance, monitor events, steps_per_print logging)."""

    def __init__(self, interval: int):
        self.interval = max(1, int(interval))
        self.entries = []  # (steps, loss, overflow) — device values
        self.comm_steps = 0  # bucketed grad-comm dispatches in this window
        self.t_start = None

    def push(self, steps, loss, overflow):
        if self.t_start is None:
            self.t_start = time.perf_counter()
        self.entries.append((steps, loss, overflow))

    @property
    def in_flight(self) -> int:
        return sum(e[0] for e in self.entries)

    def take(self):
        """Hand back (entries, wall_seconds, comm_steps) and reset."""
        entries, self.entries = self.entries, []
        duration = (time.perf_counter() - self.t_start
                    if self.t_start is not None else 0.0)
        comm_steps, self.comm_steps = self.comm_steps, 0
        self.t_start = None
        return entries, duration, comm_steps


class DeepSpeedTpuEngine:

    @staticmethod
    def _dp_world_from(raw) -> int:
        """dp world = product of (data, fsdp) axes of the configured mesh."""
        import json as _json
        from ..comm.mesh import resolve_axis_sizes, MESH_AXES
        if isinstance(raw, str):
            with open(raw) as f:
                raw = _json.load(f)
        if mesh_is_initialized():
            return get_mesh_context().dp_size
        mesh_cfg = dict(raw.get("mesh", {})) if isinstance(raw, dict) else {}
        mesh_cfg.pop("axis_order", None)
        tp_sz = ((raw.get("tensor_parallel") or {}).get("tp_size")
                 if isinstance(raw, dict) else None)
        if not isinstance(tp_sz, int):
            tp_sz = None  # "auto"/null tolerated like every ConfigModel field
        if tp_sz and tp_sz > 1 and mesh_cfg.get("model", 1) == 1:
            # tensor_parallel.tp_size will create the model axis — the dp
            # estimate (and the batch triangle it validates) must see it.
            # SAME condition as the mesh-creation injection below (model
            # absent OR explicitly 1), or the two dp worlds diverge.
            mesh_cfg["model"] = tp_sz
        # partial specs (e.g. {"model": 2}) leave "data" to absorb leftovers,
        # mirroring MeshContext.create
        if mesh_cfg and all(v != -1 for v in mesh_cfg.values()) and "data" not in mesh_cfg:
            mesh_cfg["data"] = -1
        try:
            sizes = resolve_axis_sizes(jax.device_count(), mesh_cfg or {"data": -1})
        except ValueError:
            return jax.device_count()
        return sizes.get("data", 1) * sizes.get("fsdp", 1)

    def __init__(self,
                 model=None,
                 optimizer=None,
                 model_parameters=None,
                 training_data=None,
                 lr_scheduler=None,
                 mpu=None,
                 collate_fn=None,
                 config=None,
                 mesh_param=None,
                 dont_shard=False,
                 loss_fn=None,
                 **kwargs):
        # Resolve the true data-parallel world BEFORE validating the batch
        # triangle: it depends on the mesh shape (dp = data*fsdp), not on
        # jax.device_count() — a {data:2, model:2} mesh on 4 devices has dp=2.
        if isinstance(config, DeepSpeedTpuConfig):
            self._config = config
        else:
            raw = config if config is not None else {}
            self._config = DeepSpeedTpuConfig(raw, world_size=self._dp_world_from(raw))
        # the span API (observability/tracing.py): the process-wide tracer,
        # off with the observability block like every other recording
        self._tracer = (get_tracer()
                        if self._config.observability_config.enabled
                        else NO_TRACER)
        with self._tracer.scope("ds.init", annotate=False):
            self._construct(model, optimizer, model_parameters, training_data,
                            lr_scheduler, mpu, collate_fn, mesh_param,
                            loss_fn, kwargs)
            self._publish_layer_kinds(model)

    def _construct(self, model, optimizer, model_parameters, training_data,
                   lr_scheduler, mpu, collate_fn, mesh_param, loss_fn,
                   kwargs):
        self.module = model
        # multi-output models (reference test_multi_output_model.py): the
        # torch pattern combines the returned losses BETWEEN forward and
        # backward; under the fused step the combiner must live inside the
        # traced program — loss_fn(model_output) -> scalar does exactly that
        self._loss_fn = loss_fn
        self.client_optimizer = optimizer
        self.client_lr_scheduler = lr_scheduler
        self.training_dataloader = None
        self.mpu = mpu
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        self.skipped_steps = 0
        self._pending = None  # (grads, loss) from forward awaiting backward
        self._training = True  # torch module.train()/eval() semantics
        self._last_grad_norm = None
        self.losses = None
        self.last_fwd_spec = None  # abstract fwd arg spec (flops profiler)

        # ---- mesh ----
        with self._tracer.scope("ds.init.mesh"):
            if not mesh_is_initialized():
                mc = self._config.mesh_config
                axes = {a: getattr(mc, a) for a in mc.axis_order}
                tp_sz = self._config.tensor_parallel_config.tp_size
                if tp_sz and tp_sz > 1 and axes.get("model", 1) == 1:
                    # tensor_parallel.tp_size creates the model axis when the
                    # mesh config doesn't name one (inference-config spelling)
                    axes["model"] = tp_sz
                elif (tp_sz and tp_sz > 1
                      and axes.get("model", 1) not in (tp_sz, -1)):
                    # -1 means the user delegated the size to absorption — only
                    # an EXPLICIT different size is a real conflict
                    from ..utils.logging import logger as _logger
                    _logger.warning(
                        f"tensor_parallel.tp_size={tp_sz} conflicts with mesh "
                        f"model={axes.get('model')} — the mesh axis wins; TP "
                        f"runs at {axes.get('model')}")
                hpz = self._config.zero_config.zero_hpz_partition_size
                if hpz > 1 and axes.get("fsdp", 1) == 1:
                    # hpZ (ZeRO++ secondary partition): shard params over the
                    # innermost ICI-local axis only; replicate across nodes
                    from .zeropp import hpz_mesh_axes
                    axes.update(hpz_mesh_axes(jax.device_count(), hpz))
                mics = self._config.zero_config.mics_shard_size
                if mics > 1 and axes.get("fsdp", 1) == 1:
                    # MiCS: ZeRO-3 within shard groups, replicate across
                    from .mics import mics_mesh_axes
                    axes.update(mics_mesh_axes(jax.device_count(), mics))
                if mesh_param is not None:  # reference mesh_param=(dp, sp)
                    axes = {"data": mesh_param[0], "seq": mesh_param[1]}
                dist.init_distributed(mesh_axes=axes)
            self.mesh_ctx = get_mesh_context()
            self.dp_world_size = self.mesh_ctx.dp_size
            # pre-initialized mesh may differ from the config's pre-mesh guess
            self._config.reresolve(self.dp_world_size)

        # ---- precision policy ----
        if self._config.bf16_enabled:
            self.compute_dtype = jnp.bfloat16
        elif self._config.fp16_enabled:
            self.compute_dtype = jnp.float16
        else:
            self.compute_dtype = jnp.float32
        self.scaler_cfg = LossScalerConfig.from_fp16_config(self._config.fp16_config)
        self._use_loss_scaling = self._config.fp16_enabled
        # data_types.grad_accum_dtype (reference engine.py:938-944): dtype of
        # the gradient-accumulation buffer/scan-carry. None = fp32 (full
        # accumulation precision); bf16 halves the buffer at a documented
        # precision cost. apply_step up-casts to fp32 before the update.
        from ..utils.dtypes import resolve_dtype
        try:
            self.grad_accum_dtype = resolve_dtype(
                self._config.data_types_config.grad_accum_dtype, jnp.float32)
        except ValueError as e:
            raise ValueError(f"data_types.grad_accum_dtype: {e}") from None
        if self.grad_accum_dtype not in (jnp.float32, jnp.bfloat16, jnp.float16):
            raise ValueError("data_types.grad_accum_dtype must be "
                             "fp32/bf16/fp16")
        if self.grad_accum_dtype == jnp.float16 and not self._use_loss_scaling:
            # fp16 accumulation saturates at 65504; only the fp16 loss-scaler
            # path runs the overflow check that turns saturation into a
            # skipped step instead of silent inf/NaN params
            raise ValueError("grad_accum_dtype=fp16 requires fp16 training "
                             "(loss scaling + overflow skip); use bf16 or "
                             "fp32 accumulation otherwise")

        # ---- apply fn (+ activation checkpointing) ----
        # what a recomputed layer keeps is chosen against what the chip
        # holds when the step is first traced: this engine's state, not an
        # earlier one's
        from ..ops import remat
        remat.forget_plans()
        self.apply_fn, self._apply_with_stats = _as_apply_fns(model)
        self._sown_families = tuple(getattr(model, "sown_families", ()))
        ac = self._config.activation_checkpointing_config
        if ac.remat_policy:
            policy = getattr(jax.checkpoint_policies, ac.remat_policy, None)
            self.apply_fn = jax.checkpoint(self.apply_fn, policy=policy)
            if self._apply_with_stats is not None:
                self._apply_with_stats = jax.checkpoint(
                    self._apply_with_stats, policy=policy)

        # ---- lr schedule ----
        self.lr_scheduler = None
        base_lr = (self._config.optimizer_params or {}).get("lr", 1e-3)
        lr_fn = None
        if lr_scheduler is not None:
            self.lr_scheduler = lr_scheduler
            lr_fn = getattr(lr_scheduler, "lr_at", None)
        elif self._config.scheduler_name is not None:
            self.lr_scheduler = get_lr_schedule(self._config.scheduler_name,
                                                self._config.scheduler_params or {},
                                                base_lr=base_lr)
            lr_fn = self.lr_scheduler.lr_at

        # ---- optimizer ----
        self._lr_fn = lr_fn
        if optimizer is not None and isinstance(optimizer, optax.GradientTransformation):
            self.base_tx, self._base_lr = optimizer, base_lr
        else:
            self.base_tx, self._base_lr = build_optimizer(self._config.optimizer_name,
                                                          self._config.optimizer_params, lr_fn=lr_fn)
        self.optimizer = self  # engine exposes optimizer-ish API (reference returns the wrapper)

        # ---- ZeRO sharding plan (optionally composed with native TP) ----
        with self._tracer.scope("ds.init.zero_plan"):
            zc = self._config.zero_config
            tpc = self._config.tensor_parallel_config
            tp_requested = tpc.enabled or (tpc.tp_size or 0) > 1
            self._tp_training = tp_requested and self.mesh_ctx.axis_size("model") > 1
            if tp_requested and not self._tp_training:
                from ..utils.logging import logger as _logger
                _logger.warning(
                    "tensor_parallel requested but the mesh has no model axis "
                    "> 1 — TP sharding disabled (add model to the mesh config "
                    "or set tensor_parallel.tp_size)")
            self.zero_plan = ZeroShardingPlan(self.mesh_ctx, zc.stage,
                                              param_persistence_threshold=zc.param_persistence_threshold,
                                              tp=self._tp_training,
                                              logical_axes=kwargs.get("logical_axes"))
            if zc.stage >= 3 and model_parameters is not None:
                # max_live_parameters governor advisory (zero_governor.py): the
                # structural ceiling is scan chunking — warn when the model's
                # unrolled params exceed the configured budget AND the model isn't
                # already scan-governed (embeddings/head stay live regardless)
                scan_governed = bool(getattr(getattr(model, "config", None),
                                             "scan_layers", False))
                n_el = sum(int(np.prod(getattr(p, "shape", ())))
                           for p in jax.tree_util.tree_leaves(model_parameters))
                if n_el > zc.max_live_parameters and not scan_governed:
                    from ..utils.logging import logger as _logger
                    _logger.warning(
                        f"ZeRO-3: model has {n_el:.3g} elements > "
                        f"stage3_max_live_parameters={zc.max_live_parameters:.3g}. "
                        f"XLA may gather beyond the budget on an unrolled model — "
                        f"use scan_layers (LlamaConfig.with_live_param_budget) or "
                        f"runtime.zero_governor.governed_layer_scan to make the "
                        f"ceiling structural.")

        # ZeRO-Offload: optimizer states on host DRAM or NVMe (reference
        # stage_1_and_2.py cpu-offload path + cpu_adam); frees HBM of the
        # fp32 master + moments at the cost of a device<->host stream per step.
        # ratio < 1.0 = Offload++ Twin-Flow (reference stage3.py:849): the
        # first `ratio` fraction of elements step on host, the rest on device.
        self._offload_device = zc.offload_optimizer_device  # none | cpu | nvme
        self._host_optimizer = None
        self._offload_ratio = (float(zc.offload_optimizer.ratio)
                               if zc.offload_optimizer else 1.0)
        self._host_param_names = set()
        self._device_tx = None

        # ---- persistent compilation cache (async_pipeline tentpole 4:
        # the autotuner-only jax_compilation_cache_dir wiring, promoted) ----
        from .compiler import configure_compile_cache
        configure_compile_cache(self._config.compile_config)

        # ---- async step pipeline (windowed host sync) ----
        apc = self._config.async_pipeline_config
        self._async_window = (_AsyncStepWindow(apc.sync_interval)
                              if apc.enabled else None)
        self._sown_pending = []  # device stats of fused steps not yet published
        self._kernel_line_logged = False
        # the process's grouped-matmul trace counts before this engine's
        # programs: its one `kernels:` line reports what came after
        from ..ops.grouped_matmul import traced_counts
        self._gmm_traced_before = traced_counts()
        # the block-diffusion objective's noising of raw token batches
        # (data_pipeline/block_diffusion.py), seeded by the config's ``seed``
        self._diffusion_noiser = None
        cfg = getattr(model, "config", None)
        if getattr(cfg, "block_diffusion_", False):
            from .data_pipeline.block_diffusion import BlockDiffusionNoiser
            self._diffusion_noiser = BlockDiffusionNoiser(
                cfg.diffusion_block_length, cfg.diffusion_mask_id_,
                cfg.diffusion_t_min, seed=self._config.seed)

        # ---- training/compiler observability (observability/xla.py +
        # observability/goodput.py): created before the compiled fns so the
        # compile watch can wrap them; the goodput ledger's clock starts
        # here, so construction/auto-resume lands in "restart" ----
        oc = self._config.observability_config
        self._train_obs = None
        self._obs_textfile = None
        if oc.enabled:
            from ..observability.goodput import GoodputLedger
            from ..observability.xla import (TrainInstruments,
                                             install_backend_compile_listener)
            ledger = GoodputLedger() if oc.goodput else None
            self._train_obs = TrainInstruments(ledger=ledger)
            if oc.compile_watch:
                install_backend_compile_listener()
            self._obs_textfile = (oc.textfile
                                  or os.environ.get("DS_TPU_METRICS_TEXTFILE")
                                  or None)

        # ---- state init ----
        if model_parameters is None and _HAS_FLAX and isinstance(model, nn.Module):
            raise ValueError("model_parameters (the flax params pytree) is required")
        self._init_state(model_parameters)

        # ---- compiled steps ----
        with self._tracer.scope("ds.init.build_step"):
            self._build_compiled_fns()
            self._watch_compiled_fns()

        # ---- compile() / is_compiled surface (reference engine.py:3665) ----
        from .compiler import attach_compile_api
        attach_compile_api(self)

        # ---- timers / monitor ----
        self.wall_clock_breakdown = self._config.wall_clock_breakdown
        self.timers = SynchronizedWallClockTimer() if self.wall_clock_breakdown else NoopTimer()
        self.tput_timer = ThroughputTimer(
            self._config, batch_size=self.train_batch_size(),
            steps_per_output=self._config.steps_per_print,
            # async pipeline: the per-step effects_barrier is the stall the
            # windowed sync exists to remove; the boundary drain is the
            # barrier that keeps multi-step averages honest
            synchronize=self._async_window is None)
        self.monitor = None
        if any([self._config.monitor_config.tensorboard.enabled,
                self._config.monitor_config.wandb.enabled,
                self._config.monitor_config.csv_monitor.enabled,
                self._config.monitor_config.comet.enabled]):
            from ..monitor.monitor import MonitorMaster
            self.monitor = MonitorMaster(self._config.monitor_config)

        # flops profiler (reference engine.py flops_profiler hook)
        self.flops_profiler = None
        self._flops_auto_active = False  # session opened by the auto-hook
        if self._config.flops_profiler_config.enabled:
            from ..profiling.flops_profiler.profiler import FlopsProfiler
            self.flops_profiler = FlopsProfiler(
                model, ds_engine=self,
                recompute_fwd_factor=self._config.flops_profiler_config.recompute_fwd_factor)

        # ---- data efficiency: curriculum + random-LTD (reference
        # engine.py:349-356 scheduler construction, :1877-1883 forward hooks) ----
        self.curriculum_scheduler_legacy = None
        if self._config.curriculum_enabled_legacy:
            from .data_pipeline.curriculum_scheduler import CurriculumScheduler
            self.curriculum_scheduler_legacy = CurriculumScheduler(
                self._config.curriculum_params_legacy)
        self.random_ltd_scheduler = None
        routing = (self._config.data_efficiency_config or {}).get("data_routing", {})
        if routing.get("enabled") and routing.get("random_ltd", {}).get("enabled", False):
            from .data_pipeline.data_routing import RandomLTDScheduler
            self.random_ltd_scheduler = RandomLTDScheduler(routing)
        # inject the LTD keep-count into models that declare the kwarg (the
        # reference mutates wrapped layers in place; functional models take it
        # as an argument instead — each annealing level is one cached compile)
        self._ltd_kwarg = False
        if self.random_ltd_scheduler is not None:
            import inspect
            try:
                sig = inspect.signature(model.__call__ if _HAS_FLAX
                                        and isinstance(model, nn.Module) else model)
                self._ltd_kwarg = "random_ltd_keep" in sig.parameters
            except (TypeError, ValueError):
                pass

        # built at its first use (the `checkpoint_engine` property): a run
        # that never saves or loads never imports orbax. A run that will save
        # on a signal has a grace period to save in, so it pays the import now
        self._checkpoint_engine = None
        rc = self._config.resilience_config
        if rc.enabled and rc.preempt_save:
            self._build_checkpoint_engine()
        dist.configure(deepspeed_config=self._config)

        # training data loader (reference deepspeed_io, engine.py:1743)
        if training_data is not None:
            from .dataloader import DeepSpeedDataLoader
            # the host-global batch: per-device micro batch * dp world (the
            # loader yields global arrays that batch_sharding splits over dp)
            self.training_dataloader = DeepSpeedDataLoader(
                training_data,
                batch_size=self.train_micro_batch_size_per_gpu() * self.dp_world_size,
                collate_fn=collate_fn,
                sampler=self._build_curriculum_sampler(training_data))
            if apc.enabled and apc.prefetch_depth > 0:
                # device-side input prefetch (async_pipeline tentpole 1):
                # the next N batches' host→device transfers dispatch while
                # the current step runs; the train paths' device_put on an
                # already-sharded batch is a no-op
                from .dataloader import PrefetchingLoader
                self.training_dataloader = PrefetchingLoader(
                    self.training_dataloader, self._prefetch_put,
                    apc.prefetch_depth)

        # ---- resilience: preemption autosave, anomaly sentry, auto-resume
        # (after the dataloader so auto-resume can restore sampler state) ----
        with self._tracer.scope("ds.init.resume"):
            self._init_resilience()

        if self._train_obs is not None:
            # everything up to here — construction, compile-cache setup,
            # auto-resume — is "restart" time; anchor the step clock so the
            # first step's sample measures the step, not engine init
            if self._train_obs.ledger is not None:
                self._train_obs.ledger.mark("restart")
            self._train_obs.start_clock()

        log_dist(
            f"DeepSpeedTpuEngine ready: zero_stage={zc.stage} dtype={self.compute_dtype.__name__} "
            f"mesh={dict(self.mesh_ctx.mesh.shape)} micro_bs={self.train_micro_batch_size_per_gpu()} "
            f"gas={self.gradient_accumulation_steps()}", ranks=[0])

    # ------------------------------------------------------------------
    # state
    # ------------------------------------------------------------------

    def _set_grad_acc(self, acc):
        """``engine.grad_acc`` and its gauge: None until a path that reads
        the buffer asks for it (``_ensure_grad_acc``) or a checkpoint that
        holds one is restored."""
        self.grad_acc = acc
        if self._config.observability_config.enabled:
            from ..observability import get_registry
            get_registry().gauge(
                "ds_grad_acc_bytes",
                "Bytes of the gradient accumulation buffer over all chips "
                "(0 until the unfused forward/backward/step path, an offload "
                "step or a restore makes it: the fused steps never do)"
            ).set(float(sum(x.nbytes for x in jax.tree_util.tree_leaves(acc))))

    def _grad_acc_struct(self):
        """The buffer's abstract tree: the parameters' shapes (the ZeRO-3
        store's where that holds them) at ``grad_accum_dtype`` under
        ``grad_shardings``."""
        return jax.tree_util.tree_map(
            lambda p, s: jax.ShapeDtypeStruct(p.shape, self.grad_accum_dtype,
                                              sharding=s),
            self.params, self.grad_shardings)

    def _ensure_grad_acc(self):
        """The accumulation buffer, zeros on first use."""
        if self.grad_acc is None:
            struct = self._grad_acc_struct()
            self._set_grad_acc(jax.jit(
                lambda: jax.tree_util.tree_map(
                    lambda x: jnp.zeros(x.shape, x.dtype), struct),
                out_shardings=self.grad_shardings)())
        return self.grad_acc

    def _init_state(self, params):
        """Master params fp32 (BF16/FP16 optimizer semantics: reference
        bf16_optimizer.py:34 keeps fp32 master weights), sharded per plan."""
        ctx = self.mesh_ctx
        with self._tracer.scope("ds.init.place_params"):
            # host (numpy) leaves stay on the host until device_put places each
            # shard: jnp.asarray would stage the whole tree on device 0 first
            params = jax.tree_util.tree_map(
                lambda x: (jnp.asarray(x, dtype=jnp.float32)
                           if isinstance(x, jax.Array)
                           else np.asarray(x, dtype=np.float32)), params)
            # Compiler-scheduled ZeRO-3 (runtime/zero3_schedule.py): when the
            # bucketed wire is on and the mesh qualifies, the fp32 masters live
            # as 1/dp-sharded flat buckets (+ replicated persistent leaves)
            # instead of a leaf tree — the optimizer state below is then built
            # OVER the store, so moments shard identically (params+opt ~dp×
            # smaller per chip). Grads are store-shaped too.
            from .zero3_schedule import init_param_store, zero3_store_supported
            self._zero3_store = None
            self._zero3_schedule = None
            if zero3_store_supported(self):
                init_param_store(self, params)  # sets params/param_shardings/_zero3_store
            else:
                self.param_shardings = self.zero_plan.param_shardings(params)
                self.params = jax.device_put(params, self.param_shardings)

        with self._tracer.scope("ds.init.opt_state"):
            self.grad_shardings = (self.param_shardings if self._zero3_store is not None
                                   else self.zero_plan.grad_shardings(params))
            # the accumulation buffer (a tree the size of the parameters) is
            # made by the first path that reads it: the fused steps never do
            self._set_grad_acc(None)

            if self._offload_device in ("cpu", "nvme") and self._offload_ratio >= 1.0:
                # no device opt state at all — that's the HBM saving
                self.opt_state = None
                self.opt_state_shardings = None
                self._build_host_optimizer(params)
            elif self._offload_device in ("cpu", "nvme"):
                # Twin-Flow partial offload: split leaves at the `ratio` element
                # boundary (leaf-greedy ≙ reference sub-group split). Host subset:
                # numpy Adam; device subset: the fused optax path. set_to_zero on
                # the host subset keeps those params untouched by the device
                # program — the host step merges its masters back afterwards.
                from .host_offload import flatten_tree, unflatten_like
                # sizes come from array metadata — no device->host transfer here
                flat = flatten_tree(params)
                total = sum(v.size for v in flat.values())
                budget = self._offload_ratio * total
                cum, labels = 0, {}
                for k, v in flat.items():
                    if cum < budget:
                        labels[k] = "host"
                        self._host_param_names.add(k)
                        cum += v.size
                    else:
                        labels[k] = "device"
                label_tree = unflatten_like(labels, params)
                self._device_tx = optax.multi_transform(
                    {"device": self.base_tx, "host": optax.set_to_zero()}, label_tree)
                opt_state_shape = jax.eval_shape(self._device_tx.init, self.params)
                self.opt_state_shardings = self.zero_plan.opt_state_shardings(opt_state_shape)
                self.opt_state = jax.jit(self._device_tx.init,
                                         out_shardings=self.opt_state_shardings)(self.params)
                self._build_host_optimizer(params, subset=self._host_param_names)
                log_dist(f"Twin-Flow partial offload: {cum}/{total} elements "
                         f"({cum/total:.2f}) on host, rest on device", ranks=[0])
            else:
                opt_state_shape = jax.eval_shape(self.base_tx.init, self.params)
                if self._zero3_store is not None:
                    from .zero3_schedule import store_opt_state_shardings
                    self.opt_state_shardings = store_opt_state_shardings(
                        opt_state_shape, self.param_shardings, self.mesh_ctx)
                else:
                    self.opt_state_shardings = self.zero_plan.opt_state_shardings(opt_state_shape)
                self.opt_state = jax.jit(self.base_tx.init,
                                         out_shardings=self.opt_state_shardings)(self.params)

            # Pin every piece of loop-carried state to an explicit NamedSharding —
            # a leaf whose sharding differs between iterations (eager-created
            # scalars come back SingleDeviceSharding) forces a jit recompile every
            # step.
            repl = self.mesh_ctx.replicated()
            self.scale_state = jax.device_put(self.scaler_cfg.initial_state(), repl)
            self.scale_state_shardings = jax.tree_util.tree_map(lambda _: repl,
                                                                tuple(self.scale_state))
            self._one = jax.device_put(jnp.float32(1.0), repl)

    def _build_host_optimizer(self, params, subset=None):
        """ZeRO-Offload host optimizer (numpy Adam ≙ cpu_adam; NVMe moments
        via the pipelined swapper when device=nvme). `subset` restricts it to
        the Twin-Flow host partition."""
        import numpy as _np
        from .host_offload import HostAdamOptimizer, flatten_tree
        op = dict(self._config.optimizer_params or {})
        name = (self._config.optimizer_name or "adamw").lower()
        if name not in ("adam", "adamw", "adagrad", "lion"):
            raise ValueError(
                f"optimizer offload supports adam/adamw/adagrad/lion, got {name}")
        swapper = None
        if self._offload_device == "nvme":
            from .swap_tensor import PipelinedOptimizerSwapper, AioConfig
            oc = self._config.zero_config.offload_optimizer
            nvme_path = str(getattr(oc, "nvme_path", None) or "/tmp/ds_tpu_offload")
            swapper = PipelinedOptimizerSwapper(
                AioConfig(**(self._config._param_dict.get("aio", {}))),
                swap_folder=nvme_path)
        # flatten first, copy only the leaves this optimizer owns (with a
        # Twin-Flow subset, the device partition never crosses the PCIe)
        host_params = {k: _np.asarray(v, _np.float32)
                       for k, v in flatten_tree(params).items()
                       if subset is None or k in subset}
        # hyperparameters mirror the DEVICE path (optimizers.py) exactly so
        # offloaded runs are numerically interchangeable (adagrad has no
        # weight decay in either path; lion's conventional b2 default is 0.99)
        from .optimizers import ADAM_DEFAULT_BETAS, LION_DEFAULT_BETAS
        default_betas = LION_DEFAULT_BETAS if name == "lion" else ADAM_DEFAULT_BETAS
        self._host_optimizer = HostAdamOptimizer(
            host_params,
            lr=float(op.get("lr", 1e-3)),
            betas=tuple(op.get("betas", default_betas)),
            eps=float(op.get("eps", 1e-8)),
            weight_decay=float(op.get("weight_decay", 0.0)),
            mode=name,
            nvme_swapper=swapper,
            lr_fn=(lambda t: self.get_lr()[0]) if self.lr_scheduler is not None else None)

    # ------------------------------------------------------------------
    # compiled fns
    # ------------------------------------------------------------------

    def _build_compiled_fns(self):
        gas = self.gradient_accumulation_steps()
        compute_dtype = self.compute_dtype
        apply_fn = self.apply_fn
        use_scaling = self._use_loss_scaling
        clip = float(self._config.gradient_clipping or 0.0)
        tx = self._device_tx if self._device_tx is not None else self.base_tx
        scaler_cfg = self.scaler_cfg
        self._grad_comm_layout = None  # set when the bucketed program engages

        # Scheduled ZeRO-3 store: every program below sees the bucket store
        # where it used to see the param tree; materialize_params is the
        # slice-back (under jit, GSPMD turns the sharded-bucket reads into
        # per-bucket all-gathers — the resilience fallback; the scheduled
        # train-batch program places those gathers explicitly instead)
        zmeta = getattr(self, "_zero3_store", None)
        if zmeta is not None:
            from .zero3_schedule import materialize_params as _materialize

        # ZeRO++ qwZ/qgZ: explicit int8-wire param gather (fwd) and gradient
        # reduce-scatter (bwd) instead of XLA's implicit bf16 resharding.
        # Under the bucket store the same int8 wire rides the scheduled
        # bucket gathers (param_gather_bucket) — no per-leaf wrap needed.
        zc = self._config.zero_config
        qwz_gather = None
        if zc.zero_quantized_weights and self.zero_plan.stage >= 3 \
                and self.zero_plan.zero_axes and zmeta is None:
            from .zeropp import make_qwz_param_gather
            qwz_gather = make_qwz_param_gather(self.mesh_ctx, self.param_shardings,
                                               qgz=zc.zero_quantized_gradients,
                                               zero_axes=self.zero_plan.zero_axes)

        apply_with_stats = self._apply_with_stats or (
            lambda *a, **kw: (apply_fn(*a, **kw), {}))

        def loss_from_cparams(cparams, args, kwargs, static_kv, scale):
            out, stats = apply_with_stats(cparams, *args,
                                          **dict(kwargs, **dict(static_kv)))
            if self._loss_fn is not None:
                loss = self._loss_fn(out)
            else:
                loss, _ = _extract_loss(out)
            # scale_loss_by_gas (engine.py:1816) + fp16 loss scaling
            scaled = loss.astype(jnp.float32) / gas
            if use_scaling:
                scaled = scaled * scale
            return scaled, (loss, stats)

        # param_cast="model": pass fp32 masters straight into apply and let
        # the model's use-site casts (flax `dtype=` convention) down-convert
        # each weight where it is consumed. Under nn.scan this is the
        # structural fix for the whole-model-sized convert_element_type
        # temps an engine-side tree cast creates: the stacked [L, ...] leaf
        # is sliced per scan step and only that chunk is cast. Gradients
        # come back fp32 (cotangent of the fp32 primal) — model-sized, same
        # total as engine-cast's bf16 copy + bf16 grads, without the
        # un-schedulable full-tree cast. qwZ keeps engine casts: its int8
        # wire gather must be followed by an explicit up/down-cast.
        cast_in_model = (self._config.param_cast == "model"
                         and qwz_gather is None)

        def gathered(params, qwz=True):
            with _step_scope("gather"):
                if zmeta is not None:
                    params = _materialize(params, zmeta)
                if qwz and qwz_gather is not None:
                    params = qwz_gather(params)
            return params

        def to_compute(params):
            with _step_scope("cast"):
                return jax.tree_util.tree_map(
                    lambda x: x.astype(compute_dtype), params)

        def loss_of(params, args, kwargs, static_kv, scale):
            params = gathered(params)
            if not cast_in_model:
                params = to_compute(params)
            return loss_from_cparams(params, args, kwargs, static_kv, scale)

        def value_and_grads(params, args, kwargs, static_kv, scale):
            """((scaled, (loss, stats)), grads) for one microbatch (``stats``:
            what the model sowed for the host, ``{}`` when nothing). With engine-side
            casting, differentiate wrt the COMPUTE-dtype cast of the params,
            not the fp32 masters, when possible: bit-identical values (the
            cast's VJP is an exact bf16->fp32 up-cast, so the fp32 cotangent
            holds the same bf16-representable numbers), but the grad tree is
            STORED at compute dtype — half the gradient HBM at the
            global-norm barrier, where every grad is live at once, and the
            consumers' up-casts fuse into each leaf's optimizer update /
            accumulate. With param_cast="model" the masters go in as-is and
            grads are fp32."""
            fn = loss_of
            if (compute_dtype != jnp.float32 and qwz_gather is None
                    and zmeta is None and not cast_in_model):
                fn, params = loss_from_cparams, to_compute(params)
            # JAX's own jvp(...), transpose(...) and rematted_computation
            # marks split this scope into forward, backward and recomputation
            with _step_scope("loss"):
                return jax.value_and_grad(fn, has_aux=True)(
                    params, args, kwargs, static_kv, scale)

        def fwd_bwd(params, acc, scale, args, kwargs, static_kv):
            # acc dtype = grad_accum_dtype (fp32 default: full accumulation
            # precision across microbatches; bf16 opt-in halves the buffer)
            (scaled, (loss, _)), grads = value_and_grads(
                params, args, kwargs, static_kv, scale)
            new_acc = jax.tree_util.tree_map(lambda a, g: a + g.astype(a.dtype), acc, grads)
            return loss, new_acc

        self._fwd_bwd = jax.jit(
            fwd_bwd,
            donate_argnums=(1, ),
            static_argnums=(5, ),
            out_shardings=(None, self.grad_shardings),
        )

        def fwd_only(params, args, kwargs, static_kv):
            params = gathered(params, qwz=False)
            if not cast_in_model:
                params = to_compute(params)
            return apply_fn(params, *args, **dict(kwargs, **dict(static_kv)))

        self._fwd_only = jax.jit(fwd_only, static_argnums=(3, ))

        def update_from(params, grads, opt_state, scale_state, scale):
            """Everything of a step after the gradients, for the fused step,
            the K-step scan and the split apply alike: unscale, overflow
            test, global norm and clip, then the optimizer."""
            with _step_scope("grad_norm"):
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32) / scale, grads)
                overflow = has_overflow(grads) if use_scaling else jnp.bool_(False)
                gnorm = optax.global_norm(grads)
                if clip > 0:
                    factor = jnp.minimum(1.0, clip / (gnorm + 1e-6))
                    grads = jax.tree_util.tree_map(lambda g: g * factor, grads)
            with _step_scope("optimizer"):
                updates, new_opt = tx.update(grads, opt_state, params)
                new_params = optax.apply_updates(params, updates)
                if use_scaling:
                    # skip the step entirely on overflow (reference fused_optimizer.py)
                    new_params = _tree_where(overflow, params, new_params)
                    new_opt = _tree_where(overflow, opt_state, new_opt)
                new_scale_state = scaler_cfg.update(scale_state, overflow)
            return new_params, new_opt, new_scale_state, overflow, gnorm

        def apply_step(params, acc, opt_state, scale_state):
            scale = scale_state.cur_scale if use_scaling else jnp.float32(1.0)
            new_params, new_opt, new_scale_state, overflow, gnorm = update_from(
                params, acc, opt_state, scale_state, scale)
            zeroed = jax.tree_util.tree_map(jnp.zeros_like, acc)
            return new_params, new_opt, zeroed, new_scale_state, overflow, gnorm

        # On-device grad-norm/clip for the offload paths (async_pipeline
        # tentpole 2): the old host step pulled EVERY gradient leaf over
        # PCIe just to compute the global norm with numpy. This compiled
        # prep program unscales, norms and clips on device — the host sees
        # the (already clipped) host-subset grads plus two scalars.
        self._offload_prep = None
        if self._host_optimizer is not None:
            from .host_offload import flatten_tree
            prep_subset = (frozenset(self._host_param_names)
                           if self._device_tx is not None else None)

            def offload_prep(acc, scale_state):
                scale = (scale_state.cur_scale if use_scaling
                         else jnp.float32(1.0))
                flat = flatten_tree(acc)
                grads = {k: v.astype(jnp.float32) / scale
                         for k, v in flat.items()}
                # left-fold of per-leaf fp32 sums in flat-key order: a
                # deterministic reduction the parity test mirrors on host
                sq = jnp.float32(0.0)
                for k in grads:
                    sq = sq + jnp.sum(jnp.square(grads[k]))
                gnorm = jnp.sqrt(sq)
                # non-finite sum ⇔ the old host path's overflow predicate
                overflow = ~jnp.isfinite(sq)
                if clip > 0:
                    factor = jnp.where(
                        overflow, jnp.float32(1.0),
                        jnp.minimum(1.0, clip / (gnorm + 1e-6)))
                    grads = {k: g * factor for k, g in grads.items()}
                out = {k: g for k, g in grads.items()
                       if prep_subset is None or k in prep_subset}
                return out, overflow, gnorm

            self._offload_prep = jax.jit(offload_prep)

        from .loss_scaler import LossScaleState
        scale_out = LossScaleState(*self.scale_state_shardings)
        repl = self.mesh_ctx.replicated()
        if self._host_optimizer is not None and self._device_tx is None:
            # full ZeRO-Offload: the optimizer step happens on host; no device
            # apply program exists (its state would defeat the offload)
            self._apply_step = None
            self._train_step_fused = None
            self._train_steps_fused = None
            self._train_batch_fused = None
            return
        self._apply_step = jax.jit(
            apply_step,
            donate_argnums=(0, 1, 2),
            out_shardings=(self.param_shardings, self.opt_state_shardings, self.grad_shardings,
                           scale_out, repl, repl),
        )

        # gas=1 fast path: fwd+bwd+optimizer fused into ONE XLA program — no
        # grad-accumulation buffer materialized in HBM and one dispatch per
        # step instead of two (the reference necessarily splits these across
        # host-driven kernel launches; under XLA the fusion is free win)
        def train_step(params, opt_state, scale_state, args, kwargs, static_kv):
            scale = scale_state.cur_scale if use_scaling else jnp.float32(1.0)
            (_, (loss, stats)), grads = value_and_grads(
                params, args, kwargs, static_kv, scale)
            new_params, new_opt, new_scale_state, overflow, gnorm = update_from(
                params, grads, opt_state, scale_state, scale)
            return (loss, new_params, new_opt, new_scale_state, overflow, gnorm,
                    stats)

        self._train_step_fused = jax.jit(
            train_step,
            donate_argnums=(0, 1),
            static_argnums=(5, ),
            out_shardings=(None, self.param_shardings, self.opt_state_shardings,
                           scale_out, repl, repl, repl),
        ) if gas == 1 and self._device_tx is None else None
        # (Twin-Flow needs the materialized grad buffer to snapshot the host
        # subset, so the one-program fused path is off under partial offload)

        # Multi-step fusion: K OPTIMIZER STEPS in one XLA program — a
        # lax.scan whose carry is (params, opt_state, scale_state) and whose
        # xs are K stacked batches. One host dispatch per K steps amortizes
        # the per-dispatch host latency to nothing; the schedule
        # stays exact because optax's injected lr_fn reads the update count
        # carried in opt_state. HLO size == one step's body (scan compiles
        # the body once), so compile time does not grow with K. The torch
        # reference cannot express this — its optimizer step is host-driven
        # by construction; under XLA it is one more scan.
        def train_steps(params, opt_state, scale_state, stacked_args,
                        stacked_kwargs, static_kv):
            def one(carry, batch):
                p, o, s = carry
                b_args, b_kwargs = batch
                loss, p, o, s, overflow, gnorm, stats = train_step(
                    p, o, s, b_args, b_kwargs, static_kv)
                return (p, o, s), (loss, overflow, gnorm, stats)

            (p, o, s), (losses, overflows, gnorms, stats) = jax.lax.scan(
                one, (params, opt_state, scale_state),
                (stacked_args, stacked_kwargs))
            return losses, p, o, s, overflows, gnorms, stats

        self._train_steps_fused = jax.jit(
            train_steps,
            donate_argnums=(0, 1),
            static_argnums=(5, ),
            out_shardings=(None, self.param_shardings, self.opt_state_shardings,
                           scale_out, repl, repl, repl),
        ) if self._train_step_fused is not None else None

        # 1-bit compressed WIRE program (reference runtime/comm/nccl.py:16):
        # post-warmup steps exchange packed sign bits instead of fp32 grads.
        # Opt-in via optimizer.params.comm_backend_name (the reference's knob).
        self._wire_step = None
        self._wire_freeze_step = 0
        opname = (self._config.optimizer_name or "").lower()
        op = self._config.optimizer_params or {}
        if (opname in ("onebitadam", "onebitlamb") and op.get("comm_backend_name")
                and self._train_step_fused is not None):
            if self.client_optimizer is not None:
                # a client tx has a different opt-state pytree than the wire
                # program's chain — surface the conflict, don't compress
                logger.warning("1-bit wire program disabled: a client optimizer "
                               "was passed to initialize(); gradients exchange "
                               "uncompressed fp32")
            else:
                from .onebit_wire import build_wire_step, wire_supported
                if wire_supported(self):
                    self._wire_step = build_wire_step(self, opname)
                    self._wire_freeze_step = int(op.get("freeze_step", 100000))
                else:
                    logger.warning("1-bit wire program unavailable (its "
                                   "stateful optimizer-side compression needs "
                                   "gas=1, unpartitioned gradients [ZeRO stage "
                                   "0], bf16/fp32, a pure-DP mesh, and no "
                                   "gradient clipping); falling back to fp32 "
                                   "reduce — consider gradient_comm's onebit "
                                   "tier, which composes with ZeRO stages 1-3")

        # gas>1 fused batch: lax.scan over stacked microbatches + optimizer
        # apply, all in ONE XLA program (one dispatch per optimizer step
        # instead of gas+1; the grad-accumulation buffer is a scan carry, and
        # only one microbatch's activations are live at a time)
        def train_batch_steps(params, opt_state, scale_state, stacked_args, static_kv):
            scale = scale_state.cur_scale if use_scaling else jnp.float32(1.0)
            acc_dtype = self.grad_accum_dtype
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, acc_dtype), params)

            def micro(carry, margs):
                acc, loss_sum = carry
                loss, acc = fwd_bwd(params, acc, scale, margs, {}, static_kv)
                return (acc, loss_sum + loss), None

            (acc, loss_sum), _ = jax.lax.scan(micro, (zeros, jnp.float32(0.0)),
                                              stacked_args)
            outs = apply_step(params, acc, opt_state, scale_state)
            new_params, new_opt, _, new_scale_state, overflow, gnorm = outs
            return (loss_sum / gas, new_params, new_opt, new_scale_state,
                    overflow, gnorm)

        self._train_batch_fused = jax.jit(
            train_batch_steps,
            donate_argnums=(0, 1),
            static_argnums=(4, ),
            out_shardings=(None, self.param_shardings, self.opt_state_shardings,
                           scale_out, repl, repl),
        ) if gas > 1 and self._device_tx is None and self._host_optimizer is None \
            else None

        # Bucketed + quantized gradient collectives with microbatch overlap
        # (gradient_comm config; comm/bucketing.py + grad_comm.py): replaces
        # the implicit GSPMD boundary reduce with explicit per-bucket
        # reduce-scatter/all-gather through the configured wire tier,
        # optionally issued per microbatch inside the scan (overlap_comm).
        gcc = self._config.gradient_comm_config
        if (gcc.active and self._device_tx is None
                and self._host_optimizer is None and self._wire_step is None):
            from .grad_comm import build_grad_comm_step, grad_comm_supported
            if grad_comm_supported(self):
                step_fn, layout = build_grad_comm_step(self, apply_step)
                self._train_batch_fused = step_fn
                self._grad_comm_layout = layout
                # route train_batch through the bucketed program (gas=1 runs
                # as a 1-microbatch scan); the K-step fused scan and the
                # split forward/backward/step API keep the default reduce
                self._train_step_fused = None
                self._train_steps_fused = None
            else:
                logger.warning(
                    "gradient_comm requested but unsupported here (needs a "
                    "pure data-parallel mesh, ZeRO stage <= 3, bf16/fp32, "
                    "device optimizer; the stage-3 scheduled store further "
                    "excludes optimizer offload, composed tensor-parallel "
                    "training, and meshes whose ZeRO axes don't span the "
                    "full dp world); gradients exchange via the default "
                    "GSPMD reduce")

    def _watch_compiled_fns(self):
        """Compile observability: wrap every jitted step program in a
        ``WatchedJit`` so compile vs cache-hit vs retrace is counted per
        compile key and the MFU publisher can cost-analyze each dispatched
        program. Runs after every ``_build_compiled_fns`` (idempotent on
        already-wrapped programs); transparent to the flops profiler and
        the grad-comm path (``WatchedJit`` forwards attribute access)."""
        obs = getattr(self, "_train_obs", None)
        if obs is None or not self._config.observability_config.compile_watch:
            return
        w = obs.watch_program
        self._fwd_bwd = w(self._fwd_bwd, "train_fwd_bwd")
        self._fwd_only = w(self._fwd_only, "eval_fwd")
        self._apply_step = w(self._apply_step, "optimizer_apply")
        if getattr(self, "_offload_prep", None) is not None:
            self._offload_prep = w(self._offload_prep, "offload_prep")
        if getattr(self, "_train_step_fused", None) is not None:
            self._train_step_fused = w(self._train_step_fused,
                                       "train_step_fused")
        if getattr(self, "_train_steps_fused", None) is not None:
            self._train_steps_fused = w(self._train_steps_fused,
                                        "train_steps_fused")
        if getattr(self, "_train_batch_fused", None) is not None \
                and not getattr(self._train_batch_fused, "_zero3_scheduled",
                                False):
            # the scheduled ZeRO-3 entry is a lazy python wrapper; its inner
            # jit is watched at build time under "zero3_scheduled_step"
            self._train_batch_fused = w(self._train_batch_fused,
                                        "train_batch_fused")
        if getattr(self, "_wire_step", None) is not None:
            self._wire_step = w(self._wire_step, "onebit_wire_step")

    # ------------------------------------------------------------------
    # train API (reference engine.py:1838/:1977/:2176)
    # ------------------------------------------------------------------

    def _build_curriculum_sampler(self, training_data):
        """``data_efficiency.data_sampling.curriculum_learning`` → a
        difficulty-gated DeepSpeedDataSampler over the analyzer's metric
        files (reference deepspeed_io consuming data_sampling config;
        ``data_sampling/data_sampler.py:36``). Returns None when disabled.

        Under single-controller SPMD the sampler draws the GLOBAL batch
        (dp_size=1, micro = per-device micro × dp world); the engine's
        batch sharding splits it over devices."""
        ds_cfg = (self._config.data_efficiency_config or {}).get("data_sampling", {})
        cl = ds_cfg.get("curriculum_learning", {})
        if not (ds_cfg.get("enabled", False) and cl.get("enabled", False)):
            return None
        metrics = cl.get("curriculum_metrics", {})
        if len(metrics) != 1:
            raise ValueError(
                "data_sampling.curriculum_learning.curriculum_metrics must "
                f"contain exactly one metric (got {sorted(metrics)}); the "
                "reference's multi-metric clustering is not implemented")
        from .data_pipeline.curriculum_scheduler import CurriculumScheduler
        from .data_pipeline.data_analyzer import load_metric
        from .data_pipeline.data_sampler import DeepSpeedDataSampler
        name, m = next(iter(metrics.items()))
        values = load_metric(m["metric_path"], name)
        if len(values) != len(training_data):
            raise ValueError(
                f"metric '{name}' covers {len(values)} samples but the "
                f"dataset has {len(training_data)} — rerun the data analyzer")
        sched = CurriculumScheduler({
            "curriculum_type": name,
            "min_difficulty": m["min_difficulty"],
            "max_difficulty": m["max_difficulty"],
            "schedule_type": m.get("schedule_type", "fixed_linear"),
            "schedule_config": m.get("schedule_config", {})})
        return DeepSpeedDataSampler(
            total_samples=len(training_data),
            micro_batch_size=self.train_micro_batch_size_per_gpu() * self.dp_world_size,
            gradient_accumulation_steps=self.gradient_accumulation_steps(),
            curriculum_scheduler=sched, metric_values=values,
            shuffle=ds_cfg.get("shuffle", True),
            seed=ds_cfg.get("seed", 1234))

    # ------------------------------------------------------------------
    # resilience: preemption autosave, anomaly sentry + rollback
    # ------------------------------------------------------------------

    def _init_resilience(self):
        rc = self._config.resilience_config
        self._resilience = rc
        self._sentry = None
        self._preempted = False
        self.preempt_count = 0
        self._autosave_requested = False
        self._last_good_tag = None
        self._resilience_save_dir = rc.save_dir
        self._signal_prev_handlers = {}
        if not rc.enabled:
            return
        if rc.fault_injection.enabled:
            get_fault_injector().configure(rc.fault_injection)
        from .sentry import AnomalySentry
        self._sentry = AnomalySentry(
            max_consecutive=rc.max_consecutive_anomalies,
            spike_window=rc.loss_spike_window,
            spike_factor=rc.loss_spike_factor,
            spike_min_history=rc.loss_spike_min_history,
            monitor=self.monitor)
        if rc.preempt_save:
            self._install_preempt_handlers()
        if rc.auto_resume and rc.save_dir:
            # scan for the newest checkpoint that passes manifest
            # verification (NOT blindly `latest`: after a crash the pointer
            # may name a torn dir) and resume from it
            path, _ = self.load_checkpoint(rc.save_dir)
            if path is not None:
                log_dist(f"[resilience] auto-resumed from {path} at step "
                         f"{self.global_steps}", ranks=[0])

    def _install_preempt_handlers(self):
        import signal
        for name in self._resilience.preempt_signals:
            sig = getattr(signal, name, None)
            if sig is None:
                continue
            try:
                prev = signal.signal(sig, self._on_preempt_signal)
            except (ValueError, OSError):  # not the main thread
                continue
            self._signal_prev_handlers[sig] = prev

    def _remove_preempt_handlers(self):
        import signal
        for sig, prev in getattr(self, "_signal_prev_handlers", {}).items():
            try:
                signal.signal(sig, prev)
            except (ValueError, OSError):
                pass
        self._signal_prev_handlers = {}

    def _on_preempt_signal(self, signum, frame):
        # async-signal context: set flags only; the save happens at the next
        # step boundary where the engine's state is consistent
        self._preempted = True
        self.preempt_count += 1
        logger.warning(f"[resilience] signal {signum} received; checkpoint "
                       "will be saved at the next step boundary")

    @property
    def preempted(self) -> bool:
        return self.preempt_count > 0

    def _resilience_step_boundary(self, loss=None, overflow=None,
                                  losses_vec=None, overflows_vec=None):
        """Per-optimizer-step resilience hook (all four train paths).

        Sync mode feeds the sentry here; async mode feeds it at the window
        drain (the fetched values already exist there — no extra sync).
        Autosave/preemption saves always run here: ``save_checkpoint`` drains
        the async window itself, so the snapshot is exact either way."""
        rc = self._resilience
        if not rc.enabled:
            return
        fi = get_fault_injector()
        if fi.enabled and fi.fire("train.sigterm") is not None:
            import signal
            os.kill(os.getpid(), signal.SIGTERM)
        if self._sentry is not None and self._async_window is None:
            with self._tracer.scope("ds.train.loss_read"):
                if losses_vec is not None:
                    lv = np.asarray(host_fetch(losses_vec)).ravel()
                    ov = (np.asarray(host_fetch(overflows_vec)).ravel()
                          if overflows_vec is not None else np.zeros(len(lv)))
                    base = self.global_steps - len(lv)
                    obs = [(float(l), bool(o), base + i + 1)
                           for i, (l, o) in enumerate(zip(lv, ov))]
                else:
                    l = (None if loss is None
                         else float(np.asarray(host_fetch(loss)).ravel()[-1]))
                    o = (bool(host_fetch(overflow))
                         if overflow is not None and self._use_loss_scaling else False)
                    obs = [(l, o, self.global_steps)]
            for l, o, s in obs:
                self._sentry.observe(l, o, s)
                if self._sentry.should_rollback:
                    self._rollback_to_last_good()
                    break
        if (rc.autosave_interval_steps and self.global_steps > 0
                and self.global_steps % rc.autosave_interval_steps == 0):
            self._autosave_requested = True
        if self._preempted and rc.preempt_save:
            self._autosave_requested = True
            self._preempted = False  # one save per preemption notice
        if self._autosave_requested and self._resilience_save_dir:
            self._autosave_requested = False
            ok = self.save_checkpoint(self._resilience_save_dir)
            log_dist(f"[resilience] autosave at step {self.global_steps}: "
                     f"{'committed' if ok else 'FAILED'}", ranks=[0])

    def _sentry_observe_window(self, entries, fetched):
        """Async path: feed the sentry from the drain's already-fetched
        (loss, overflow) window, newest-last; roll back at most once."""
        base = self.global_steps
        total = sum(steps for steps, _, _ in entries)
        step = base - total
        for (steps, _, _), (loss_h, ovf_h) in zip(entries, fetched):
            lv = (np.asarray(loss_h).ravel() if loss_h is not None
                  else np.asarray([np.nan] * steps))
            ov = np.asarray(ovf_h).ravel() if ovf_h is not None else np.zeros(steps)
            if len(lv) < steps:
                lv = np.resize(lv, steps)
            if len(ov) < steps:
                ov = np.resize(ov, steps)
            for i in range(steps):
                step += 1
                l = float(lv[i]) if loss_h is not None else None
                self._sentry.observe(l, bool(ov[i]) and self._use_loss_scaling,
                                     step)
                if self._sentry.should_rollback:
                    self._rollback_to_last_good()
                    return

    def _rollback_to_last_good(self) -> bool:
        """Anomaly recovery: restore params/opt-state/counters from the last
        good checkpoint, but KEEP the data sampler's current position — the
        offending data window is skipped, not replayed (replaying it would
        reproduce the same anomaly)."""
        rc = self._resilience
        self._sentry.reset()
        if not rc.rollback or not self._resilience_save_dir:
            logger.warning("[resilience] anomaly threshold hit but rollback "
                           "is disabled or no save_dir is configured")
            return False
        sampler = getattr(self.training_dataloader, "sampler", None) \
            if self.training_dataloader is not None else None
        sampler_sd = sampler.state_dict() \
            if sampler is not None and hasattr(sampler, "state_dict") else None
        tag = self._last_good_tag or \
            find_latest_valid_checkpoint(self._resilience_save_dir)
        if tag is None:
            logger.warning("[resilience] no valid checkpoint to roll back to")
            return False
        try:
            # goodput: the whole excursion (incl. the inner load_checkpoint,
            # whose nested span folds into this one) is "anomaly_rollback"
            with self._obs_span("anomaly_rollback"):
                path, _ = self.load_checkpoint(self._resilience_save_dir,
                                               tag=tag)
        except CheckpointCorruptionError as e:
            logger.error(f"[resilience] rollback target is corrupt: {e}")
            return False
        if path is None:
            return False
        if sampler_sd is not None:
            # load_checkpoint rewound the sampler with everything else;
            # restore its pre-rollback position to skip the bad window
            sampler.load_state_dict(sampler_sd)
        self._sentry.note_rollback(tag, self.global_steps)
        return True

    def _apply_data_efficiency(self, args, kwargs):
        """Per-micro-batch data-efficiency hooks (reference engine.py:1877-1883):
        advance the curriculum and truncate the batch to the current seqlen
        difficulty; advance random-LTD and inject its keep-count. Seqlen
        truncation changes array shapes, so each difficulty level compiles
        once — ``difficulty_step`` bounds the number of distinct programs."""
        fi = get_fault_injector()
        if fi.enabled and fi.fire("train.nan_grads") is not None:
            # poison the micro-batch's float inputs: forward produces a NaN
            # loss, backward NaN grads — the sentry must catch the episode
            def _poison(x):
                if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
                    return jnp.full_like(x, jnp.nan)
                return x
            args = jax.tree_util.tree_map(_poison, args)
        if self.curriculum_scheduler_legacy is not None:
            self.curriculum_scheduler_legacy.update_difficulty(self.global_steps + 1)
            if self._config.curriculum_params_legacy.get("curriculum_type") == "seqlen":
                L = int(self.curriculum_scheduler_legacy.get_current_difficulty())
                # the canonical sequence length is axis 1 of the first array
                # arg (input ids); ONLY axes of that exact length are
                # truncated, so (B, F) feature arrays and unrelated dims pass
                # through; (B, S, S) masks get both seq axes cut
                leaves = [x for x in jax.tree_util.tree_leaves(args)
                          if hasattr(x, "ndim") and x.ndim >= 2]
                S = leaves[0].shape[1] if leaves else None

                def trunc(x):
                    if S is None or L >= S or not hasattr(x, "ndim"):
                        return x
                    for axis in (1, 2):
                        if x.ndim > axis and x.shape[axis] == S:
                            x = jax.lax.slice_in_dim(x, 0, L, axis=axis)
                    return x

                args = jax.tree_util.tree_map(trunc, args)
                kwargs = jax.tree_util.tree_map(trunc, kwargs)
        if self.random_ltd_scheduler is not None:
            self.random_ltd_scheduler.update_seq(self.global_steps)
            if self._ltd_kwarg:
                kwargs = dict(kwargs)
                kwargs["random_ltd_keep"] = int(self.random_ltd_scheduler.get_current_seq())
        return args, kwargs

    def train(self, mode: bool = True):
        """Torch-style mode switch (reference engine is an nn.Module). In
        eval mode ``forward()`` runs the grad-free compiled path — a ported
        eval loop that calls ``engine.eval(); engine.forward(batch)`` does
        NOT silently pay a full backward."""
        self._training = bool(mode)
        return self

    def eval(self):
        return self.train(False)

    def forward(self, *args, **kwargs):
        """Compute loss AND cache gradients (see module docstring). After
        ``engine.eval()`` this is forward-only (identical to
        ``eval_batch``); ``engine.train()`` restores the fused
        grad-at-forward training path."""
        if not self._training:
            return self.eval_batch(*args, **kwargs)
        if self._pending is not None:
            # forward() accumulates grads at forward time (module docstring);
            # a second forward without backward() would silently contaminate
            # the accumulation buffer — the reference's forward is pure, so
            # ported eval loops must use eval_batch()/module_forward()
            raise RuntimeError(
                "forward() called twice without backward(); for inference/eval "
                "use engine.eval() (then forward() is grad-free), eval_batch() "
                "or module_forward()")
        self.timers(FORWARD_MICRO_TIMER).start()
        scale = self.scale_state.cur_scale if self._use_loss_scaling else self._one
        args, kwargs = self._apply_data_efficiency(args, kwargs)
        kwargs, static_kv = _split_static_kwargs(kwargs)
        args = jax.device_put(args, self.zero_plan.batch_sharding(args))
        kwargs = jax.device_put(kwargs, self.zero_plan.batch_sharding(kwargs))
        loss, new_acc = self._fwd_bwd(self.params, self._ensure_grad_acc(), scale,
                                      args, kwargs, static_kv)
        # grad_acc was donated; keep the new buffer, commit on backward()
        self.grad_acc = new_acc
        self._pending = loss
        # abstract arg spec for the flops profiler's cost analysis
        self.last_fwd_spec = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype) if hasattr(x, "shape") else x,
            (self.params, self.grad_acc, scale, args, kwargs, static_kv))
        # AFTER the spec records THIS step's shapes (curriculum can resize
        # per step); dispatch above is async, so the timing window still
        # covers the device execution
        self._flops_profile_pre()
        self.timers(FORWARD_MICRO_TIMER).stop()
        return loss

    __call__ = forward

    def backward(self, loss, retain_graph=False, scale_wrt_gas=True):
        """Commit the pending accumulated grads (bookkeeping; compute happened
        fused with forward)."""
        assert self._pending is not None, "backward() called without a preceding forward()"
        self.timers(BACKWARD_MICRO_TIMER).start()
        self._pending = None
        self.losses = loss
        self.micro_steps += 1
        self.timers(BACKWARD_MICRO_TIMER).stop()
        return loss

    def is_gradient_accumulation_boundary(self):
        return (self.micro_steps % self.gradient_accumulation_steps()) == 0

    def _flops_profile_pre(self, step_fn=None, step_args=None, steps: int = 1):
        """Reference engine.py flops-profiler hooks: the engine itself starts
        the profile when global_steps reaches ``profile_step`` — the config
        knob used to be accepted and silently ignored (a user enabling
        ``flops_profiler`` got no output without driving the profiler by
        hand). ``step_fn``/``step_args``: the fused one-program step, whose
        exact compiled cost is recorded (the split path's cost comes from
        ``last_fwd_spec`` inside ``start_profile``). ``steps``: how many
        real optimizer steps the upcoming dispatch covers — a K-step fused
        dispatch must trigger when profile_step falls anywhere inside
        [global_steps, global_steps + K)."""
        fp = self.flops_profiler
        c = self._config.flops_profiler_config
        if (fp is None or fp.started
                or not (self.global_steps <= c.profile_step
                        < self.global_steps + steps)):
            return
        # the fused program already contains fwd+bwd+step: accruing the
        # split-path _fwd_bwd cost too would double the reported flops
        fp.start_profile(skip_engine_cost=step_fn is not None)
        self._flops_auto_active = True
        if step_fn is not None and step_args is not None:
            try:
                fp.profile_fn(step_fn, *step_args)
            except Exception as e:  # noqa: BLE001 — cost analysis best-effort
                logger.debug(f"flops profiler: fused cost analysis skipped: {e}")

    def _flops_profile_post(self):
        fp = self.flops_profiler
        c = self._config.flops_profiler_config
        if (fp is None or not fp.started or self.global_steps <= c.profile_step
                or not getattr(self, "_flops_auto_active", False)):
            # only close sessions the auto-hook opened — a profile the USER
            # started via the manual reference API is theirs to stop/print
            return
        self._flops_auto_active = False
        fp.stop_profile()
        fp.print_model_profile(profile_step=c.profile_step,
                               module_depth=c.module_depth,
                               top_modules=c.top_modules, detailed=c.detailed,
                               output_file=c.output_file,
                               batch_tokens=self.train_batch_size())
        fp.end_profile()

    def step(self, lr_kwargs=None):
        """Optimizer step at gradient-accumulation boundaries (engine.py:2176)."""
        self.timers(STEP_MICRO_TIMER).start()
        if self.is_gradient_accumulation_boundary() and self.micro_steps > 0:
            self.tput_timer.start()
            if self._host_optimizer is not None and self._device_tx is not None:
                overflow, gnorm = self._partial_offload_step()
            elif self._host_optimizer is not None:
                overflow, gnorm = self._host_offload_step()
            else:
                (self.params, self.opt_state, self.grad_acc, self.scale_state, overflow,
                 gnorm) = self._apply_step(self.params, self._ensure_grad_acc(),
                                           self.opt_state, self.scale_state)
            self._last_grad_norm = gnorm
            self.global_steps += 1
            self.global_samples += self.train_batch_size()
            self.tput_timer.stop(global_step=True)
            self._obs_step_mark(1)
            if (self._async_window is not None
                    and self._host_optimizer is None):
                # windowed host sync: overflow stays a device scalar; every
                # per-step host decision (skip accounting, schedule advance,
                # monitor, print cadence) is reconciled at the drain
                self._push_async_step(self.losses, overflow)
            else:
                if self._use_loss_scaling:
                    # host sync only for logging cadence; cheap scalar
                    if bool(overflow):
                        self.skipped_steps += 1
                        log_dist(f"[deepspeed] OVERFLOW! Skipping step. New loss scale: "
                                 f"{float(self.scale_state.cur_scale)}", ranks=[0])
                    else:
                        self._advance_schedule()
                else:
                    self._advance_schedule()
                if self.monitor is not None and self.losses is not None:
                    self.monitor.write_events([("Train/Samples/train_loss", float(self.losses),
                                                self.global_samples)])
                self._publish_registry_events()
                if self._config.steps_per_print and self.global_steps % self._config.steps_per_print == 0:
                    log_dist(
                        f"step={self.global_steps}, skipped={self.skipped_steps}, "
                        f"lr={self.get_lr()}, loss={float(self.losses) if self.losses is not None else None}",
                        ranks=[0])
            self._flops_profile_post()
            self._resilience_step_boundary(loss=self.losses, overflow=overflow)
        self.timers(STEP_MICRO_TIMER).stop()

    def _host_offload_step(self):
        """ZeRO-Offload step, pipelined (reference stage_1_and_2.py cpu-offload
        + cpu_adam + pipelined_optimizer_swapper.py overlap):

        1. the compiled prep program unscales, global-norms and clips ON
           DEVICE (async_pipeline tentpole 2 — no grad leaf crosses PCIe
           for the norm; only the overflow/gnorm scalars do);
        2. async device→host copies for every (clipped) grad leaf kick off
           up front — the per-leaf readbacks below then wait only for their
           own leaf while the rest stream in the background;
        3. the Adam pass updates one leaf at a time and immediately kicks its
           async host→device upload — uploads overlap the remaining leaves'
           host math (double buffering without CUDA streams)."""
        from .host_offload import flatten_tree, unflatten_like
        clipped, overflow_d, gnorm_d = self._offload_prep(self._ensure_grad_acc(),
                                                          self.scale_state)
        for v in clipped.values():
            if hasattr(v, "copy_to_host_async"):
                v.copy_to_host_async()
        overflow_h, gnorm_h = host_fetch((overflow_d, gnorm_d))
        overflow, gnorm = bool(overflow_h), float(gnorm_h)
        if not overflow:
            flat_s = flatten_tree(self.param_shardings)
            names = list(clipped.keys())
            self._host_optimizer.step_begin()
            new_flat = {}
            for i, k in enumerate(names):
                g = np.asarray(clipped[k])
                p_new = self._host_optimizer.step_param(
                    k, g, prefetch=names[i + 1] if i + 1 < len(names) else None)
                # async dispatch: this upload flies while the next leaf steps
                # (numpy straight to the target sharding — one transfer)
                new_flat[k] = jax.device_put(p_new, flat_s[k])
            self._host_optimizer.step_end()
            self.params = unflatten_like(new_flat, self.params)
        if self._use_loss_scaling:
            self.scale_state = self.scaler_cfg.update(self.scale_state, jnp.bool_(overflow))
        self.grad_acc = jax.tree_util.tree_map(
            lambda g: jax.device_put(jnp.zeros(g.shape, g.dtype), g.sharding),
            self.grad_acc)
        return overflow, gnorm

    def _partial_offload_step(self):
        """Twin-Flow (Offload++) step: snapshot the host-subset grads, kick the
        device-subset program (async XLA dispatch), then run host Adam WHILE
        the device program executes — the overlap the reference gets from CUDA
        streams (blogs/deepspeed-offloadpp/README.md:10) falls out of XLA's
        async dispatch. Finally merge host masters back into the param tree.

        Unscale + global-norm + clip happen ON DEVICE in the compiled prep
        program (async_pipeline tentpole 2) BEFORE the apply program donates
        grad_acc: the host subset arrives over PCIe already clipped, so —
        unlike the old host-side clip — a gradient-clipping config no longer
        forces a device/host serialization point; only fp16 loss scaling
        still syncs one scalar (the host Adam must know whether to skip)."""
        from .host_offload import flatten_tree, unflatten_like
        clipped, overflow_d, _ = self._offload_prep(self._ensure_grad_acc(),
                                                    self.scale_state)
        for v in clipped.values():
            if hasattr(v, "copy_to_host_async"):
                v.copy_to_host_async()
        # device subset steps in its compiled program (donates grad_acc/opt);
        # host params pass through it unchanged (set_to_zero)
        (params, self.opt_state, self.grad_acc, self.scale_state, overflow,
         gnorm) = self._apply_step(self.params, self.grad_acc, self.opt_state,
                                   self.scale_state)
        overflow_b = (bool(host_fetch(overflow_d))
                      if self._use_loss_scaling else False)
        if not overflow_b:
            # np.asarray blocks only on the host-subset leaves, whose async
            # copies started before the device apply dispatched
            master = self._host_optimizer.step(
                {k: np.asarray(v) for k, v in clipped.items()})
            flat_p = flatten_tree(params)
            flat_s = flatten_tree(self.param_shardings)
            for k in self._host_param_names:
                flat_p[k] = jax.device_put(master[k], flat_s[k])
            params = unflatten_like(flat_p, params)
        self.params = params
        return overflow_b, gnorm

    def _advance_schedule(self):
        if self.lr_scheduler is not None and hasattr(self.lr_scheduler, "step"):
            self.lr_scheduler.step()
        from ..observability import get_registry
        get_registry().counter(
            "ds_train_steps_total", "Effective (non-skipped) optimizer steps"
        ).inc()

    def _obs_step_mark(self, steps=1):
        """Per-optimizer-step observability boundary: record the step-wall
        histogram sample(s) and attribute the interval to goodput
        "useful_step". Host-only (one perf_counter + histogram bump) —
        never syncs the device."""
        obs = self._train_obs
        if obs is not None:
            obs.step_mark(steps)

    def _obs_span(self, category):
        """Goodput span for an excursion (checkpoint save/load, rollback,
        host-sync stall); nullcontext when observability is off."""
        obs = getattr(self, "_train_obs", None)
        if obs is not None and obs.ledger is not None:
            return obs.ledger.span(category)
        return nullcontext()

    def _publish_layer_kinds(self, model):
        """``ds_model_layers{kind="<operator>+<ffn>"}``: how many decoder
        layers of each kind the model was built with, for a model whose
        config spells its layers out (``LlamaConfig.layer_specs``)."""
        cfg = getattr(model, "config", None)
        specs = getattr(cfg, "layer_specs", None)
        if not self._config.observability_config.enabled:
            return
        from collections import Counter
        from ..observability import get_registry
        kinds = Counter(f"{s.operator}+{s.ffn}" for s in specs or ())
        if not specs and getattr(cfg, "block_diffusion_", False):
            # alike layers are counted too where the objective is not the
            # default: the kind then says what such a step runs
            ffn = "moe" if cfg.num_local_experts > 0 else "dense"
            kinds[f"attention+{ffn}"] = cfg.num_hidden_layers
        for kind, n in kinds.items():
            get_registry().gauge(
                "ds_model_layers", "Decoder layers by kind (operator+ffn)",
                labels={"kind": kind}).set(float(n))
        if any(s.kv_from >= 0 or s.memory_from >= 0 for s in specs or ()):
            for name, what, n in (
                    ("ds_model_shared_kv_readers", "the keys and values of an earlier "
                     "layer", sum(s.kv_from >= 0 for s in specs)),
                    ("ds_model_shared_memory_readers", "the scan output of an earlier "
                     "state-space layer", sum(s.memory_from >= 0 for s in specs))):
                get_registry().gauge(
                    name, f"Decoder layers that read {what} (passed beside the "
                    "residual stream)").set(float(n))

    def _publish_sown_stats(self):
        """What the model's operators sowed in the fused steps dispatched
        since the last call, in one fetch, as the gauges and counters their
        families declare. Called before the newest step's stats are held,
        so what it reads has been computed (the step before was read, or
        the window drained) and the device never waits for it: the gauges
        lag the step counter by one dispatch."""
        if not self._sown_pending:
            return
        from ..observability import get_registry
        fetched, self._sown_pending = host_fetch(self._sown_pending), []
        reg = get_registry()
        for family in self._sown_families:
            steps = [family.of(step) for step in fetched]
            if not steps[0]:
                continue
            derived = family.derive(steps) if family.derive else {}
            for gauge in family.gauges:
                if gauge.steps is None:
                    value = derived.get(gauge.source)
                elif gauge.source in steps[0]:
                    value = gauge.steps([step[gauge.source] for step in steps])
                else:
                    continue
                if value is not None:
                    gauge.publish(reg, value)
        if not self._kernel_line_logged:
            # once, after the first step that sowed was traced: which grouped
            # matmul this engine's call sites took, by pass (what
            # ds_moe_gmm_traced_total counted since the engine was built)
            from ..ops.grouped_matmul import traced_counts, traced_note
            self._kernel_line_logged = True
            if traced_counts() != self._gmm_traced_before:
                log_dist(f"kernels: {traced_note(self._gmm_traced_before)}",
                         ranks=[0])

    def _publish_registry_events(self, window_start=None, window_len=None):
        """Registry publish cadence: refresh derived observability views
        (MFU, memory, goodput fraction), fan the registry into the monitor
        bridge (``monitor.registry_events``), and rewrite the Prometheus
        textfile. Async windows pass ``window_start``/``window_len`` so the
        events are stamped at the step the window STARTED on plus an
        explicit length event — stamping the drain-time ``global_steps``
        attributed a whole window's metrics to its last step."""
        if self._train_obs is not None:
            self._train_obs.publish()
        if (self.monitor is not None
                and self._config.monitor_config.registry_events):
            step = self.global_steps if window_start is None else window_start
            self.monitor.write_registry(step, window_len=window_len)
        if self._obs_textfile:
            from ..observability import get_registry
            try:
                get_registry().write_textfile(self._obs_textfile)
            except OSError as e:
                logger.warning(
                    f"observability textfile export to "
                    f"{self._obs_textfile} failed: {e}; disabling")
                self._obs_textfile = None
        if self._train_obs is not None:
            self._train_obs.publish_done()

    # ------------------------------------------------------------------
    # async step pipeline (windowed host sync)
    # ------------------------------------------------------------------

    def _prefetch_put(self, batch):
        """Dispatch one host batch to device, sharded per the mesh (the
        prefetch iterator's put_fn). Transfers are async — this returns
        immediately with arrays whose copies stream in the background."""
        return jax.device_put(batch, self.zero_plan.batch_sharding(batch))

    def prefetch(self, data_iter, depth=None):
        """Wrap any batch iterator in the device-side prefetch
        (async_pipeline tentpole 1): the next ``depth`` batches'
        host→device transfers stay in flight while the current step runs.
        Yields device-resident batches the train paths consume without a
        further transfer."""
        from .dataloader import DevicePrefetchIterator
        if depth is None:
            depth = self._config.async_pipeline_config.prefetch_depth or 2
        return DevicePrefetchIterator(data_iter, self._prefetch_put, depth)

    def _push_async_step(self, loss, overflow, steps=1, sample_base=None):
        """Record one dispatch's un-fetched device scalars (``steps`` > 1 ⇔
        a K-step fused dispatch pushing vectors) and queue its monitor
        events; drain when the window fills."""
        w = self._async_window
        w.push(steps, loss, overflow)
        if self.monitor is not None and loss is not None:
            bs = self.train_batch_size()
            if steps == 1:
                self.monitor.write_events_async(
                    [("Train/Samples/train_loss", loss, self.global_samples)])
            else:
                base = (self.global_samples - (steps - 1) * bs
                        if sample_base is None else sample_base)
                self.monitor.write_events_async(
                    [("Train/Samples/train_loss", loss,
                      [base + i * bs for i in range(steps)])])
        if w.in_flight >= w.interval:
            self._drain_async_window()

    def _drain_async_window(self):
        """Fetch every in-flight step's (loss, overflow) in ONE batched
        device→host transfer and reconcile the deferred host accounting:
        skipped-step counts, lr-scheduler advances (compiled-path lr is
        exact regardless — optax reads the update count carried in
        opt_state; only host-side ``get_lr()`` reporting lags mid-window),
        bucketed-comm traffic banking, monitor flush, steps_per_print."""
        w = self._async_window
        if w is None or not w.entries:
            return
        entries, duration, comm_steps = w.take()
        with self._obs_span("host_sync_stall"), \
                self._tracer.scope("ds.train.loss_read", steps=len(entries)):
            # the ONE deliberate device→host block of the window
            fetched = host_fetch([(loss, ovf) for (_, loss, ovf) in entries])
        total_steps, n_overflow, last_loss = 0, 0, None
        for (steps, _, _), (loss_h, ovf_h) in zip(entries, fetched):
            total_steps += steps
            if self._use_loss_scaling:
                a = np.asarray(ovf_h)
                n_overflow += int(a.sum()) if a.ndim else int(bool(a))
            if loss_h is not None:
                l = np.asarray(loss_h)
                last_loss = float(l.ravel()[-1]) if l.ndim else float(l)
        self.skipped_steps += n_overflow
        for _ in range(total_steps - n_overflow):
            self._advance_schedule()
        if n_overflow:
            log_dist(f"[deepspeed] OVERFLOW! {n_overflow} step(s) skipped "
                     f"in the last sync window.", ranks=[0])
        if comm_steps and self._grad_comm_layout is not None:
            from .grad_comm import record_window_traffic
            gcc = self._config.gradient_comm_config
            tier = getattr(gcc.comm_quantization, "value",
                           gcc.comm_quantization)
            record_window_traffic(
                self._grad_comm_layout, self.dp_world_size, str(tier),
                gcc.quantization_block_size, duration, comm_steps,
                op="reduce_scatter")
            self._bank_zero3_gathers(comm_steps)
        with self._tracer.scope("ds.train.publish"):
            if self.monitor is not None:
                self.monitor.flush_events(fetch=host_fetch)
            self._publish_sown_stats()
            self._publish_registry_events(
                window_start=self.global_steps - total_steps,
                window_len=total_steps)
        if getattr(self, "_sentry", None) is not None:
            # async-mode sentry feed: the window's values were just fetched
            # in the batched transfer above — zero additional syncs
            self._sentry_observe_window(entries, fetched)
        spp = self._config.steps_per_print
        if spp and (self.global_steps // spp
                    > (self.global_steps - total_steps) // spp):
            log_dist(
                f"step={self.global_steps}, skipped={self.skipped_steps}, "
                f"lr={self.get_lr()}, loss={last_loss}", ranks=[0])

    def get_loss(self):
        """Latest training loss as a host float. Async mode: drains the
        in-flight sync window first (ONE batched fetch — this is the
        documented on-demand sync point), so mid-window calls return the
        newest step's loss, not a stale boundary value. Returns None before
        the first step."""
        self._drain_async_window()
        if self.losses is None:
            return None
        l = np.asarray(host_fetch(self.losses))
        return float(l.ravel()[-1]) if l.ndim else float(l)

    def train_batch(self, data_iter=None):
        """Pipeline-engine-style full batch step (reference pipe/engine.py:337):
        runs gradient_accumulation_steps micro-batches + the optimizer step."""
        # train_batch IS training: restore train mode so an eval loop's
        # engine.eval() doesn't strand the non-fused path (forward would
        # reroute to eval_batch and backward() would fail) — matches the
        # reference, where eval mode never blocks train_batch
        self._training = True
        if self._train_step_fused is not None:
            with self._tracer.scope("ds.train.data_wait"):
                batch = next(data_iter)
            args, kwargs = self._noised(batch)
            loss = self.fused_train_step(*args, **kwargs)
            # async mode returns the LIVE device scalar — float() here would
            # reinstate the very per-step barrier the window removes; callers
            # wanting a host number use get_loss() (drains the window)
            if self._async_window is not None:
                return loss
            with self._tracer.scope("ds.train.loss_read"):
                return float(loss)  # the host waits for the device here
        if self._train_batch_fused is not None:
            return self._run_fused_train_batch(data_iter)
        losses = []
        for _ in range(self.gradient_accumulation_steps()):
            batch = next(data_iter)
            if not isinstance(batch, tuple):
                batch = (batch, )
            loss = self.forward(*batch)
            self.backward(loss)
            self.step()
            losses.append(loss)  # device scalars; convert after the loop so
            # micro-steps pipeline instead of syncing the host every iteration
        if self._async_window is not None:
            return sum(losses) / self.gradient_accumulation_steps()
        return float(sum(float(l) for l in losses)) / self.gradient_accumulation_steps()

    def _noised(self, batch):
        """-> (args, kwargs) of the model's call for one batch of the data
        iterator. Under the block-diffusion objective a batch of raw token
        ids ([rows, L], alone or as ``(ids, ...)``) is noised here, on the
        host, with step ``global_steps``'s draw; a ``DiffusionBatch`` made by
        the caller passes as it is."""
        if self._diffusion_noiser is None:
            return (batch if isinstance(batch, tuple) else (batch, )), {}
        from .data_pipeline.block_diffusion import DiffusionBatch
        if not isinstance(batch, DiffusionBatch):
            ids = batch[0] if isinstance(batch, tuple) else batch
            with self._tracer.scope("ds.train.noise"):
                batch = self._diffusion_noiser(np.asarray(ids), self.global_steps)
        return batch.model_args()

    def _run_fused_train_batch(self, data_iter):
        """gas>1 one-program path: pull gas microbatches, stack on a leading
        axis, run the scan-fused program (one dispatch per optimizer step)."""
        gas = self.gradient_accumulation_steps()
        micros = []
        with self._tracer.scope("ds.train.data_wait"):
            for _ in range(gas):
                batch = next(data_iter)
                if not isinstance(batch, tuple):
                    batch = (batch, )
                batch, kw = self._apply_data_efficiency(batch, {})
                assert not kw, "fused gas path takes positional batch arrays only"
                micros.append(batch)
        with self._tracer.scope("ds.train.batch_put"):
            stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *micros)
            stacked = jax.device_put(
                stacked, self.zero_plan.batch_sharding(stacked, stacked=True))
        step_t0 = time.perf_counter()
        self.tput_timer.start()
        self._flops_profile_pre(self._train_batch_fused,
                                (self.params, self.opt_state, self.scale_state,
                                 stacked, ()))
        with self._tracer.scope("ds.train.dispatch"):
            (loss, self.params, self.opt_state, self.scale_state, overflow,
             gnorm) = self._train_batch_fused(self.params, self.opt_state,
                                              self.scale_state, stacked, ())
        self._last_grad_norm = gnorm
        self.losses = loss
        self.micro_steps += gas
        self.global_steps += 1
        self.global_samples += self.train_batch_size()
        self.tput_timer.stop(global_step=True)
        self._obs_step_mark(1)
        if self._async_window is not None:
            # windowed sync: the loss stays a device scalar; comm traffic is
            # banked at the drain against the whole window's wall clock
            # (per-step host timing would itself be the sync we're removing)
            if self._grad_comm_layout is not None:
                self._async_window.comm_steps += 1
            self._push_async_step(loss, overflow)
            with self._tracer.scope("ds.train.publish"):
                self._flops_profile_post()
                self._resilience_step_boundary(loss=loss, overflow=overflow)
            return loss
        if self._use_loss_scaling and bool(overflow):
            self.skipped_steps += 1
        else:
            self._advance_schedule()
        with self._tracer.scope("ds.train.publish"):
            if self.monitor is not None:
                with self._tracer.scope("ds.train.loss_read"):
                    loss_f = float(loss)
                self.monitor.write_events([("Train/Samples/train_loss", loss_f,
                                            self.global_samples)])
            self._publish_registry_events()
            self._flops_profile_post()
        with self._tracer.scope("ds.train.loss_read"):
            loss_val = float(loss)  # blocks on the dispatch
        if self._grad_comm_layout is not None:
            # per-step wire volume -> CommsLogger/calc_bw_log; the in-trace
            # collectives can't time themselves, so bank the host-measured
            # step wall against the bucketed byte count
            from ..comm.bucketing import record_bucket_traffic
            gcc = self._config.gradient_comm_config
            tier = getattr(gcc.comm_quantization, "value", gcc.comm_quantization)
            record_bucket_traffic(
                self._grad_comm_layout, self.dp_world_size,
                str(tier), gcc.quantization_block_size,
                duration=time.perf_counter() - step_t0, op="reduce_scatter")
            self._bank_zero3_gathers(1)
        with self._tracer.scope("ds.train.publish"):
            self._resilience_step_boundary(loss=loss, overflow=overflow)
        return loss_val

    def _bank_zero3_gathers(self, steps: int):
        """Registry accounting for the scheduled ZeRO-3 param gathers:
        wire bytes actually moved by the bucket all-gathers (post-
        quantization, receive side per chip) and the prefetch-epoch count —
        the schedule is static per compiled program, so ``steps`` optimizer
        steps move exactly ``steps * gas`` microbatch traversals of it."""
        sched = getattr(self, "_zero3_schedule", None)
        if sched is None or steps <= 0:
            return
        from ..observability import get_registry
        reg = get_registry()
        n = steps * self.gradient_accumulation_steps()
        reg.counter(
            "ds_zero3_gather_bytes_total",
            "Scheduled ZeRO-3 param all-gather wire bytes (post-quantization)"
        ).inc(float(sched.gather_wire_bytes) * n)
        reg.counter(
            "ds_zero3_prefetch_hits_total",
            "ZeRO-3 gather epochs issued ahead of first use (T3 overlap)"
        ).inc(float(sched.prefetch_count) * n)

    def fused_train_step(self, *args, **kwargs):
        """One-program fwd+bwd+step (gas=1 only). Same semantics as
        forward();backward();step() with one dispatch and no grad buffer."""
        assert self._train_step_fused is not None, \
            "fused_train_step requires gradient_accumulation_steps == 1"
        self.tput_timer.start()
        with self._tracer.scope("ds.train.data_wait"):
            args, kwargs = self._apply_data_efficiency(args, kwargs)
        kwargs, static_kv = _split_static_kwargs(kwargs)
        with self._tracer.scope("ds.train.batch_put"):
            args = jax.device_put(args, self.zero_plan.batch_sharding(args))
            kwargs = jax.device_put(kwargs, self.zero_plan.batch_sharding(kwargs))
        step_fn = self._train_step_fused
        if self._wire_step is not None and self.global_steps >= self._wire_freeze_step:
            # post-warmup: packed 1-bit momentum exchange replaces the fp32
            # grad reduce (the reference's freeze_step phase switch)
            step_fn = self._wire_step
        self._flops_profile_pre(step_fn, (self.params, self.opt_state,
                                          self.scale_state, args, kwargs,
                                          static_kv))
        with self._tracer.scope("ds.train.dispatch"):
            (loss, self.params, self.opt_state, self.scale_state, overflow,
             gnorm, stats) = step_fn(self.params, self.opt_state,
                                     self.scale_state, args, kwargs, static_kv)
        self._last_grad_norm = gnorm
        self.losses = loss
        self.micro_steps += 1
        self.global_steps += 1
        self.global_samples += self.train_batch_size()
        self.tput_timer.stop(global_step=True)
        self._obs_step_mark(1)
        if self._async_window is not None:
            # zero host syncs this step: loss/overflow stay device scalars
            # until the window drains (ONE batched fetch per sync_interval)
            self._push_async_step(loss, overflow)
        else:
            if self._use_loss_scaling and bool(overflow):
                self.skipped_steps += 1
            else:
                self._advance_schedule()
        with self._tracer.scope("ds.train.publish"):
            if self._async_window is None:
                if self.monitor is not None:
                    with self._tracer.scope("ds.train.loss_read"):
                        loss_f = float(loss)
                    self.monitor.write_events([("Train/Samples/train_loss", loss_f,
                                                self.global_samples)])
                self._publish_sown_stats()   # of the steps before this one
                self._publish_registry_events()
            self._flops_profile_post()
            self._resilience_step_boundary(loss=loss, overflow=overflow)
        if stats:
            self._sown_pending.append(stats)
        return loss

    def sown_stats(self, family: str):
        """What the operators of one family (a name of the model's
        ``sown_families``: docs/TRAINING.md, "What an operator sows for the
        host") sowed in the newest fused step not yet published, as host
        values under the family's own names, or the view its ``derive`` makes
        of them. A device→host fetch that waits for that step; ``None`` for a
        model that sows none of the family."""
        declared = next((f for f in self._sown_families if f.name == family), None)
        if declared is None or not self._sown_pending:
            return None
        newest = declared.of(self._sown_pending[-1])
        if not newest:
            return None
        newest = host_fetch(newest)
        return declared.derive([newest]) if declared.derived_view else newest

    # the families' names as they were before sown_stats: what
    # benchmark/runners and benchmark/layers call
    moe_stats = partialmethod(sown_stats, "moe")
    diffusion_stats = partialmethod(sown_stats, "diffusion")
    ssm_stats = partialmethod(sown_stats, "ssm")
    kda_stats = partialmethod(sown_stats, "kda")
    gdn_stats = partialmethod(sown_stats, "gdn")
    attn_stats = partialmethod(sown_stats, "attn")
    selscan_stats = partialmethod(sown_stats, "selscan")
    diffattn_stats = partialmethod(sown_stats, "diffattn")
    mla_stats = partialmethod(sown_stats, "mla")
    dsa_stats = partialmethod(sown_stats, "dsa")

    def eval_batch(self, *args, **kwargs):
        """Forward-only compiled path for evaluation.

        Plain Python int/bool/str kwargs are STATIC jit arguments (flax-style
        ``deterministic`` flags, LTD keep-counts): each distinct value compiles
        once. Pass per-step varying numbers as arrays, not Python scalars.
        """
        kwargs, static_kv = _split_static_kwargs(kwargs)
        return self._fwd_only(self.params, args, kwargs, static_kv)

    def fused_train_steps(self, *args, **kwargs):
        """K optimizer steps in ONE compiled program (one dispatch).

        Every array argument carries a leading step axis ``[K, ...]``; step
        ``i`` consumes slice ``i``. Semantics are identical to calling
        ``fused_train_step`` K times (losses returned per step); requires
        gradient_accumulation_steps == 1. The win is dispatch amortization:
        per-dispatch host latency is paid once per K steps instead of per
        step."""
        assert self._train_steps_fused is not None, \
            ("fused_train_steps requires gradient_accumulation_steps == 1, "
             "no optimizer offload (full or Twin-Flow partial), and a "
             "device apply program")
        if self._wire_step is not None:
            # the 1-bit wire program swaps in per-step after freeze_step;
            # a K-step scan would silently run uncompressed past the switch
            raise RuntimeError(
                "fused_train_steps does not compose with the 1-bit wire "
                "program (onebit* + comm_backend_name) — use fused_train_step")
        if (self.curriculum_scheduler_legacy is not None
                or self.random_ltd_scheduler is not None):
            # data-efficiency hooks transform each batch per step (seqlen
            # truncation changes shapes) — incompatible with one stacked
            # uniform-shape dispatch
            raise RuntimeError(
                "fused_train_steps does not compose with curriculum/"
                "random-LTD batch routing — use fused_train_step")
        kwargs, static_kv = _split_static_kwargs(kwargs)
        K = jax.tree_util.tree_leaves(args + tuple(kwargs.values()))[0].shape[0]
        with self._tracer.scope("ds.train.batch_put"):
            args = jax.device_put(args, self.zero_plan.batch_sharding(args, stacked=True))
            kwargs = jax.device_put(kwargs,
                                    self.zero_plan.batch_sharding(kwargs, stacked=True))
        self.tput_timer.start()
        self._flops_profile_pre(self._train_steps_fused,
                                (self.params, self.opt_state, self.scale_state,
                                 args, kwargs, static_kv), steps=K)
        with self._tracer.scope("ds.train.dispatch", steps=int(K)):
            (losses, self.params, self.opt_state, self.scale_state, overflows,
             gnorms, stats) = self._train_steps_fused(
                 self.params, self.opt_state, self.scale_state, args, kwargs,
                 static_kv)
        self._last_grad_norm = gnorms[-1]
        self.losses = losses[-1]
        self.micro_steps += K
        self.global_steps += K
        self.global_samples += K * self.train_batch_size()
        # one dispatch = K real optimizer steps: the throughput timer and
        # the monitor both see K events, not one
        self.tput_timer.stop(global_step=True, steps=K)
        self._obs_step_mark(K)
        if self._async_window is not None:
            # push the whole K-step dispatch as ONE vector entry: the loss
            # vector and per-step overflow mask drain together at the window
            self._push_async_step(losses, overflows, steps=K)
        else:
            n_overflow = int(jnp.sum(overflows)) if self._use_loss_scaling else 0
            self.skipped_steps += n_overflow
            for _ in range(K - n_overflow):
                self._advance_schedule()
        with self._tracer.scope("ds.train.publish"):
            if self._async_window is None:
                if self.monitor is not None:
                    base = self.global_samples - (K - 1) * self.train_batch_size()
                    self.monitor.write_events(
                        [("Train/Samples/train_loss", float(l),
                          base + i * self.train_batch_size())
                         for i, l in enumerate(np.asarray(losses))])
                self._publish_sown_stats()   # of the dispatches before this one
                self._publish_registry_events(
                    window_start=self.global_steps - K, window_len=K)
            self._flops_profile_post()
            self._resilience_step_boundary(losses_vec=losses, overflows_vec=overflows)
        if stats:
            self._sown_pending.append(stats)
        return losses

    def module_forward(self, *args, **kwargs):
        kwargs, static_kv = _split_static_kwargs(kwargs)
        return self._fwd_only(self.params, args, kwargs, static_kv)

    # ------------------------------------------------------------------
    # info API (reference engine.py assorted getters)
    # ------------------------------------------------------------------

    def train_batch_size(self):
        return self._config.train_batch_size

    def train_micro_batch_size_per_gpu(self):
        return self._config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self):
        return self._config.gradient_accumulation_steps

    def set_train_batch_size(self, train_batch_size):
        """Adjust the GLOBAL batch by changing gradient-accumulation steps;
        the micro batch is untouched (reference engine.py:455). The compiled
        programs closed over the old gas (loss /gas scaling and the
        gas==1-vs-scan fused-path choice are baked in at build time), so they
        are rebuilt here — shape retracing alone would keep stale closures."""
        denom = self.train_micro_batch_size_per_gpu() * self.dp_world_size
        if train_batch_size <= 0 or train_batch_size % denom != 0:
            raise ValueError(
                f"train_batch_size={train_batch_size} must be a positive "
                f"multiple of micro_batch*dp={denom}")
        new_gas = train_batch_size // denom
        gas_changed = new_gas != self.gradient_accumulation_steps()
        self._config.train_batch_size = train_batch_size
        self._config.gradient_accumulation_steps = new_gas
        if gas_changed:  # gas is the only value baked into the closures
            self._build_compiled_fns()
            self._watch_compiled_fns()

    def set_train_micro_batch_size(self, micro_batch_size):
        """Adjust the micro batch, keeping gradient-accumulation steps
        (reference engine.py:473); the global batch follows."""
        if micro_batch_size <= 0:
            raise ValueError(f"micro_batch_size must be positive, got "
                             f"{micro_batch_size}")
        gas = self.gradient_accumulation_steps()
        self._config.train_micro_batch_size_per_gpu = micro_batch_size
        self._config.train_batch_size = micro_batch_size * gas * self.dp_world_size

    def get_lr(self):
        sched = self.lr_scheduler
        if sched is not None and hasattr(sched, "get_last_lr"):
            if getattr(sched, "_last_lr", None) is not None:
                # stepped (ours and torch-style both set _last_lr): any
                # exception from here is a real bug — let it surface
                return sched.get_last_lr()
            try:  # pre-step only: reference-style schedulers assert here
                return sched.get_last_lr()
            except AssertionError:
                return [self._base_lr]
        return [self._base_lr]

    def set_lr(self, lr):
        """Reference ``engine.py set_lr``: override the base learning rate.
        With a scheduler attached, the scheduler keeps driving subsequent
        steps — override its base instead (lr_schedules expose params)."""
        self._base_lr = float(lr)
        if self.lr_scheduler is not None and hasattr(self.lr_scheduler, "set_base_lr"):
            self.lr_scheduler.set_base_lr(float(lr))

    def get_mom(self):
        """Reference ``engine.py get_mom``: current momentum/betas."""
        op = dict(self._config.optimizer_params or {})
        return [tuple(op.get("betas", (0.9, 0.999)))]

    def empty_partition_cache(self):
        """Reference ZeRO-3 ``empty_partition_cache``: drop gathered full
        params. Under pjit there is no host-visible gather cache — XLA frees
        gathered buffers when the step program ends — so this is a documented
        no-op kept for API portability."""
        return None

    def destroy(self):
        """Reference ``engine.destroy``: release engine state references so
        device memory can be reclaimed between engines in one process."""
        self._drain_async_window()  # settle deferred host accounting first
        self._remove_preempt_handlers()
        for attr in ("params", "opt_state", "scale_state", "_pending"):
            setattr(self, attr, None)
        self._fwd_bwd = self._fwd_only = self._apply_step = None
        self._train_step_fused = self._train_batch_fused = None
        self._train_steps_fused = None

    def get_global_grad_norm(self):
        return None if self._last_grad_norm is None else float(self._last_grad_norm)

    @property
    def cur_scale(self):
        return float(self.scale_state.cur_scale)

    def loss_scale(self):
        return self.cur_scale

    def zero_optimization_stage(self):
        return self._config.zero_optimization_stage

    def get_sequence_parallel_group(self):
        return "seq"

    def random_ltd_enabled(self):
        return self.random_ltd_scheduler is not None

    def curriculum_enabled_legacy(self):
        return self.curriculum_scheduler_legacy is not None

    def curriculum_params_legacy(self):
        return self._config.curriculum_params_legacy

    # ------------------------------------------------------------------
    # checkpoint (reference engine.py:3109 save / :2763 load)
    # ------------------------------------------------------------------

    def _state_dict(self):
        # Under the scheduled ZeRO-3 store, "params"/"grad_acc"/"opt_state"
        # are the store pytrees: orbax writes each sharded bucket from its
        # owning chips — a per-shard save with NO full gather (the reference
        # stage-3 default; consolidation stays the explicit
        # stage3_gather_16bit_weights_on_model_save / save_16bit_model path).
        # "grad_acc" only where the buffer exists (a run on the unfused or an
        # offload path; in mid-accumulation it holds the sums to resume on)
        sd = {
            "params": self.params,
            "scale_state": tuple(self.scale_state),
        }
        if self.grad_acc is not None:
            sd["grad_acc"] = self.grad_acc
        if self.opt_state is not None:
            sd["opt_state"] = self.opt_state
        return sd

    def full_params(self):
        """Full leaf-tree fp32 master params. Under the scheduled ZeRO-3
        store this is the one deliberate whole-model gather (store buckets
        sliced back into leaves; GSPMD gathers each bucket) — used by the
        explicit consolidation paths, and accounted to the
        ``param_gather_stall`` goodput category."""
        if getattr(self, "_zero3_store", None) is None:
            return self.params
        from .zero3_schedule import materialize_params
        meta = self._zero3_store
        with self._obs_span("param_gather_stall"):
            return jax.jit(lambda s: materialize_params(s, meta))(self.params)

    def _checkpoint_tag_validation(self, tag) -> None:
        """All processes must agree on the tag before anyone writes
        (reference engine.py:3092 _checkpoint_tag_validation): a diverged
        tag fragments one logical checkpoint across directories."""
        from ..config.feature_configs import ValidationMode
        mode = self._config.checkpoint_config.tag_validation
        if jax.process_count() == 1 or mode == ValidationMode.IGNORE:
            return
        import zlib
        from jax.experimental import multihost_utils
        h = np.asarray([zlib.crc32(str(tag).encode())], np.int64)
        all_h = np.asarray(multihost_utils.process_allgather(h)).ravel()
        if not (all_h == all_h[0]).all():
            msg = (f"checkpoint tag '{tag}' is not consistent across "
                   "processes — a mixed-tag save fragments the checkpoint")
            if mode == ValidationMode.FAIL:
                raise ValueError(msg)
            logger.warning(msg)

    def _host_state(self, client_state):
        sd = {
            "global_steps": self.global_steps,
            "global_samples": self.global_samples,
            "micro_steps": self.micro_steps,
            "skipped_steps": self.skipped_steps,
            "ds_config_batch": [self.train_batch_size(),
                                self.train_micro_batch_size_per_gpu(),
                                self.gradient_accumulation_steps()],
            "client_state": client_state or {},
            # whether the arrays hold an accumulation buffer (a checkpoint
            # from before the key always does)
            "grad_acc": self.grad_acc is not None,
        }
        if self.lr_scheduler is not None and hasattr(self.lr_scheduler, "state_dict"):
            sd["lr_scheduler"] = self.lr_scheduler.state_dict()
        if self._host_optimizer is not None:
            sd["host_optimizer"] = self._host_optimizer.state_dict()
        # data-efficiency schedulers (reference engine.py:3300 saves
        # random_ltd + sampler/curriculum state in the checkpoint)
        if self.random_ltd_scheduler is not None:
            sd["random_ltd"] = self.random_ltd_scheduler.state_dict()
        if self.curriculum_scheduler_legacy is not None:
            sd["curriculum_state"] = dict(self.curriculum_scheduler_legacy.get_state())
        sampler = getattr(self.training_dataloader, "sampler", None) \
            if self.training_dataloader is not None else None
        if sampler is not None and hasattr(sampler, "state_dict"):
            sd["data_sampler"] = sampler.state_dict()
        if getattr(self, "_zero3_store", None) is not None:
            # enough to rebuild the exact bucket layout at load time (the
            # planner is deterministic given these + the leaf structs), so a
            # stage-2 engine can reshard a stage-3 checkpoint and vice versa
            m = self._zero3_store
            sd["zero3_store"] = {
                "bucket_size_mb": float(m.bucket_size_mb),
                "pad_multiple": int(m.pad_multiple),
                "persistent_idx": [int(i) for i in m.p_idx],
                "n_leaves": int(m.n_leaves),
            }
        return sd

    @property
    def checkpoint_engine(self):
        """The engine checkpoints are written and read through: an
        ``OrbaxCheckpointEngine`` built when first asked for, or whatever
        the caller assigned (an ``AsyncCheckpointEngine``, say)."""
        if self._checkpoint_engine is None:
            self._build_checkpoint_engine()
        return self._checkpoint_engine

    @checkpoint_engine.setter
    def checkpoint_engine(self, engine):
        self._checkpoint_engine = engine

    def _build_checkpoint_engine(self):
        # the first one of a process imports orbax (seconds): the span says
        # where they fell, and a run without it never paid them
        with self._tracer.scope("ds.checkpoint.engine_build"):
            self._checkpoint_engine = OrbaxCheckpointEngine()

    def save_checkpoint(self, save_dir, tag=None, client_state=None, save_latest=True,
                        exclude_frozen_parameters=False):
        # settle the async window first: deferred skipped-step / scheduler
        # accounting must land in the host state the checkpoint captures
        self._drain_async_window()
        with self._obs_span("checkpoint_save"):
            return self._save_checkpoint(save_dir, tag=tag,
                                         client_state=client_state,
                                         save_latest=save_latest)

    def _save_checkpoint(self, save_dir, tag=None, client_state=None,
                         save_latest=True):
        tag = tag or f"global_step{self.global_steps}"
        self._checkpoint_tag_validation(tag)
        self.checkpoint_engine.create(tag)
        path = os.path.join(save_dir, str(tag))
        self.checkpoint_engine.save(self._state_dict(), path,
                                    host_state=self._host_state(client_state))
        if self._config.zero_config.gather_16bit_weights_on_model_save:
            # reference stage3_gather_16bit_weights_on_model_save
            # (engine.py:3538): every checkpoint also carries consolidated
            # 16-bit weights a serving stack can load without the topology
            self.save_16bit_model(path)
        # commit BEFORE advancing `latest`: commit is the durability barrier
        # (async write settled, host state flushed, manifest + marker
        # sealed) — the old order left `latest` pointing at an uncommitted,
        # possibly torn checkpoint if the process died in between
        committed = self.checkpoint_engine.commit(tag) is not False
        if not committed:
            logger.error(f"checkpoint {tag} failed to commit; `latest` still "
                         f"points at the previous checkpoint")
            return False
        self._last_good_tag = str(tag)
        if jax.process_index() == 0:
            if save_latest:
                write_latest_tag(save_dir, tag)
            rc = getattr(self, "_resilience", None)
            if rc is not None and rc.enabled and rc.keep_last_n:
                prune_checkpoints(save_dir, rc.keep_last_n,
                                  protect=(str(tag), ))
        return True

    def save_16bit_model(self, save_dir, save_filename="pytorch_model.npz",
                         exclude_frozen_parameters=False):
        """Consolidated 16-bit weight export (reference engine.py:3538
        _zero3_consolidated_16bit_state_dict + save_16bit_model): gathers
        the full (unsharded) bf16 weights and writes one flat archive a
        serving stack can load without the training topology."""
        from ..checkpoint.universal import _flatten
        os.makedirs(save_dir, exist_ok=True)
        # npz can't hold ml_dtypes.bfloat16 — store the bf16 bit pattern as
        # uint16 with a dtype sidecar key (fp16 stores natively)
        bf16 = self.compute_dtype == jnp.bfloat16
        sd = {}
        for k, v in _flatten(jax.tree_util.tree_map(np.asarray,
                                                    self.full_params())).items():
            if bf16:
                import ml_dtypes
                sd[k] = np.asarray(v).astype(ml_dtypes.bfloat16).view(np.uint16)
            else:
                sd[k] = np.asarray(v).astype(np.float16)
        sd["__dtype__"] = np.asarray("bfloat16" if bf16 else "float16")
        path = os.path.join(save_dir, save_filename)
        np.savez(path, **sd)
        log_dist(f"saved 16-bit model to {path} ({len(sd)} tensors)", ranks=[0])
        return True

    def load_universal_checkpoint(self, universal_dir):
        """Resume from a universal checkpoint at ANY parallelism (reference
        bf16_optimizer.py:519 load_hp_checkpoint_state / universal_checkpoint
        config flag): fp32 fragments are re-laid-out onto the live mesh's
        shardings regardless of what topology wrote them."""
        from ..checkpoint.universal import load_universal_into
        if getattr(self, "_zero3_store", None) is not None:
            raise NotImplementedError(
                "universal-checkpoint load into the scheduled ZeRO-3 param "
                "store is not supported yet — regular checkpoints reshard "
                "automatically on load_checkpoint (stage 2<->3); to consume "
                "a universal checkpoint, load it at zero stage <= 2 and "
                "save a regular checkpoint")
        params_host = jax.tree_util.tree_map(lambda x: np.zeros(x.shape, jnp.float32),
                                             jax.eval_shape(lambda p: p, self.params))
        params, opt_state, meta = load_universal_into(universal_dir, params_host,
                                                      self.opt_state)
        self.params = jax.device_put(
            jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), params),
            self.param_shardings)
        if opt_state is not None:
            self.opt_state = jax.device_put(opt_state, self.opt_state_shardings)
        self.global_steps = meta.get("step", 0)
        log_dist(f"loaded universal checkpoint {universal_dir} at step {self.global_steps}",
                 ranks=[0])
        return universal_dir, {}

    def load_checkpoint(self, load_dir, tag=None, load_module_strict=True,
                        load_optimizer_states=True, load_lr_scheduler_states=True,
                        load_module_only=False, custom_load_fn=None):
        # goodput: a load inside a rollback nests under "anomaly_rollback"
        with self._obs_span("checkpoint_load"):
            return self._load_checkpoint(
                load_dir, tag=tag,
                load_lr_scheduler_states=load_lr_scheduler_states,
                load_optimizer_states=load_optimizer_states,
                load_module_only=load_module_only)

    def _load_checkpoint(self, load_dir, tag=None, load_optimizer_states=True,
                         load_lr_scheduler_states=True,
                         load_module_only=False):
        if tag is None:
            # `latest` is authoritative while it names a sealed, verified
            # checkpoint. After a crash it may be missing, stale, or name a
            # torn/corrupt dir — then fall back through older tags until one
            # passes manifest verification (provably-bad dirs quarantined).
            lt = read_latest_tag(load_dir)
            if lt is not None and verify_checkpoint(
                    os.path.join(load_dir, str(lt)), require_manifest=True)[0]:
                tag = lt
            if tag is None:
                tag = find_latest_valid_checkpoint(load_dir)
            if tag is None and lt is not None and verify_checkpoint(
                    os.path.join(load_dir, str(lt)), require_manifest=False)[0]:
                # pre-manifest (legacy) checkpoint: the pointer is the only
                # trust anchor available — honor it
                tag = lt
            if tag is None:
                logger.warning(f"Unable to find a valid checkpoint in "
                               f"{load_dir}, if trying to load a specific "
                               "checkpoint please pass tag")
                return None, {}
        path = os.path.join(load_dir, str(tag))

        peeked = self._peek_host_state(path)
        saved_store = peeked.get("zero3_store")
        # the target names what the checkpoint holds: a buffer it lacks stays
        # unmade, one it holds is restored whether or not this engine has one
        has_acc = bool(peeked.get("grad_acc", True))
        if (saved_store is not None) != (getattr(self, "_zero3_store", None)
                                         is not None):
            # the checkpoint's arrays are in the OTHER param format
            # (bucketed ZeRO-3 store vs leaf tree): reshard on load
            restored, host_state = self._reshard_load(path, saved_store, has_acc)
        else:
            # abstract target: restore straight into the live shardings
            target = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
                if hasattr(x, "sharding") else x, self._state_dict())
            target.pop("grad_acc", None)
            if has_acc:
                target["grad_acc"] = self._grad_acc_struct()
            restored, host_state = self.checkpoint_engine.load(path, target=target)
        self.params = restored["params"]
        if load_optimizer_states and not load_module_only:
            if "opt_state" in restored:
                self.opt_state = restored["opt_state"]
            self._set_grad_acc(restored.get("grad_acc"))
            from .loss_scaler import LossScaleState
            self.scale_state = LossScaleState(*restored["scale_state"])
            if self._host_optimizer is not None and host_state \
                    and "host_optimizer" in host_state:
                self._host_optimizer.load_state_dict(host_state["host_optimizer"])
        client_state = {}
        if host_state:
            self.global_steps = host_state.get("global_steps", 0)
            self.global_samples = host_state.get("global_samples", 0)
            self.micro_steps = host_state.get("micro_steps", 0)
            self.skipped_steps = host_state.get("skipped_steps", 0)
            client_state = host_state.get("client_state", {})
            if (load_lr_scheduler_states and self.lr_scheduler is not None
                    and "lr_scheduler" in host_state):
                self.lr_scheduler.load_state_dict(host_state["lr_scheduler"])
            if self.random_ltd_scheduler is not None and "random_ltd" in host_state:
                self.random_ltd_scheduler.load_state_dict(host_state["random_ltd"])
            if (self.curriculum_scheduler_legacy is not None
                    and "curriculum_state" in host_state):
                self.curriculum_scheduler_legacy.set_state(host_state["curriculum_state"])
            sampler = getattr(self.training_dataloader, "sampler", None) \
                if self.training_dataloader is not None else None
            if sampler is not None and "data_sampler" in host_state:
                # resume consumed_samples + curriculum difficulty: training
                # continues on the right difficulty band, no replayed data
                sampler.load_state_dict(host_state["data_sampler"])
        self._last_good_tag = str(tag)
        return path, client_state

    def _peek_host_state(self, path) -> dict:
        """The checkpoint's host-state sidecar (tiny pickle, no array data),
        read before the arrays to learn their form: ``zero3_store`` (the
        saved store's descriptor, where they are in ZeRO-3 store form) and
        ``grad_acc`` (whether they hold an accumulation buffer); ``{}`` when
        there is none to read."""
        import pickle
        from ..checkpoint.engine import OrbaxCheckpointEngine
        f = os.path.join(path, OrbaxCheckpointEngine.HOST_STATE_FILE)
        if not os.path.exists(f):
            return {}
        try:
            with open(f, "rb") as fh:
                return pickle.load(fh) or {}
        except Exception as e:  # legacy/foreign sidecar: same-format load
            logger.warning(f"could not peek host state at {f}: {e}")
            return {}

    def _reshard_load(self, path, saved_store, has_acc=True):
        """Stage 2<->3 reshard-on-load: restore into an abstract target
        shaped like the SAVE-time format, then convert on device into the
        live format. Both directions are exact (pure slice/concat of fp32
        masters and moments), so a 2->3->2 round trip is bitwise."""
        from .zero3_schedule import (build_store_meta, map_store_subtrees,
                                     materialize_params, store_from_tree)
        repl = self.mesh_ctx.replicated()

        def _repl_struct(t):
            return jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                               sharding=repl), t)

        acc_dtype = self.grad_accum_dtype
        scale_target = _repl_struct(tuple(self.scale_state))
        if saved_store is not None:
            # checkpoint holds the bucketed store; live engine wants a tree
            fp32_tree = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, jnp.float32),
                self.params)
            meta = build_store_meta(fp32_tree, saved_store["persistent_idx"],
                                    saved_store["bucket_size_mb"],
                                    saved_store["pad_multiple"])
            if meta.n_leaves != int(saved_store.get("n_leaves",
                                                    meta.n_leaves)):
                raise ValueError(
                    f"checkpoint ZeRO-3 store covers "
                    f"{saved_store['n_leaves']} param leaves but the live "
                    f"model has {meta.n_leaves}")

            def _store_struct(dtype):
                return {"buckets": [jax.ShapeDtypeStruct((b.padded_size, ),
                                                         dtype, sharding=repl)
                                    for b in meta.layout.buckets],
                        "persistent": [jax.ShapeDtypeStruct(
                            meta.leaf_structs[i].shape, dtype, sharding=repl)
                            for i in meta.p_idx]}

            target = {"params": _store_struct(jnp.float32),
                      "scale_state": scale_target}
            if has_acc:
                target["grad_acc"] = _store_struct(acc_dtype)
            if self.opt_state is not None:
                target["opt_state"] = _repl_struct(jax.eval_shape(
                    self.base_tx.init, _store_struct(jnp.float32)))
            restored, host_state = self.checkpoint_engine.load(path,
                                                               target=target)
            out = {"params": jax.jit(
                       lambda s: materialize_params(s, meta),
                       out_shardings=self.param_shardings)(restored["params"]),
                   "scale_state": restored["scale_state"]}
            if has_acc:
                out["grad_acc"] = jax.jit(
                    lambda s: materialize_params(s, meta),
                    out_shardings=self.grad_shardings)(restored["grad_acc"])
            if "opt_state" in restored:
                store_def = jax.tree_util.tree_structure(
                    _store_struct(jnp.float32))
                out["opt_state"] = jax.jit(
                    lambda o: map_store_subtrees(
                        o, store_def, lambda s: materialize_params(s, meta)),
                    out_shardings=self.opt_state_shardings)(
                        restored["opt_state"])
            log_dist(f"resharded ZeRO-3 store checkpoint {path} into the "
                     f"live leaf-tree layout (stage 3 -> "
                     f"{self.zero_plan.stage})", ranks=[0])
            return out, host_state
        # checkpoint holds a leaf tree; live engine runs the ZeRO-3 store
        meta = self._zero3_store
        leaves_f32 = [jax.ShapeDtypeStruct(s.shape, jnp.float32,
                                           sharding=repl)
                      for s in meta.leaf_structs]
        fp32_tree = jax.tree_util.tree_unflatten(meta.treedef, leaves_f32)
        acc_tree = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, acc_dtype, sharding=repl),
            fp32_tree)
        target = {"params": fp32_tree, "scale_state": scale_target}
        if has_acc:
            target["grad_acc"] = acc_tree
        if self.opt_state is not None:
            target["opt_state"] = _repl_struct(jax.eval_shape(
                self.base_tx.init, fp32_tree))
        restored, host_state = self.checkpoint_engine.load(path,
                                                           target=target)
        out = {"params": jax.jit(
                   lambda t: store_from_tree(t, meta),
                   out_shardings=self.param_shardings)(restored["params"]),
               "scale_state": restored["scale_state"]}
        if has_acc:
            out["grad_acc"] = jax.jit(
                lambda t: store_from_tree(t, meta),
                out_shardings=self.grad_shardings)(restored["grad_acc"])
        if "opt_state" in restored:
            out["opt_state"] = jax.jit(
                lambda o: map_store_subtrees(
                    o, meta.treedef, lambda t: store_from_tree(t, meta)),
                out_shardings=self.opt_state_shardings)(restored["opt_state"])
        log_dist(f"resharded leaf-tree checkpoint {path} into the live "
                 f"ZeRO-3 bucket store", ranks=[0])
        return out, host_state
