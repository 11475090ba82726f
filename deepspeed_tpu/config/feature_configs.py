"""Per-feature config models.

Mirrors the reference JSON surface: ``runtime/zero/config.py:83``
(DeepSpeedZeroConfig), ``runtime/fp16`` keys, ``runtime/activation_checkpointing/config.py``,
``utils/comms_logging`` keys, ``profiling/config.py``, ``monitor/config.py``,
``runtime/swap_tensor/aio_config.py`` — with identical key names so reference
JSON configs parse unchanged. TPU-only extensions are marked.
"""

from enum import Enum
from typing import Any, Dict, List, Optional
from pathlib import Path

from pydantic import Field, model_validator

from .config_utils import ConfigModel

# -------------------- ZeRO --------------------


class OffloadDeviceEnum(str, Enum):
    none = "none"
    cpu = "cpu"
    nvme = "nvme"


class DeepSpeedZeroOffloadParamConfig(ConfigModel):
    """Param offload (reference ``runtime/zero/offload_config.py``)."""
    device: OffloadDeviceEnum = "none"
    nvme_path: Optional[Path] = None
    buffer_count: int = Field(5, ge=0)
    buffer_size: int = Field(int(1e8), ge=0)
    max_in_cpu: int = Field(int(1e9), ge=0)
    pin_memory: bool = False


class DeepSpeedZeroOffloadOptimizerConfig(ConfigModel):
    device: OffloadDeviceEnum = "none"
    nvme_path: Optional[Path] = None
    buffer_count: int = Field(4, ge=0)
    pin_memory: bool = False
    pipeline_read: bool = False
    pipeline_write: bool = False
    fast_init: bool = False
    ratio: float = Field(1.0, ge=0.0, le=1.0)

    @property
    def pipeline(self):
        return self.pipeline_read or self.pipeline_write


class ZeroConfig(ConfigModel):
    """ZeRO sharding config (reference ``runtime/zero/config.py:83``).

    On TPU the stages map to sharding rules over the ``fsdp``/``data`` mesh
    axes rather than hook-driven partitioning:
      stage 0 = pure DP; stage 1 = optimizer-state sharding;
      stage 2 = + gradient (accumulation buffer) sharding;
      stage 3 = + parameter sharding (XLA inserts gather/scatter).

    Knob disposition (the audit of every accepted key):
    - WIRED: stage, offload_param/offload_optimizer (device/ratio),
      max_live_parameters (scan-chunk governor), param_persistence_threshold,
      zero_hpz_partition_size, zero_quantized_weights/gradients (qwZ/qgZ),
      mics_shard_size, gather_16bit_weights_on_model_save (consolidated
      16-bit export with every checkpoint).
    - MOOT by construction (accepted for config-file compatibility, the
      guarantee they buy is unconditional here): elastic_checkpoint (orbax
      restores across any topology), load_from_fp32_weights (master weights
      are always fp32), ignore_unused_parameters (no hook machinery to
      trip), contiguous_gradients (XLA owns layout).
    - TORCH-MECHANISM knobs with no XLA seam (accepted, inert, the
      scheduler/compiler owns the behavior they tuned): bucket sizes,
      overlap_comm, round_robin_gradients, sub_group_size, prefetch/
      reuse-distance/module-granularity thresholds, legacy_stage1,
      use_all_reduce_for_fetch_params, use_multi_rank_bucket_allreduce,
      memory_efficient_linear, pipeline_loading_checkpoint,
      override_module_apply, cpu_offload* legacy spellings (the offload_*
      sub-configs are the wired path).
    """
    stage: int = Field(0, ge=0, le=3)
    contiguous_gradients: bool = True
    reduce_scatter: bool = True
    reduce_bucket_size: int = Field(int(5e8), ge=0)
    use_multi_rank_bucket_allreduce: bool = True
    allgather_partitions: bool = True
    allgather_bucket_size: int = Field(int(5e8), ge=0)
    overlap_comm: Optional[bool] = None
    load_from_fp32_weights: bool = True
    elastic_checkpoint: bool = False
    offload_param: Optional[DeepSpeedZeroOffloadParamConfig] = None
    offload_optimizer: Optional[DeepSpeedZeroOffloadOptimizerConfig] = None
    sub_group_size: int = Field(int(1e9), ge=0)
    cpu_offload_param: Optional[bool] = None
    cpu_offload_use_pin_memory: Optional[bool] = None
    cpu_offload: Optional[bool] = None
    prefetch_bucket_size: int = Field(int(5e7), ge=0, alias="stage3_prefetch_bucket_size")
    param_persistence_threshold: int = Field(int(1e5), ge=0, alias="stage3_param_persistence_threshold")
    model_persistence_threshold: int = Field(int(1e9), ge=0, alias="stage3_model_persistence_threshold")
    max_live_parameters: int = Field(int(1e9), ge=0, alias="stage3_max_live_parameters")
    max_reuse_distance: int = Field(int(1e9), ge=0, alias="stage3_max_reuse_distance")
    gather_16bit_weights_on_model_save: bool = Field(False, alias="stage3_gather_16bit_weights_on_model_save")
    module_granularity_threshold: int = Field(0, alias="stage3_module_granularity_threshold")
    use_all_reduce_for_fetch_params: bool = Field(False, alias="stage3_use_all_reduce_for_fetch_params")
    ignore_unused_parameters: bool = True
    legacy_stage1: bool = False
    round_robin_gradients: bool = False
    zero_hpz_partition_size: int = Field(1, ge=0)
    zero_quantized_weights: bool = False
    zero_quantized_nontrainable_weights: bool = False
    zero_quantized_gradients: bool = False
    mics_shard_size: int = Field(-1, alias="mics_shard_size")
    mics_hierarchical_params_gather: bool = False
    memory_efficient_linear: bool = True
    pipeline_loading_checkpoint: bool = False
    override_module_apply: bool = True

    @model_validator(mode="after")
    def offload_ratio_check(self):
        offload_config = self.offload_optimizer
        if offload_config and offload_config.ratio < 1.0:
            assert self.stage == 3, "Partial offload only supported for ZeRO Stage 3."
        return self

    @property
    def offload_optimizer_device(self):
        return self.offload_optimizer.device if self.offload_optimizer else "none"

    @property
    def offload_param_device(self):
        return self.offload_param.device if self.offload_param else "none"


# -------------------- precision --------------------


class FP16Config(ConfigModel):
    enabled: Any = False
    auto_cast: bool = False
    loss_scale: float = Field(0.0, ge=0.0)
    initial_scale_power: int = Field(16, ge=0)
    loss_scale_window: int = Field(1000, ge=0)
    hysteresis: int = Field(2, ge=0)
    consecutive_hysteresis: bool = False
    min_loss_scale: float = Field(1.0, ge=0.0)
    fp16_master_weights_and_grads: bool = False


class BF16Config(ConfigModel):
    enabled: Any = False
    immediate_grad_update: bool = True


class DataTypesConfig(ConfigModel):
    grad_accum_dtype: Optional[str] = None


# -------------------- activation checkpointing --------------------


class ActivationCheckpointingConfig(ConfigModel):
    """Reference ``runtime/activation_checkpointing/config.py``.

    On TPU: ``partition_activations`` maps to sharding the saved residuals
    over the ``model`` axis; cpu_checkpointing maps to host offload of remat
    inputs; contiguous/synchronize flags are accepted for parity (XLA owns
    buffer placement).
    """
    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    # TPU extension: jax.checkpoint policy name; the engine wraps the model
    # in jax.checkpoint only when one is named. For
    # activation_checkpointing.checkpoint() None recomputes everything but
    # what an attention kernel gave (ops/attention.py::RESIDUAL_NAMES) and as
    # many of the function's named values (ops/remat.py, in its order) as the
    # chip reports room for; "nothing_saveable" keeps nothing
    remat_policy: Optional[str] = None


# -------------------- gradient comm planner (extension) --------------------


class CommQuantizationEnum(str, Enum):
    fp32 = "fp32"
    int8 = "int8"
    onebit = "onebit"


class GradientCommConfig(ConfigModel):
    """Bucketed + quantized gradient collectives (TPU extension; the analog
    of the reference's ``reduce_bucket_size``/``overlap_comm`` knobs, which
    are torch-mechanism-inert here — see ZeroConfig docstring — plus an
    EQuARX-style int8 wire tier between fp32 and the 1-bit sign path).

    - ``enabled``: build the bucketed gradient-comm program when supported
      (implied by overlap_comm or a non-fp32 quantization tier).
    - ``bucket_size_mb``: flat-bucket budget; gradients flow as
      ``ceil(total_bytes / bucket_size)`` collectives per dtype instead of
      one per pytree leaf.
    - ``comm_quantization``: wire tier for the gradient reduce —
      fp32 (exact), int8 (blockwise scale+zero-point, ~4x wire cut),
      onebit (sign+scale, ~32x).
    - ``quantization_block_size``: elements per int8 quantization block.
    - ``error_feedback``: carry the quantization residual into the next
      microbatch's gradients (quantized tiers only).
    - ``overlap_comm``: reduce bucket i inside the microbatch scan while
      microbatch i+1's backward runs (T3-style), carrying partially-reduced
      bucket shards through the scan instead of reducing the whole
      accumulated tree at the boundary.
    - ``comm_quantization_per_dtype``: per-dtype tier override, e.g.
      ``{"bfloat16": "int8"}`` — selects the tier per-bucket (buckets are
      dtype-homogeneous).
    """
    enabled: bool = False
    bucket_size_mb: float = Field(25.0, gt=0)
    comm_quantization: CommQuantizationEnum = CommQuantizationEnum.fp32
    quantization_block_size: int = Field(256, gt=0)
    error_feedback: bool = True
    overlap_comm: bool = False
    comm_quantization_per_dtype: Dict[str, CommQuantizationEnum] = {}

    @property
    def active(self) -> bool:
        return (self.enabled or self.overlap_comm
                or self.comm_quantization != CommQuantizationEnum.fp32
                or bool(self.comm_quantization_per_dtype))

    def tier_for_dtype(self, dtype) -> str:
        import numpy as _np
        key = str(_np.dtype(dtype))
        tier = self.comm_quantization_per_dtype.get(key, self.comm_quantization)
        return tier.value if isinstance(tier, CommQuantizationEnum) else str(tier)


# -------------------- comms logging --------------------


class CommsLoggerConfig(ConfigModel):
    enabled: bool = False
    prof_all: bool = True
    prof_ops: List[str] = []
    verbose: bool = False
    debug: bool = False


# -------------------- flops profiler --------------------


class FlopsProfilerConfig(ConfigModel):
    enabled: bool = False
    recompute_fwd_factor: float = 0.0
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


# -------------------- monitors --------------------


class TensorBoardConfig(ConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


class WandbConfig(ConfigModel):
    enabled: bool = False
    group: Optional[str] = None
    team: Optional[str] = None
    project: str = "deepspeed"


class CometConfig(ConfigModel):
    enabled: bool = False
    samples_log_interval: int = 100
    project: Optional[str] = None
    workspace: Optional[str] = None
    api_key: Optional[str] = None
    experiment_name: Optional[str] = None
    experiment_key: Optional[str] = None
    online: Optional[bool] = None
    mode: Optional[str] = None


class CSVConfig(ConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


class MonitorConfig(ConfigModel):
    tensorboard: TensorBoardConfig = {}
    comet: CometConfig = {}
    wandb: WandbConfig = {}
    csv_monitor: CSVConfig = {}
    registry_events: bool = False
    """Also publish the process observability registry (counters/gauges/
    histogram percentiles from ``deepspeed_tpu.observability``) into the
    monitor fan-out at each flush — one event schema across training steps
    and serving metrics."""


# -------------------- AIO / NVMe --------------------


class AioConfig(ConfigModel):
    """Reference ``runtime/swap_tensor/aio_config.py`` keys; consumed by the
    C++ host AIO library (``deepspeed_tpu/csrc/aio.cpp``)."""
    block_size: int = 1048576
    queue_depth: int = 8
    thread_count: int = 1
    single_submit: bool = False
    overlap_events: bool = True
    use_gds: bool = False


# -------------------- checkpoint --------------------


class ValidationMode(str, Enum):
    WARN = "WARN"
    IGNORE = "IGNORE"
    FAIL = "FAIL"


class ParallelWriteConfig(ConfigModel):
    pipeline_stage: bool = False


class CheckpointConfig(ConfigModel):
    """Knob disposition: tag_validation WIRED (cross-process tag agreement
    check before any write, reference engine.py:3092); load_universal WIRED
    (engine.load_universal_checkpoint path). use_node_local_storage and
    parallel_write.pipeline_stage are torch-engine IO staging knobs with no
    seam here — orbax owns per-process shard writes and async staging —
    accepted inert for config-file compatibility."""
    tag_validation: ValidationMode = ValidationMode.WARN
    load_universal: bool = False
    use_node_local_storage: bool = False
    parallel_write: ParallelWriteConfig = {}


# -------------------- compile --------------------


class CompileConfig(ConfigModel):
    """Reference ``runtime/compiler.py`` surface; on TPU everything is always
    compiled — these knobs control jit options (donation, persistent cache).
    The cache directory is not a knob: ``runtime/compiler.py`` takes
    ``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``.

    - ``cache_min_compile_secs``: only programs whose compile took at least
      this long are persisted (JAX's
      ``jax_persistent_cache_min_compile_time_secs``).
    """
    enabled: bool = True
    backend: str = "xla"
    kwargs: Dict[str, Any] = {}
    cache_min_compile_secs: Optional[float] = Field(None, ge=0)


# -------------------- training observability --------------------


class TrainObservabilityConfig(ConfigModel):
    """TPU extension (``"observability"`` config block): training-side
    compile/goodput/MFU telemetry (``observability/xla.py`` +
    ``observability/goodput.py``), the training sibling of serving's
    ``ObservabilityConfig``.

    - ``enabled``: master gate. Off ⇒ the engine records nothing beyond
      the pre-existing ``ds_train_steps_total`` counter (the bench A/B
      arm).
    - ``goodput``: wall-clock goodput ledger
      (``ds_goodput_seconds_total{category=...}`` + fraction gauge).
    - ``compile_watch``: wrap every jitted step program so compile vs
      cache-hit vs retrace is counted per compile key
      (``ds_compile_seconds{key=...}`` etc.), and install the process-wide
      ``backend_compile_duration`` listener.
    - ``mfu``: publish ``ds_train_mfu`` from cost-analysis FLOPs at each
      registry publish (lazy AOT cost analysis — never on the step path).
    - ``memory``: refresh device-memory gauges (live/peak/limit bytes) at
      the publish cadence; silently absent on backends without
      ``memory_stats`` (CPU).
    - ``textfile``: path of an atomically-replaced Prometheus textfile
      written at each registry publish (training has no HTTP server; this
      is what ``ds_top --file`` and node-exporter textfile collectors
      read). ``DS_TPU_METRICS_TEXTFILE`` env is the fallback when unset.
    """
    enabled: bool = True
    goodput: bool = True
    compile_watch: bool = True
    mfu: bool = True
    memory: bool = True
    textfile: Optional[str] = None


class AsyncPipelineConfig(ConfigModel):
    """TPU extension: fully asynchronous train-step pipeline — keep the
    device's dispatch queue full by never blocking the host on a per-step
    device→host round trip in steady state.

    - ``enabled``: switch the engine's train paths to windowed host sync
      (losses/overflow flags accumulate as device scalars and are fetched
      in ONE batched transfer every ``sync_interval`` optimizer steps, or
      on demand via ``engine.get_loss()``), and skip the per-step
      ``effects_barrier`` in the throughput timer.
    - ``prefetch_depth``: how many upcoming batches the device-side
      prefetch iterator keeps in flight (``jax.device_put`` dispatched,
      sharded per the mesh) while the current step runs; 0 disables the
      prefetch wrap of ``engine.training_dataloader``.
    - ``sync_interval``: optimizer steps per host sync window. Deferred
      inside a window: loss fetch, overflow/skipped-step accounting, host
      lr-scheduler advance (compiled-path lr is exact regardless — optax
      reads the update count carried in opt_state), monitor events, and
      steps_per_print logging.
    """
    enabled: bool = False
    prefetch_depth: int = Field(2, ge=0)
    sync_interval: int = Field(16, ge=1)


# -------------------- resilience (extension) --------------------


class FaultInjectionConfig(ConfigModel):
    """Deterministic fault plan (``deepspeed_tpu/utils/fault_injection.py``).
    Each fault entry: ``{"site": <name>, "nth": 1, "times": 1, "args": {}}``
    — the site fires on its ``nth`` visit for ``times`` visits. Sites:
    checkpoint.torn_write, checkpoint.corrupt, train.sigterm,
    train.nan_grads, comm.init_timeout. Inert unless ``enabled``."""
    enabled: bool = False
    seed: int = 0
    faults: List[Dict[str, Any]] = []


class ResilienceConfig(ConfigModel):
    """Fault-tolerant training lifecycle (extension; reference analogue is
    Nebula tiered checkpointing + the elastic agent). Three cooperating
    pieces, all off by default:

    - **Preemption autosave / auto-resume**: SIGTERM/SIGINT request a save
      at the next step boundary (the async window is drained first so the
      snapshot is exact); ``autosave_interval_steps`` adds periodic saves;
      ``auto_resume`` scans ``save_dir`` at init for the newest checkpoint
      that passes manifest verification and restores it.
    - **Anomaly sentry**: watches overflow/loss-scaler signals plus a
      windowed loss-spike detector (loss > ``loss_spike_factor`` x median of
      the last ``loss_spike_window`` good losses, once
      ``loss_spike_min_history`` good steps exist). After
      ``max_consecutive_anomalies`` consecutive bad steps it rolls params /
      opt-state back to the last good checkpoint while keeping the data
      sampler's position — the offending data window is skipped, not
      replayed.
    - **Retention**: ``keep_last_n`` committed tags survive GC (0 keeps
      all); storage writes retry with exponential backoff
      (``save_retries`` attempts, ``retry_backoff_secs`` base delay).
    """
    enabled: bool = False
    save_dir: Optional[str] = None
    autosave_interval_steps: int = Field(0, ge=0)
    keep_last_n: int = Field(3, ge=0)
    auto_resume: bool = False
    preempt_save: bool = True
    preempt_signals: List[str] = ["SIGTERM", "SIGINT"]
    max_consecutive_anomalies: int = Field(3, ge=1)
    loss_spike_window: int = Field(20, ge=2)
    loss_spike_factor: float = Field(3.0, gt=1.0)
    loss_spike_min_history: int = Field(5, ge=1)
    rollback: bool = True
    save_retries: int = Field(3, ge=1)
    retry_backoff_secs: float = Field(0.05, ge=0)
    fault_injection: FaultInjectionConfig = {}


# -------------------- TPU mesh (extension) --------------------


class MeshConfig(ConfigModel):
    """TPU extension: logical mesh shape. -1 on an axis means "fill with
    remaining devices". Axes order fixed: (pipe, data, fsdp, seq, expert, model)."""
    pipe: int = 1
    data: int = -1
    fsdp: int = 1
    seq: int = 1
    expert: int = 1
    model: int = 1
    axis_order: List[str] = ["pipe", "data", "fsdp", "seq", "expert", "model"]


class TensorParallelConfig(ConfigModel):
    """Native tensor-parallel TRAINING (extension beyond the reference,
    which delegates training TP to a user-provided Megatron ``mpu`` —
    ``deepspeed/runtime/engine.py`` mpu plumbing, ``utils/groups.py:68``).
    Here TP is a sharding rule composed WITH the ZeRO plan: linear weights
    are column/row-sharded over the mesh ``model`` axis (AutoTP name
    heuristics / logical-axis rules, ``parallel/tp.py``) and ZeRO shards a
    dimension TP left free, so ZeRO-1/2/3 x TP compose in one program and
    XLA inserts the per-layer psum the reference's mpu codes by hand.

    ``tp_size`` also creates the mesh ``model`` axis when the mesh config
    doesn't name one (the inference config's ``tensor_parallel.tp_size``
    spelling). ``enabled`` engages composition on an existing model axis."""
    enabled: bool = False
    tp_size: Optional[int] = None
