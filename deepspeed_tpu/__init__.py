"""deepspeed_tpu — a TPU-native training & inference framework with the
capabilities of DeepSpeed, built on JAX/XLA/pjit/Pallas.

Public API mirrors the reference (``deepspeed/__init__.py``):
  initialize()      — build a training engine from a model + JSON config
  init_inference()  — build an inference engine
  comm              — functional collectives over the device mesh
"""

import time as _time
_IMPORT_T0 = _time.monotonic()  # first: the package's own import is a span

from .version import __version__
from . import comm
from . import zero
from . import moe
from . import ops
from .config import DeepSpeedTpuConfig
from .runtime import pipe
from .comm.comm import init_distributed

__git_hash__ = None
__git_branch__ = None


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               distributed_port=29500,
               mpu=None,
               dist_init_required=None,
               collate_fn=None,
               config=None,
               mesh_param=None,
               config_params=None,
               **kwargs):
    """Build a :class:`deepspeed_tpu.runtime.engine.DeepSpeedTpuEngine`.

    Reference: ``deepspeed/__init__.py:69``. `model` is a flax module (or
    (init_fn, apply_fn) pair); returns (engine, optimizer, dataloader,
    lr_scheduler) like the reference.
    """
    from .runtime.engine import DeepSpeedTpuEngine

    config = config if config is not None else config_params
    if args is not None and config is None:
        config = getattr(args, "deepspeed_config", None)

    # Normalize the config (dict | json path | DeepSpeedTpuConfig) before ANY
    # engine-selection gate so every spelling routes the same way; JSON nulls
    # stay inert.
    from .config import DeepSpeedTpuConfig as _Cfg
    if isinstance(config, str):
        import json as _json
        with open(config) as _f:
            config = _json.load(_f)
    _pd = config._param_dict if isinstance(config, _Cfg) else (
        config if isinstance(config, dict) else {})

    # RLHF hybrid engine (reference __init__.py: DeepSpeedHybridEngine when
    # config.hybrid_engine.enabled)
    if (_pd.get("hybrid_engine") or {}).get("enabled"):
        from .runtime.hybrid_engine import DeepSpeedHybridEngine as DeepSpeedTpuEngine  # noqa: F811

    # ZeRO-3 parameter offload (ZeRO-Infinity): the streaming layer-list
    # executor (reference stage3.py:614 _configure_tensor_swapping path)
    _op = ((_pd.get("zero_optimization") or {}).get("offload_param") or {})
    if str(_op.get("device", "none")) != "none":
        from .runtime.zero_infinity import ZeroInfinityEngine
        if not isinstance(model, (list, tuple)):
            raise ValueError(
                "zero_optimization.offload_param requires the model as a layer "
                "list (the PipelineModule/LayerSpec contract): params stream "
                "host->HBM per layer, which needs explicit layer boundaries")
        if "loss_fn" not in kwargs:
            raise ValueError("offload_param training requires loss_fn=... "
                             "(maps the last layer's output + batch tail to a scalar)")
        engine = ZeroInfinityEngine(
            layers=model, layer_params=model_parameters,
            loss_fn=kwargs.pop("loss_fn"),
            config=_Cfg(config) if not isinstance(config, _Cfg) else config)
        return engine, engine.optimizer, None, None

    engine = DeepSpeedTpuEngine(model=model,
                                optimizer=optimizer,
                                model_parameters=model_parameters,
                                training_data=training_data,
                                lr_scheduler=lr_scheduler,
                                mpu=mpu,
                                collate_fn=collate_fn,
                                config=config,
                                mesh_param=mesh_param,
                                **kwargs)
    return_items = [engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler]
    return tuple(return_items)


def init_inference(model=None, config=None, params=None, **kwargs):
    """Build an inference engine (reference ``deepspeed/__init__.py:291``)."""
    from .inference.engine import InferenceEngine
    from .inference.config import DeepSpeedInferenceConfig
    if config is None:
        config = {}
    if isinstance(config, dict):
        config = DeepSpeedInferenceConfig(**{**config, **kwargs})
    return InferenceEngine(model, config=config, params=params)


def pipeline(model_dir, **kwargs):
    """Text-generation pipeline from a HF checkpoint dir (the MII
    ``mii.pipeline`` surface; see ``inference.v2.pipeline``)."""
    from .inference.v2.pipeline import pipeline as _pipeline
    return _pipeline(model_dir, **kwargs)


def add_config_arguments(parser):
    """Reference ``deepspeed/__init__.py:268`` argparse passthrough."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true")
    group.add_argument("--deepspeed_config", default=None, type=str)
    group.add_argument("--deepscale", default=False, action="store_true")
    group.add_argument("--local_rank", type=int, default=-1)
    return parser


# last: what this package's import took, whatever was imported before it
# (jax, flax), as the span ``ds.import`` of the tracer's kept ring
from .observability.tracing import get_tracer as _get_tracer
_get_tracer().closed_scope("ds.import", _IMPORT_T0, _time.monotonic())
