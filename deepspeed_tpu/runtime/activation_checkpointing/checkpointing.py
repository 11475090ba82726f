"""Activation checkpointing (Megatron-compatible API surface).

Reference: ``runtime/activation_checkpointing/checkpointing.py`` —
``checkpoint() :993`` / ``CheckpointFunction :486`` (autograd recompute),
``partition_activations :375`` (shard saved activations across TP ranks),
CPU checkpointing (host offload of saved activations), contiguous buffers,
``CudaRNGStatesTracker :124`` (fork RNG so dropout is consistent between the
forward and the recomputed forward).

TPU mapping:
- recompute = ``jax.checkpoint`` (jax.remat): policy-driven, composable with
  scan-over-layers; CheckpointFunction's saved-tensor plumbing is the AD
  system's job.
- partition_activations = saving residuals *sharded over the model axis*:
  achieved by a with_sharding_constraint on the checkpointed function's
  inputs — under GSPMD each rank then materializes only its slice of the
  saved activation (same memory win as the reference's explicit
  scatter/gather, no manual all_gather on backward: XLA inserts it).
- cpu_checkpointing = ``save_and_offload_only_these_names`` host offload
  when the jax version provides it; otherwise falls back to full recompute
  (strictly less memory than saving on device).
- RNG tracker: explicit key bookkeeping (JAX RNG is functional — the
  fork/restore dance reduces to reusing the same key for both executions,
  which jax.checkpoint does by construction; the tracker exists for
  Megatron-style callers that manage named dropout streams).
"""

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ...utils.logging import logger

_CONFIG = {
    "partition_activations": False,
    "cpu_checkpointing": False,
    "contiguous_memory_optimization": False,
    "number_checkpoints": None,
    "synchronize_checkpoint_boundary": False,
    "profile": False,
    "policy": None,  # jax.checkpoint_policies name, e.g. "dots_saveable"
}

_MODEL_PARALLEL_RNG_KEY = "model-parallel-rng"


def configure(mpu_=None, deepspeed_config=None, partition_activations=None,
              contiguous_checkpointing=None, num_checkpoints=None,
              checkpoint_in_cpu=None, synchronize=None, profile=None):
    """Reference checkpointing.py:configure — store the knobs."""
    if deepspeed_config is not None:
        ac = getattr(deepspeed_config, "activation_checkpointing_config", None)
        if ac is not None:
            _CONFIG["partition_activations"] = getattr(ac, "partition_activations", False)
            _CONFIG["cpu_checkpointing"] = getattr(ac, "cpu_checkpointing", False)
            _CONFIG["contiguous_memory_optimization"] = \
                getattr(ac, "contiguous_memory_optimization", False)
            _CONFIG["number_checkpoints"] = getattr(ac, "number_checkpoints", None)
            _CONFIG["policy"] = getattr(ac, "remat_policy", None)
    for key, val in (("partition_activations", partition_activations),
                     ("contiguous_memory_optimization", contiguous_checkpointing),
                     ("number_checkpoints", num_checkpoints),
                     ("cpu_checkpointing", checkpoint_in_cpu),
                     ("synchronize_checkpoint_boundary", synchronize),
                     ("profile", profile)):
        if val is not None:
            _CONFIG[key] = val


def is_configured() -> bool:
    return True


def _resolve_policy(function=None, args=(), kwargs=None):
    name = _CONFIG["policy"]
    if _CONFIG["cpu_checkpointing"]:
        # host-offload the saved residuals when this jax exposes it
        offload = getattr(jax.checkpoint_policies, "save_and_offload_only_these_names",
                          None)
        if offload is None:
            logger.warning("cpu_checkpointing: offload policy unavailable; "
                           "falling back to full recompute")
            return jax.checkpoint_policies.nothing_saveable
        return offload(names_which_can_be_saved=[], names_which_can_be_offloaded=[],
                       offload_src="device", offload_dst="pinned_host")
    if name:
        pol = getattr(jax.checkpoint_policies, name, None)
        if pol is None:
            raise ValueError(f"unknown remat policy '{name}'")
        return pol
    # no policy named: recompute everything but what an attention kernel gave
    # (its output and log-sum-exp, ``ops/attention.py::RESIDUAL_NAMES``) and,
    # as ``models/llama.py`` does, as many of the values ``function`` names
    # (``ops/remat.py::CANDIDATE_NAMES``, in that order) as the chip has room
    # for beside the step. A function that reaches no named producer keeps
    # nothing, ``remat_policy: "nothing_saveable"`` keeps nothing anywhere.
    # A policy by names, made anew for each plan: jax remembers its trace of
    # ``function`` by function and policy, so the choice has to be in one
    from ...ops import remat
    names = remat.RESIDUAL_NAMES
    if function is not None:
        leaves = [a for a in jax.tree_util.tree_leaves((args, kwargs))
                  if hasattr(a, "shape")]
        plan = remat.plan_for(
            (function, tuple((a.shape, str(a.dtype)) for a in leaves)),
            lambda: [remat.price_list(function, *args, **(kwargs or {}))],
            rows=leaves[0].shape[0] if leaves and leaves[0].ndim else 1,
            layer_input_bytes=sum(a.size * a.dtype.itemsize for a in leaves))
        names = plan[0] if plan else names
    return jax.checkpoint_policies.save_only_these_names(*names, *remat.DSA_CHOICE)


def _partition_arg(x):
    """Shard a saved activation over the model axis (reference
    partition_activations :375: each TP rank keeps 1/mp of the tensor)."""
    from ...comm.mesh import get_mesh_context, mesh_is_initialized
    if not mesh_is_initialized() or not hasattr(x, "ndim") or x.ndim == 0:
        return x
    ctx = get_mesh_context()
    mp = ctx.mp_size
    if mp <= 1:
        return x
    # constrain the last axis (feature dim) over 'model' when divisible
    if x.shape[-1] % mp == 0:
        from jax.sharding import PartitionSpec as P
        spec = P(*([None] * (x.ndim - 1) + ["model"]))
        return jax.lax.with_sharding_constraint(x, ctx.sharding(*spec))
    return x


def checkpoint(function: Callable, *args, **kwargs):
    """Reference checkpoint() :993 — run `function` under remat; activations
    are recomputed in backward rather than saved."""
    policy = _resolve_policy(function, args, kwargs)
    fn = function
    if _CONFIG["partition_activations"]:
        inner = function

        def fn(*a, **kw):  # noqa: F811 — saved inputs get model-axis sharding
            a = tuple(_partition_arg(x) for x in a)
            return inner(*a, **kw)

    return jax.checkpoint(fn, policy=policy)(*args, **kwargs)


def checkpoint_wrapper(function: Callable) -> Callable:
    """Decorator form (used by models to remat per layer)."""

    def wrapped(*args, **kwargs):
        return checkpoint(function, *args, **kwargs)

    return wrapped


# ----------------------------------------------------------- RNG tracking

class RNGStatesTracker:
    """Reference CudaRNGStatesTracker :124 — named independent RNG streams.
    JAX keys are explicit, so a "state" is just a key; fork() yields a
    subkey deterministically, and the same key reaches both the forward and
    the remat recompute by construction."""

    def __init__(self):
        self._states = {}

    def reset(self):
        self._states.clear()

    def get_states(self):
        return dict(self._states)

    def set_states(self, states):
        self._states = dict(states)

    def add(self, name: str, seed: int):
        if name in self._states:
            raise Exception(f"RNG state {name} already exists")
        self._states[name] = jax.random.PRNGKey(seed)

    def fork(self, name: str = _MODEL_PARALLEL_RNG_KEY):
        """Context-manager-free fork: returns a fresh subkey and advances the
        stream (the torch version is a context manager because CUDA RNG is
        implicit global state; JAX has no such thing)."""
        if name not in self._states:
            raise Exception(f"RNG state {name} not added")
        self._states[name], sub = jax.random.split(self._states[name])
        return sub


_RNG_TRACKER = RNGStatesTracker()


def get_cuda_rng_tracker() -> RNGStatesTracker:  # reference-compatible name
    return _RNG_TRACKER


def model_parallel_rng_seed(seed: int):
    """Reference model_parallel_cuda_manual_seed: data-parallel-identical,
    model-parallel-distinct streams. Returns (replicated_key, per-mp-rank
    key maker for use inside shard_map)."""
    base = jax.random.PRNGKey(seed)
    _RNG_TRACKER.reset()
    _RNG_TRACKER.set_states({_MODEL_PARALLEL_RNG_KEY: jax.random.fold_in(base, 2718)})

    def mp_key():
        # inside shard_map/jit: fold in this rank's model-axis index
        return jax.random.fold_in(base, jax.lax.axis_index("model") + 2718)

    return base, mp_key
