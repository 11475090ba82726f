"""1-bit Adam/LAMB compressed WIRE train program.

Reference: ``runtime/comm/nccl.py:16 compressed_allreduce`` driving
``runtime/fp16/onebit/adam.py`` — post-warmup, the DP exchange carries sign
bits + scales instead of fp32 gradients (~32x wire reduction,
docs/_tutorials/onebit-adam.md).

TPU shape: the engine's normal fused step lets GSPMD emit the fp32 gradient
psum. This module builds the POST-WARMUP alternative: a ``shard_map``
program with the data-parallel axes manual, where

  1. each worker computes LOCAL gradients (no implicit psum — the axis is
     manual),
  2. the optimizer's momentum update runs on local grads and the momentum is
     exchanged through ``comm.compressed.compressed_allreduce_tree`` — the
     arrays crossing ICI are the packed uint8 sign bits + one scale per
     worker,
  3. every worker applies the identical averaged update, keeping the
     replicated-parameter invariant (variance is frozen post-warmup, so no
     unreduced statistic can diverge).

The engine dispatches: steps < freeze_step run the standard program (exact
Adam on reduced grads — the reference's uncompressed warmup), steps >=
freeze_step run this program. The phase switch is a host-side compile-time
decision, mirroring the reference's Python branch at freeze_step.

Constraints (checked): gas=1, ZeRO stage 0 (replicated params/opt state),
pure-DP mesh (model/seq/expert/pipe axes trivial), no fp16 loss scaling,
no global gradient clipping (it would need the fp32 reduce this avoids).

Known limitation: the error-feedback buffers are per-worker by design
(reference semantics); they ride the replicated opt-state slot, so a
checkpoint captures worker 0's buffer and a restore resets the others'
residuals — bounded impact, the feedback re-accumulates within a few steps.
"""

from functools import partial

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..comm.compressed import compressed_allreduce_tree
from ..utils.logging import log_dist


def _smap(f, mesh, in_specs, out_specs, axes):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         axis_names=set(axes), check_vma=False)


def wire_supported(engine) -> bool:
    cfg = engine._config
    ctx = engine.mesh_ctx
    dp = sum(ctx.axis_size(a) > 1 for a in ("data", "fsdp"))
    return (cfg.gradient_accumulation_steps == 1
            and cfg.zero_config.stage == 0
            and not cfg.fp16_enabled
            and not cfg.gradient_clipping  # global-grad clip needs the fp32 reduce
            and dp >= 1  # something to compress across
            and all(ctx.axis_size(a) == 1 for a in ("model", "seq", "expert", "pipe")))


def build_wire_step(engine, name: str):
    """Compile the post-warmup compressed-wire step for `engine`. Returns a
    callable with the engine's fused-step signature
    ``(params, opt_state, scale_state, args, kwargs, static_kv)``."""
    from .onebit import build_onebit_optimizer
    from .engine import _extract_loss

    if not wire_supported(engine):
        raise ValueError(
            "the 1-bit compressed wire program needs gas=1, ZeRO stage 0, "
            "bf16/fp32, and a pure data-parallel mesh")
    ctx = engine.mesh_ctx
    mesh = ctx.mesh
    dp_axes = tuple(a for a in ("data", "fsdp") if ctx.axis_size(a) > 1)
    ax = dp_axes if len(dp_axes) > 1 else dp_axes[0]  # lax collective axis arg
    compute_dtype = engine.compute_dtype
    apply_fn = engine.apply_fn
    gas = 1

    exchange = partial(compressed_allreduce_tree, axis_names=ax)
    tx = build_onebit_optimizer(name, dict(engine._config.optimizer_params or {}),
                                engine._lr_fn or engine._base_lr,
                                exchange_fn=exchange)

    def local_step(params, opt_state, args, kwargs, static_kv):
        def loss_of(p):
            cp = jax.tree_util.tree_map(lambda x: x.astype(compute_dtype), p)
            out = apply_fn(cp, *args, **dict(kwargs, **dict(static_kv)))
            loss, _ = _extract_loss(out)
            return loss.astype(jnp.float32) / gas

        loss, grads = jax.value_and_grad(loss_of)(params)  # LOCAL grads
        grads = jax.tree_util.tree_map(lambda g: g.astype(jnp.float32), grads)
        updates, new_opt = tx.update(grads, opt_state, params)
        new_params = optax.apply_updates(params, updates)
        # diagnostic only: mean of per-worker local-grad norms (the true
        # global-grad norm would require the fp32 reduce this program avoids)
        gnorm = jax.lax.pmean(optax.global_norm(grads), ax)
        loss = jax.lax.pmean(loss, ax)
        return loss, new_params, new_opt, gnorm

    repl = NamedSharding(mesh, P())
    batch_spec = P(ax)

    def step(params, opt_state, scale_state, args, kwargs, static_kv):
        def region(params, opt_state, args, kwargs):
            return local_step(params, opt_state, args, kwargs, static_kv)

        in_specs = (P(), P(),
                    jax.tree_util.tree_map(lambda _: batch_spec, args),
                    jax.tree_util.tree_map(lambda _: batch_spec, kwargs))
        fn = _smap(region, mesh, in_specs, (P(), P(), P(), P()), dp_axes)
        loss, new_params, new_opt, gnorm = fn(params, opt_state, args, kwargs)
        # same output arity as the engine's fused step (no model stats)
        return (loss, new_params, new_opt, scale_state,
                jnp.bool_(False), gnorm, {})

    from .loss_scaler import LossScaleState
    jitted = jax.jit(step, donate_argnums=(0, 1), static_argnums=(5, ),
                     out_shardings=(None, engine.param_shardings,
                                    engine.opt_state_shardings,
                                    LossScaleState(*engine.scale_state_shardings),
                                    repl, repl, repl))
    log_dist(f"1-bit wire program built: dp axes {dp_axes}, "
             f"optimizer {name} (packed uint8 sign exchange)", ranks=[0])
    return jitted
