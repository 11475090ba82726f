"""PipelineEngine — training over the SPMD pipeline executor.

Rebuild of reference ``runtime/pipe/engine.py:61 PipelineEngine`` with the
same user contract — ``train_batch(data_iter)`` (:337) runs
gradient_accumulation_steps microbatches through the pipeline + one optimizer
step; ``eval_batch`` (:398) forward-only — but execution is the compiled
scan+ppermute pipeline (spmd.py), not a host instruction loop: under SPMD
the TrainSchedule's send/recv/fwd/bwd DAG is what XLA compiles the scan into.

Model structure: {embed, body, head}. Embed/head run replicated outside the
pipeline region (grads psum automatically); the homogeneous body is stacked
[L, ...] and sharded (L -> pipe axis, remaining dims by the ZeRO rule).
Composes with DP/fsdp: the batch stays sharded over the data axes — only the
``pipe`` axis is "manual" in the shard_map region.
"""

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ...comm.mesh import MeshContext
from ...utils.logging import logger
from ..zero_sharding import ZeroShardingPlan, composed_tp_zero_spec, leaf_spec
from ...parallel.tp import path_str
from .spmd import spmd_pipeline_1f1b, spmd_pipeline_eval


def _smap(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         axis_names={"pipe"}, check_vma=False)


class PipeZeroPlan(ZeroShardingPlan):
    """ZeRO sharding with the pipe dimension consumed first: body leaves are
    [L, ...] with dim0 sharded over ``pipe``; the ZeRO rule — composed with
    TP when ``tp=True`` — applies to the remaining dims. The 1F1B executor's
    shard_map is partial-manual over ``pipe`` only, so model/zero sharding
    on the trailing dims stays GSPMD-managed inside the pipeline (psums on
    row-parallel weights land inside each stage)."""

    def __init__(self, ctx: MeshContext, stage: int, body_key: str = "body", **kw):
        super().__init__(ctx, stage, **kw)
        self.body_key = body_key

    def param_shardings(self, params):
        base = super().param_shardings(params)
        return self._override_body(params, base, self.stage >= 3,
                                   min_size=self.param_persistence_threshold)

    def grad_shardings(self, params):
        base = super().grad_shardings(params)
        return self._override_body(params, base, self.stage >= 2)

    def opt_state_shardings(self, opt_state, params=None):
        base = super().opt_state_shardings(opt_state)
        return self._override_body(opt_state, base, self.stage >= 1)

    def _override_body(self, tree, base, zero_active, min_size: int = 0):
        pipe = self.ctx.axis_size("pipe")
        if pipe <= 1:
            return base
        def _one(path, leaf, cur):
            names = [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]
            shape = getattr(leaf, "shape", ())
            if self.body_key not in names or len(shape) == 0 or shape[0] % pipe != 0:
                return cur
            zaxes = self.zero_axes if (zero_active and self.zero_axes) else ()
            if self.tp:
                rest = composed_tp_zero_spec(
                    path_str(path), shape[1:], self.ctx, zaxes,
                    self.ctx.axis_size(zaxes) if zaxes else 1,
                    min_size=min_size)
            elif zaxes:
                rest = leaf_spec(shape[1:], zaxes,
                                 self.ctx.axis_size(zaxes), min_size=min_size)
            else:
                rest = P()
            return NamedSharding(self.ctx.mesh, P("pipe", *tuple(rest)))

        return jax.tree_util.tree_map_with_path(_one, tree, base)


def _zero_cotangent(x):
    """Cotangent for a non-differentiated input: float0 for int dtypes."""
    if jnp.issubdtype(x.dtype, jnp.inexact):
        return jnp.zeros_like(x)
    return np.zeros(x.shape, jax.dtypes.float0)


def pipe_compute_specs(tree, ctx: MeshContext, tp: bool, leading_pipe: bool):
    """Gather-for-compute shardings for the pre-pipeline constraint: the
    ZeRO axes are gathered ONCE per step (stage-3 semantics — collectives
    inside the scan's cond branches would also deadlock the CPU runtime's
    rendezvous), but under TP the model axis must STAY sharded — replicating
    it would silently defeat TP's compute/memory point every step.
    ``leading_pipe``: body leaves are [L, ...] with dim 0 on the pipe axis."""
    def _one(path, leaf):
        shape = getattr(leaf, "shape", ())
        lead = ("pipe", ) if leading_pipe and len(shape) > 0 else ()
        rest_shape = shape[1:] if lead else shape
        if tp:
            rest = tuple(composed_tp_zero_spec(path_str(path), rest_shape,
                                               ctx, (), 1))
        else:
            rest = ()
        return NamedSharding(ctx.mesh, P(*lead, *rest))

    return jax.tree_util.tree_map_with_path(_one, tree)


def make_pipeline_apply(embed_apply: Callable,
                        layer_apply: Callable,
                        head_apply: Callable,
                        mesh_ctx: MeshContext,
                        num_microbatches: int,
                        remat_layers: bool = True,
                        tp: bool = False):
    """Build an `apply_fn(params, *batch) -> loss` running {embed -> pipelined
    body -> head}. `params` = {"embed", "body" ([L,...] stacked), "head"}.

    - embed_apply(embed_params, *batch_inputs) -> [B, ...] activations
    - layer_apply(layer_params, x) -> x   (one body layer)
    - head_apply(head_params, x, *batch_targets) -> scalar loss
    The batch is split as inputs = batch[:-1], targets = batch[-1:].

    Training lowers to the interleaved 1F1B executor (embed inside stage 0,
    head inside the last stage — O(S·mb) activation memory); the loss's VJP
    returns the gradients the executor accumulated in-scan. Forward-only
    calls (eval) use the cheap InferenceSchedule executor.

    Loss semantics under pipe>1: the MEAN of per-microbatch head losses
    (reference pipe/engine.py:582 _aggregate_total_loss averages micro
    losses the same way). A head that masks tokens non-uniformly across
    microbatches yields mean-of-means, not a global token mean.
    """
    pipe = mesh_ctx.axis_size("pipe")
    mesh = mesh_ctx.mesh

    def stage_fn(stage_params, x):
        def one_layer(h, lp):
            f = layer_apply
            if remat_layers:
                f = jax.checkpoint(layer_apply)
            return f(lp, h), None

        out, _ = jax.lax.scan(one_layer, x, stage_params)
        return out

    # executor adapters: inputs/targets travel as tuples of microbatched arrays
    def ingest_fn(embed_params, in_mb):
        return embed_apply(embed_params, *in_mb)

    def head_fn(head_params, y, tgt_mb):
        return head_apply(head_params, y, *tgt_mb)

    body_specs = P("pipe")

    def run_train(body, embed, head, in_mbs, tgt_mbs):
        f = _smap(
            lambda b, e, hd, i, tg: spmd_pipeline_1f1b(
                stage_fn, ingest_fn, head_fn, b, e, hd, i, tg, axis_name="pipe"),
            mesh, (body_specs, P(), P(), P(), P()),
            (P(), body_specs, P(), P()))
        return f(body, embed, head, in_mbs, tgt_mbs)

    def run_eval(body, embed, head, in_mbs, tgt_mbs):
        f = _smap(
            lambda b, e, hd, i, tg: spmd_pipeline_eval(
                stage_fn, ingest_fn, head_fn, b, e, hd, i, tg, axis_name="pipe"),
            mesh, (body_specs, P(), P(), P(), P()), P())
        return f(body, embed, head, in_mbs, tgt_mbs)

    @jax.custom_vjp
    def pipelined(body, embed, head, in_mbs, tgt_mbs):
        return run_eval(body, embed, head, in_mbs, tgt_mbs)

    def pipelined_fwd(body, embed, head, in_mbs, tgt_mbs):
        loss, db, de, dh = run_train(body, embed, head, in_mbs, tgt_mbs)
        cast = lambda g, p: jax.tree_util.tree_map(  # noqa: E731
            lambda gg, pp: gg.astype(pp.dtype), g, p)
        return loss, (cast(db, body), cast(de, embed), cast(dh, head),
                      in_mbs, tgt_mbs)

    def pipelined_bwd(res, g):
        db, de, dh, in_mbs, tgt_mbs = res
        sc = lambda tree: jax.tree_util.tree_map(  # noqa: E731
            lambda x: x * g.astype(x.dtype), tree)
        z = lambda tree: jax.tree_util.tree_map(_zero_cotangent, tree)  # noqa: E731
        return sc(db), sc(de), sc(dh), z(in_mbs), z(tgt_mbs)

    pipelined.defvjp(pipelined_fwd, pipelined_bwd)

    def _microbatch(tree, M):
        def one(x):
            B = x.shape[0]
            assert B % M == 0, f"batch {B} not divisible by microbatches {M}"
            return x.reshape(M, B // M, *x.shape[1:])
        return jax.tree_util.tree_map(one, tree)

    def apply_fn(params, *batch):
        inputs, targets = batch[:-1], batch[-1:]
        M = num_microbatches
        if pipe > 1:
            in_mbs = _microbatch(tuple(inputs), M)
            tgt_mbs = _microbatch(tuple(targets), M)
            # ZeRO-3 x PP: gather params over the ZeRO axis ONCE per step,
            # OUTSIDE the pipeline scan (gather-for-compute, shard-at-rest —
            # stage3 semantics); under TP the model axis stays sharded
            # (pipe_compute_specs) — the partial-manual executor carries it
            body = jax.lax.with_sharding_constraint(
                params["body"],
                pipe_compute_specs(params["body"], mesh_ctx, tp, True))
            embed = jax.lax.with_sharding_constraint(
                params["embed"],
                pipe_compute_specs(params["embed"], mesh_ctx, tp, False))
            head = jax.lax.with_sharding_constraint(
                params["head"],
                pipe_compute_specs(params["head"], mesh_ctx, tp, False))
            return pipelined(body, embed, head, in_mbs, tgt_mbs)
        # pipe=1: plain sequential execution (no pipeline region)
        h = embed_apply(params["embed"], *inputs)
        mbs = _microbatch(h, M)
        out = jax.vmap(lambda x: stage_fn(params["body"], x))(mbs)
        out = out.reshape(h.shape[0], *out.shape[2:])
        return head_apply(params["head"], out, *targets)

    return apply_fn


class PipelineEngine:
    """Thin orchestrator with the reference train_batch/eval_batch surface.

    Delegates optimizer/checkpoint/precision to DeepSpeedTpuEngine by
    constructing it with the pipelined apply_fn and a PipeZeroPlan.
    """

    def __init__(self,
                 embed_apply: Callable,
                 layer_apply: Callable,
                 head_apply: Callable,
                 params,
                 config=None,
                 num_microbatches: Optional[int] = None):
        from ..engine import DeepSpeedTpuEngine

        assert set(params.keys()) >= {"embed", "body", "head"}, \
            "pipeline params must be {embed, body, head}"

        cfg = dict(config or {})
        gas = cfg.get("gradient_accumulation_steps", 1)

        class _Eng(DeepSpeedTpuEngine):
            def __init__(eng, **kw):
                super().__init__(**kw)

        # engine builds the mesh; apply_fn needs it — two-phase: create
        # engine with a placeholder then swap in the pipelined apply
        self._num_microbatches = num_microbatches
        self.engine = _Eng(model=lambda p, *a, **k: jnp.float32(0.0),
                           model_parameters=params, config=cfg, dont_shard=True)
        mesh_ctx = self.engine.mesh_ctx
        mb = num_microbatches or mesh_ctx.axis_size("pipe") * 2
        apply_fn = make_pipeline_apply(embed_apply, layer_apply, head_apply,
                                       mesh_ctx, mb,
                                       tp=getattr(self.engine, "_tp_training",
                                                  False))
        self.engine.apply_fn = apply_fn
        self.engine.zero_plan = PipeZeroPlan(
            mesh_ctx, self.engine._config.zero_config.stage,
            tp=getattr(self.engine, "_tp_training", False),
            param_persistence_threshold=(
                self.engine._config.zero_config.param_persistence_threshold))
        self.engine._init_state(params)
        self.engine._build_compiled_fns()
        self.micro_batches = mb

    def train_batch(self, data_iter):
        """One full batch: forward+backward over all microbatches (inside the
        compiled pipeline), then step (reference pipe/engine.py:337)."""
        batch = next(data_iter)
        if not isinstance(batch, (tuple, list)):
            batch = (batch, )
        loss = self.engine.forward(*batch)
        self.engine.backward(loss)
        self.engine.step()
        return loss

    def eval_batch(self, data_iter):
        batch = next(data_iter)
        if not isinstance(batch, (tuple, list)):
            batch = (batch, )
        return self.engine.eval_batch(*batch)

    def __getattr__(self, name):
        return getattr(self.engine, name)
