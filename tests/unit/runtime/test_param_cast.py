"""param_cast="model": fp32 masters flow into apply and the model's use-site
casts (flax ``dtype=``) down-convert per use — under nn.scan, per chunk.

This is the structural fix for the round-4 OOM: an engine-side whole-tree
cast materializes every stacked [L, ...] leaf as a model-sized
convert_element_type temp before the scan starts; use-site casting converts
only the current scan step's slice (reference analog: the ZeRO-3 param
coordinator gathers/casts one layer at a time, stage3.py's prefetch window).
"""

import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.comm.mesh import reset_mesh_context
from deepspeed_tpu.models import LlamaConfig, init_llama
from deepspeed_tpu.models.llama import cross_entropy_loss


def tiny_cfg(**over):
    kw = dict(vocab_size=128, hidden_size=64, intermediate_size=128,
              num_hidden_layers=4, num_attention_heads=4,
              num_key_value_heads=4, max_position_embeddings=64,
              scan_layers=True)
    kw.update(over)
    return LlamaConfig(**kw)


def make_engine(cfg_model, params, **over):
    reset_mesh_context()
    ds = {"train_batch_size": 8,
          "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
          "bf16": {"enabled": True},
          "steps_per_print": 1000}
    ds.update(over)
    engine, *_ = deepspeed_tpu.initialize(
        model=cfg_model, model_parameters=params, config=ds,
        loss_fn=None)
    return engine


def data(cfg, steps=3, seed=0):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.integers(0, cfg.vocab_size, size=(8, 32)), jnp.int32)
            for _ in range(steps)]


def test_param_cast_model_matches_engine_cast():
    """Same model, same data: losses from the two cast placements track each
    other (identical matmul inputs — both cast to bf16 before the MXU; only
    grad storage dtype differs, fp32 vs bf16)."""
    cfg = tiny_cfg()
    model, params = init_llama(cfg, seed=0)
    batches = data(cfg)

    losses = {}
    for mode in ("engine", "model"):
        m, p = init_llama(cfg, seed=0)
        eng = make_engine(m, p, param_cast=mode)
        out = []
        for ids in batches:
            out.append(float(eng.fused_train_step(ids, labels=ids)))
        losses[mode] = out
    np.testing.assert_allclose(losses["model"], losses["engine"], rtol=2e-2)


def test_param_cast_model_no_stacked_convert():
    """Under remat (the realistic bench config) the compiled fused step must
    contain NO whole-stacked bf16 parameter buffer at all — no
    `bf16[n_scan, ...]` convert temp (the round-4 OOM pattern) and no
    bf16 stacked residual.

    Three pieces make this structural: use-site casts (param_cast="model"),
    the optimization_barrier in _use_cast (stops XLA's
    convert/dynamic-slice commute + LICM from hoisting the casts back out
    of the scan loop), and remat (stops jax from saving per-chunk cast
    kernels as residuals, which XLA narrows into a stacked bf16 copy —
    observable with remat=False)."""
    cfg = tiny_cfg(remat=True)
    model, params = init_llama(cfg, seed=0)
    eng = make_engine(model, params, param_cast="model")
    ids = data(cfg, steps=1)[0]

    fused = eng._train_step_fused
    assert fused is not None
    lowered = fused.lower(eng.params, eng.opt_state, eng.scale_state,
                          (ids,), {"labels": ids}, ())
    hlo = lowered.compile().as_text()
    # stacked q_proj kernel leaf: [n_layers, hidden, hidden] = [4, 64, 64].
    # Engine-side casting emits `bf16[4,64,64] convert(f32[4,64,64] ...)`;
    # use-site casting converts only the sliced chunk [64, 64].
    assert "bf16[4,64,64]" not in hlo


def test_param_cast_validation():
    cfg = tiny_cfg()
    model, params = init_llama(cfg, seed=0)
    with pytest.raises(ValueError, match="param_cast"):
        make_engine(model, params, param_cast="nonsense")


def test_param_cast_model_eval_path():
    """fwd_only (eval) honors the knob too."""
    cfg = tiny_cfg()
    model, params = init_llama(cfg, seed=0)
    eng = make_engine(model, params, param_cast="model")
    ids = data(cfg, steps=1)[0]
    eng.eval()
    logits = eng(ids)
    assert logits.shape == (8, 32, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.world_size(8)
def test_param_cast_model_composes_with_zero3():
    """Use-site casting must not disturb GSPMD: ZeRO-3 with
    param_cast=model trains, and the barrier leaves shardings intact."""
    cfg = tiny_cfg(remat=True)
    model, params = init_llama(cfg, seed=0)
    eng = make_engine(model, params, param_cast="model",
                      zero_optimization={"stage": 3,
                                         "stage3_param_persistence_threshold": 0})
    ids = data(cfg, steps=2)
    l0 = float(eng.fused_train_step(ids[0], labels=ids[0]))
    l1 = float(eng.fused_train_step(ids[0], labels=ids[0]))
    assert np.isfinite(l0) and l1 < l0
    # params stayed ZeRO-sharded (over the mesh's dp axes) through the step
    q = eng.params["model"]["layers"]["layer"]["self_attn"]["q_proj"]["kernel"]
    axes = set(jax.tree_util.tree_leaves(
        [e for e in tuple(q.sharding.spec) if e is not None]))
    assert axes & {"data", "fsdp"}, q.sharding.spec
