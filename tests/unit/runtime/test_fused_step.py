"""Fused train-step equivalence: one-program fwd+bwd+optimizer must match
the forward/backward/step sequence exactly."""

import sys
import os
import numpy as np
import pytest
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from simple_model import simple_model_and_params  # noqa: E402

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.comm.mesh import reset_mesh_context  # noqa: E402


def make_engine(**over):
    reset_mesh_context()
    cfg = {"train_batch_size": 8,
           "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
           "steps_per_print": 1000}
    cfg.update(over)
    model, params = simple_model_and_params(seed=0)
    engine, *_ = deepspeed_tpu.initialize(model=model, model_parameters=params, config=cfg)
    return engine


def batches(n, seed=0):
    rng = np.random.default_rng(seed)
    return [(jnp.asarray(rng.normal(size=(8, 16)), jnp.float32), jnp.zeros((8, 16)))
            for _ in range(n)]


def test_fused_matches_split_sequence():
    data = batches(5)
    e1 = make_engine()
    ref = []
    for x, y in data:
        loss = e1.forward(x, y)
        e1.backward(loss)
        e1.step()
        ref.append(float(loss))

    e2 = make_engine()
    got = [float(e2.fused_train_step(x, y)) for x, y in data]
    np.testing.assert_allclose(got, ref, rtol=1e-6)
    # final params identical too
    for a, b in zip(jax.tree_util.tree_leaves(e1.params),
                    jax.tree_util.tree_leaves(e2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    assert e2.global_steps == 5
    # the fused step holds no accumulation buffer; the three calls made one
    from deepspeed_tpu.observability import get_registry
    assert e2.grad_acc is None and e1.grad_acc is not None
    assert get_registry().gauge("ds_grad_acc_bytes").value == 0  # e2 wrote last


def test_fused_with_fp16_scaling_and_clipping():
    data = batches(4, seed=1)
    kw = dict(fp16={"enabled": True, "initial_scale_power": 8}, gradient_clipping=0.5)
    e1 = make_engine(**kw)
    ref = []
    for x, y in data:
        loss = e1.forward(x, y)
        e1.backward(loss)
        e1.step()
        ref.append(float(loss))
    e2 = make_engine(**kw)
    got = [float(e2.fused_train_step(x, y)) for x, y in data]
    np.testing.assert_allclose(got, ref, rtol=1e-3)
    assert e2.cur_scale == e1.cur_scale


def test_train_batch_uses_fused_path():
    e = make_engine()
    assert e._train_step_fused is not None
    it = iter(batches(2, seed=2))
    loss = e.train_batch(it)
    assert isinstance(loss, float)
    assert e.global_steps == 1


def test_gas_gt_1_has_no_fused_path():
    e = make_engine(train_batch_size=16, gradient_accumulation_steps=2)
    assert e._train_step_fused is None
    with pytest.raises(AssertionError):
        e.fused_train_step(jnp.ones((8, 16)), jnp.zeros((8, 16)))


@pytest.mark.world_size(8)
def test_gas_fused_train_batch_matches_micro_loop():
    """gas>1 scan-fused train_batch (one dispatch per optimizer step) must
    be numerically identical to the forward/backward/step micro loop."""
    import numpy as np
    from simple_model import simple_model_and_params

    def mk(cfg_extra=None):
        model, params = simple_model_and_params()
        cfg = {"train_batch_size": 32, "gradient_accumulation_steps": 4,
               "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
               "steps_per_print": 100, **(cfg_extra or {})}
        eng, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                                config=cfg)
        return eng

    rng = np.random.default_rng(0)
    micros = [(jnp.asarray(rng.normal(size=(8, 16)), jnp.float32),
               jnp.zeros((8, 16), jnp.float32)) for _ in range(12)]

    eng_fused = mk()
    assert eng_fused._train_batch_fused is not None
    fused_losses = [eng_fused.train_batch(iter(micros[i * 4:(i + 1) * 4]))
                    for i in range(3)]
    assert eng_fused.global_steps == 3 and eng_fused.micro_steps == 12

    eng_loop = mk()
    loop_losses = []
    for i in range(3):
        ls = []
        for x, y in micros[i * 4:(i + 1) * 4]:
            loss = eng_loop.forward(x, y)
            eng_loop.backward(loss)
            eng_loop.step()
            ls.append(float(loss))
        loop_losses.append(sum(ls) / 4)

    assert eng_fused.grad_acc is None and eng_loop.grad_acc is not None
    np.testing.assert_allclose(fused_losses, loop_losses, rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(eng_fused.params),
                    jax.tree_util.tree_leaves(eng_loop.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.world_size(8)
def test_gas_fused_respects_zero_and_scaling():
    """fused gas path under ZeRO-2 + fp16 loss scaling still trains."""
    from simple_model import simple_model_and_params
    import numpy as np
    model, params = simple_model_and_params()
    cfg = {"train_batch_size": 32, "gradient_accumulation_steps": 4,
           "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
           "zero_optimization": {"stage": 2},
           "fp16": {"enabled": True, "initial_scale_power": 8},
           "steps_per_print": 100}
    eng, _, _, _ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                            config=cfg)
    assert eng._train_batch_fused is not None
    rng = np.random.default_rng(1)
    losses = []
    for _ in range(6):
        micros = iter([(jnp.asarray(rng.normal(size=(8, 16)), jnp.float32),
                        jnp.zeros((8, 16), jnp.float32)) for _ in range(4)])
        losses.append(eng.train_batch(micros))
    assert losses[-1] < losses[0], losses


def test_steps_compile_once_across_run():
    """Per-step recompilation is the classic silent 10x step-time killer
    (every jit signature change costs a fresh XLA compile).
    Both training paths must hit their jit caches on every step after the
    first: loop-carried state (params/opt_state/scale) keeps ONE sharding
    + aval signature, fresh same-shape batches keep one input aval."""
    engine = make_engine(optimizer={"type": "AdamW", "params": {"lr": 1e-3}})
    assert engine._train_step_fused is not None
    rng = np.random.default_rng(0)

    def fresh_batch():
        return jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)

    def split_step():
        x = fresh_batch()
        loss = engine.forward(x, jnp.zeros_like(x))
        engine.backward(loss)
        engine.step()

    # fused path (what bench/train_batch run at gas=1)
    engine.fused_train_step(fresh_batch(), jnp.zeros((8, 16), jnp.float32))
    fused0 = engine._train_step_fused._cache_size()
    # split path (forward/backward/step — compiles _fwd_bwd + _apply_step)
    split_step()
    fwdbwd0 = engine._fwd_bwd._cache_size()
    apply0 = engine._apply_step._cache_size()
    for _ in range(4):
        engine.fused_train_step(fresh_batch(), jnp.zeros((8, 16), jnp.float32))
        split_step()
    assert engine._train_step_fused._cache_size() == fused0, (
        "fused train step recompiled mid-run — a signature/sharding leak")
    assert engine._fwd_bwd._cache_size() == fwdbwd0
    assert engine._apply_step._cache_size() == apply0


def test_grad_accum_dtype_knob():
    """data_types.grad_accum_dtype (reference engine.py:938-944) controls the
    accumulation buffer dtype on both the split path (persistent buffer) and
    the gas>1 scan carry; bf16 halves the buffer and the trajectory stays
    close to fp32 accumulation. Unknown dtypes are rejected at build."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import pytest
    import deepspeed_tpu
    from deepspeed_tpu.comm.mesh import reset_mesh_context
    from simple_model import simple_model_and_params

    def run(gad):
        reset_mesh_context()
        model, params = simple_model_and_params()
        cfg = {"train_micro_batch_size_per_gpu": 2,
               "gradient_accumulation_steps": 2,
               "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
               "steps_per_print": 0}
        if gad:
            cfg["data_types"] = {"grad_accum_dtype": gad}
        engine, *_ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                              config=cfg)
        x = jnp.ones((engine.train_micro_batch_size_per_gpu() * engine.dp_world_size, 16))
        data = iter([(x, jnp.zeros_like(x))] * 6)
        losses = [engine.train_batch(data) for _ in range(3)]
        return engine, losses

    def acc_dtypes(engine):
        # the fused batch carries its sums in the program: the engine's own
        # buffer is made by the first unfused forward, at the dtype asked for
        assert engine.grad_acc is None
        x = jnp.ones((engine.train_micro_batch_size_per_gpu() * engine.dp_world_size, 16))
        engine.backward(engine.forward(x, jnp.zeros_like(x)))
        leaves = jax.tree_util.tree_leaves(engine.grad_acc)
        assert len(leaves) == len(jax.tree_util.tree_leaves(engine.params))
        return {l.dtype for l in leaves}

    ref_engine, ref = run(None)
    assert acc_dtypes(ref_engine) == {jnp.dtype(jnp.float32)}

    bf_engine, bf = run("bf16")
    assert acc_dtypes(bf_engine) == {jnp.dtype(jnp.bfloat16)}
    np.testing.assert_allclose(bf, ref, rtol=5e-3)

    with pytest.raises(ValueError, match="grad_accum_dtype"):
        run("int8")

    # fp16 accumulation without fp16 loss scaling saturates silently at
    # 65504 — no overflow check runs to skip the step, so it's rejected
    with pytest.raises(ValueError, match="fp16"):
        run("fp16")


def test_multi_step_fused_matches_sequential():
    """fused_train_steps(K stacked batches) ≡ K sequential fused steps:
    same per-step losses, same final params — one dispatch instead of K."""
    data = batches(6, seed=3)
    e1 = make_engine()
    ref = [float(e1.fused_train_step(x, y)) for x, y in data]

    e2 = make_engine()
    xs = jnp.stack([x for x, _ in data])
    ys = jnp.stack([y for _, y in data])
    losses = np.asarray(e2.fused_train_steps(xs, ys))
    np.testing.assert_allclose(losses, ref, rtol=1e-6)
    assert e2.global_steps == 6
    for a, b in zip(jax.tree_util.tree_leaves(e1.params),
                    jax.tree_util.tree_leaves(e2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_multi_step_fused_runs_lr_schedule_in_program():
    """The injected optax schedule advances per step INSIDE the scan: the
    final LR after one K-step dispatch equals K single-step dispatches."""
    sched = {"scheduler": {"type": "WarmupLR",
                           "params": {"warmup_min_lr": 0.0,
                                      "warmup_max_lr": 1e-2,
                                      "warmup_num_steps": 10}}}
    data = batches(5, seed=4)
    e1 = make_engine(**sched)
    for x, y in data:
        e1.fused_train_step(x, y)
    e2 = make_engine(**sched)
    e2.fused_train_steps(jnp.stack([x for x, _ in data]),
                         jnp.stack([y for _, y in data]))
    assert e2.get_lr()[0] == pytest.approx(e1.get_lr()[0], rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(e1.params),
                    jax.tree_util.tree_leaves(e2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_multi_step_fused_fp16_overflow_bookkeeping():
    """fp16 loss-scaling rides the scan carry; per-step overflow flags come
    back and skipped_steps accounting matches the sequential path."""
    data = batches(4, seed=5)
    kw = dict(fp16={"enabled": True, "initial_scale_power": 4})
    e1 = make_engine(**kw)
    for x, y in data:
        e1.fused_train_step(x, y)
    e2 = make_engine(**kw)
    e2.fused_train_steps(jnp.stack([x for x, _ in data]),
                         jnp.stack([y for _, y in data]))
    assert e2.skipped_steps == e1.skipped_steps
    assert float(e2.scale_state.cur_scale) == float(e1.scale_state.cur_scale)


def test_multi_step_fused_guards():
    """Clean refusals where K-step semantics can't match fused_train_step:
    full ZeRO-Offload (no device apply program) and data-efficiency batch
    routing (per-step shape transforms)."""
    data = batches(1, seed=6)
    e = make_engine(zero_optimization={
        "stage": 3, "offload_optimizer": {"device": "cpu"}})
    with pytest.raises(AssertionError, match="gradient_accumulation"):
        e.fused_train_steps(jnp.stack([data[0][0]]), jnp.stack([data[0][1]]))

    e2 = make_engine(data_efficiency={
        "enabled": True,
        "data_routing": {"enabled": True,
                         "random_ltd": {"enabled": True,
                                        "total_layer_num": 2,
                                        "random_ltd_layer_num": 1,
                                        "random_ltd_layer_id": [0],
                                        "model_mask_name": None,
                                        "model_type": "decoder",
                                        "hidden_state_order": "batch_seq_dim",
                                        "random_ltd_schedule": {
                                            "min_value": 8,
                                            "max_value": 16,
                                            "schedule_type": "fixed_linear",
                                            "schedule_config": {
                                                "require_steps": 10,
                                                "seq_per_step": 8}}}}})
    if e2.random_ltd_scheduler is not None:
        with pytest.raises(RuntimeError, match="curriculum/random-LTD"):
            e2.fused_train_steps(jnp.stack([data[0][0]]),
                                 jnp.stack([data[0][1]]))
