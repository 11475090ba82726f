"""Native tensor-parallel TRAINING through the engine (extension beyond the
reference, which delegates training TP to a user Megatron ``mpu`` —
``deepspeed/runtime/engine.py`` mpu plumbing, ``utils/groups.py:68``; the
reference's own configurable-MP coverage is
``tests/unit/model_parallelism/test_configurable_parallel_mp.py``).

Here TP is a sharding rule composed with the ZeRO plan
(``runtime/zero_sharding.py composed_tp_zero_spec``): column/row-shard
linear weights over the mesh ``model`` axis, ZeRO shards a dim TP left
free, XLA inserts the per-layer psum. These tests pin:
- placement: q/o/gate/down kernels land on the model axis, with the ZeRO
  axis composed in at stage>=1 (params at 3, moments at 1);
- numerics: a TP=2 run matches the TP=1 run at the same GLOBAL batch;
- checkpoint: save under TP=2, resume under TP=1 (and the reverse), the
  configurable-parallelism resize the reference tests via mpu checkpoints.
"""

import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import deepspeed_tpu  # noqa: E402
from deepspeed_tpu.comm.mesh import reset_mesh_context  # noqa: E402
from deepspeed_tpu.models import LlamaConfig, init_llama  # noqa: E402


def _cfg(mesh, stage, tp=None, micro=2, gas=1):
    dp = 1
    for a in ("data", "fsdp"):
        dp *= mesh.get(a, 1)
    c = {"train_micro_batch_size_per_gpu": micro,
         "gradient_accumulation_steps": gas,
         "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
         # the toy model's leaves are all under the default persistence
         # threshold (they would stay ZeRO-replicated, correctly)
         "zero_optimization": {"stage": stage,
                               "stage3_param_persistence_threshold": 0},
         "mesh": mesh,
         "steps_per_print": 0}
    if tp:
        c["tensor_parallel"] = tp
    return c


def _engine(mesh, stage, tp=None, seed=0, cfg_over=None, **kw):
    reset_mesh_context()
    cfg = LlamaConfig.tiny(num_hidden_layers=2, hidden_size=64,
                           intermediate_size=128, num_attention_heads=4,
                           num_key_value_heads=4, vocab_size=256,
                           attn_impl="xla", **(cfg_over or {}))
    model, params = init_llama(cfg, seed=seed)
    engine, *_ = deepspeed_tpu.initialize(
        model=model, model_parameters=params, config=_cfg(mesh, stage, tp, **kw))
    return engine, cfg


def _train(engine, cfg, steps, seed, batch):
    rng = np.random.default_rng(seed)
    losses = []
    for _ in range(steps):
        ids = jnp.asarray(rng.integers(0, cfg.vocab_size, size=(batch, 16)),
                          dtype=jnp.int32)
        loss = engine.forward(ids, labels=ids)
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    return losses


def _leaf(tree, *path):
    for k in path:
        tree = tree[k]
    return tree


@pytest.mark.world_size(8)
def test_tp_placement_composes_with_zero3():
    engine, _ = _engine({"model": 2, "data": 2, "fsdp": 2}, stage=3,
                        tp={"enabled": True})
    assert engine._tp_training
    q = _leaf(engine.params, "model", "layers_0", "self_attn", "q_proj", "kernel")
    o = _leaf(engine.params, "model", "layers_0", "self_attn", "o_proj", "kernel")
    ln = _leaf(engine.params, "model", "layers_0", "input_layernorm", "weight")
    # column-parallel out-dim on model; ZeRO-3 takes the free in-dim
    assert tuple(q.sharding.spec) == ("fsdp", "model")
    # row-parallel in-dim on model; ZeRO-3 takes the free out-dim
    assert tuple(o.sharding.spec) == ("model", "fsdp")
    # per-device shard really is 1/4 of the leaf
    assert q.addressable_shards[0].data.shape == (q.shape[0] // 2, q.shape[1] // 2)
    # unmatched leaves degrade to the plain ZeRO rule
    assert tuple(ln.sharding.spec) in ((), (None,), ("fsdp",))
    # moments shard exactly like their weights (paths embed the param path)
    flat = jax.tree_util.tree_leaves_with_path(engine.opt_state)
    mu_q = [l for p, l in flat
            if "q_proj" in "/".join(str(getattr(k, "key", k)) for k in p)
            and "mu" in "/".join(str(getattr(k, "key", k)) for k in p)]
    assert mu_q and tuple(mu_q[0].sharding.spec) == ("fsdp", "model")


@pytest.mark.world_size(8)
def test_tp_stage0_shards_params_only():
    """TP applies at EVERY stage — that is its memory/compute point — while
    ZeRO keeps its stage gates (stage 0: no zero axes anywhere)."""
    engine, _ = _engine({"model": 2, "data": 4}, stage=0, tp={"enabled": True})
    q = _leaf(engine.params, "model", "layers_0", "self_attn", "q_proj", "kernel")
    assert tuple(q.sharding.spec) == (None, "model")


@pytest.mark.world_size(8)
def test_tp_size_creates_model_axis_and_batch_triangle_sees_it():
    """tensor_parallel.tp_size alone (no mesh key) must create the model
    axis AND be visible to the pre-mesh dp estimate, or the batch triangle
    validates against the wrong world."""
    engine, cfg = _engine({}, stage=1, tp={"tp_size": 2})
    assert dict(engine.mesh_ctx.mesh.shape)["model"] == 2
    assert engine.dp_world_size == 4
    assert engine.train_batch_size() == 2 * 4  # micro 2 x dp 4 x gas 1
    losses = _train(engine, cfg, 2, seed=3, batch=8)
    assert np.isfinite(losses).all()


@pytest.mark.world_size(8)
def test_tp2_matches_tp1_at_same_global_batch():
    """The TP=2 trajectory must match plain DP at the same global batch —
    TP reorders the contraction across devices, nothing else."""
    engine1, cfg = _engine({"data": 8}, stage=1, seed=7, micro=1)  # dp8 x mb1
    ref = _train(engine1, cfg, 3, seed=11, batch=8)

    engine2, cfg = _engine({"model": 2, "data": 4}, stage=1,
                           tp={"enabled": True}, seed=7, micro=2)
    got = _train(engine2, cfg, 3, seed=11, batch=8)
    # TP splits the contraction across devices: pure float reassociation,
    # amplified through layernorm/softmax — ~1e-4 relative is the observed
    # fp32 envelope. A semantic bug (double psum, missing reduce) diverges
    # at O(1) and still fails this.
    np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4)


@pytest.mark.world_size(8)
def test_tp_composes_with_ulysses_and_dp():
    """3-axis engine run: model x seq x data with tensor_parallel on —
    TP shards the weights, Ulysses shards the sequence, data shards the
    batch; the trajectory must match plain DP at the same global batch."""
    engine1, cfg = _engine({"data": 8}, stage=1, seed=13, micro=1)
    ref = _train(engine1, cfg, 2, seed=31, batch=8)

    engine2, cfg = _engine({"model": 2, "seq": 2, "data": 2}, stage=1,
                           tp={"enabled": True}, seed=13, micro=4)
    q = _leaf(engine2.params, "model", "layers_0", "self_attn", "q_proj", "kernel")
    assert "model" in tuple(q.sharding.spec)
    got = _train(engine2, cfg, 2, seed=31, batch=8)
    np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4)


@pytest.mark.world_size(8)
def test_tp_composes_with_moe_ep():
    """model x expert x data: attention TP-shards, experts stay OFF the
    model axis (the heuristics deliberately don't match expert w1/w2/w3 —
    EP is their parallelism), trajectory matches the non-TP MoE run.

    Compared in float32: there the eight meshes tried (data 8, expert 2 x
    data 4, model 2 x data 4, model 2 x expert 2 x data 2, each with the
    grouped and the dense-over-experts block, ZeRO 0 and 2) agree to 1e-6 on
    both steps, so TP x EP adds nothing but the order of its sums. In the
    model's default bfloat16 that order is visible: each partitioning rounds
    other partial sums to 8 bits, and this pair read [6.0804, 6.1519]
    against [6.0791, 6.1587] (2e-4 and 1.1e-3 apart; the same run on a
    data-only mesh reads 6.0871 at ZeRO 0 and 6.0807 at ZeRO 2), which is
    bfloat16's rounding and not a property of either axis. Top-k routing is
    discontinuous, so a near-tie token could still flip experts between two
    partitionings; seed=41 routes away from ties."""
    moe = dict(num_local_experts=4, num_experts_per_tok=2, dtype=jnp.float32)
    e1, cfg = _engine({"expert": 2, "data": 4}, stage=2, micro=2, seed=9,
                      cfg_over=moe)
    ref = _train(e1, cfg, 2, seed=41, batch=8)

    e2, cfg = _engine({"model": 2, "expert": 2, "data": 2}, stage=2, micro=4,
                      seed=9, tp={"enabled": True}, cfg_over=moe)
    q = _leaf(e2.params, "model", "layers_0", "self_attn", "q_proj", "kernel")
    assert "model" in tuple(q.sharding.spec)
    # the EP invariant this test exists to pin: expert weights never land
    # on the model axis
    w1 = _leaf(e2.params, "model", "layers_0", "block_sparse_moe", "w1")
    assert "model" not in tuple(w1.sharding.spec), w1.sharding.spec
    got = _train(e2, cfg, 2, seed=41, batch=8)
    np.testing.assert_allclose(got, ref, rtol=1e-5)


@pytest.mark.world_size(8)
def test_tp_checkpoint_resumes_across_tp_degrees(tmp_path):
    """Reference test_configurable_parallel_mp.py semantics: train at MP=2,
    save, resume at MP=1 (and 1 -> 2); training continues identically."""
    e1, cfg = _engine({"model": 2, "data": 4}, stage=1, tp={"enabled": True},
                      seed=5)
    _train(e1, cfg, 2, seed=21, batch=8)
    e1.save_checkpoint(tmp_path / "ck", tag="tp2")
    ref = _train(e1, cfg, 2, seed=22, batch=8)

    e2, cfg = _engine({"data": 8}, stage=2, seed=99, micro=1)  # fresh weights
    e2.load_checkpoint(str(tmp_path / "ck"), tag="tp2")
    got = _train(e2, cfg, 2, seed=22, batch=8)
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)

    # and back up: resume the plain run under TP=2 + ZeRO-3
    e2.save_checkpoint(tmp_path / "ck2", tag="tp1")
    ref2 = _train(e2, cfg, 1, seed=23, batch=8)
    e3, cfg = _engine({"model": 2, "data": 2, "fsdp": 2}, stage=3,
                      tp={"enabled": True}, seed=123)
    e3.load_checkpoint(str(tmp_path / "ck2"), tag="tp1")
    got2 = _train(e3, cfg, 1, seed=23, batch=8)
    np.testing.assert_allclose(got2, ref2, rtol=2e-4, atol=2e-5)


@pytest.mark.world_size(8)
def test_tp_via_logical_axes_metadata():
    """t5x-style logical-axis TP: a custom module whose param names the
    AutoTP regexes can't match still TP-shards when the user passes
    per-leaf logical names (LOGICAL_RULES: 'mlp' -> model axis) to
    initialize(logical_axes=...). Trajectory matches the non-TP run."""
    import flax.linen as nn

    class _Custom(nn.Module):
        width: int = 64

        @nn.compact
        def __call__(self, x, labels=None):
            win = self.param("alpha", nn.initializers.lecun_normal(), (16, self.width))
            wout = self.param("beta", nn.initializers.lecun_normal(), (self.width, 16))
            out = jnp.tanh(x @ win) @ wout
            if labels is None:
                return out
            return ((out - labels) ** 2).mean()

    # names chosen to NOT match the AutoTP regexes ("win" would —
    # it contains "wi", the T5 spelling)
    logical = {"alpha": ("embed", "mlp"), "beta": ("mlp", "embed")}

    def build(mesh, tp, micro, logical_axes=None):
        reset_mesh_context()
        model = _Custom()
        params = model.init(jax.random.PRNGKey(2), jnp.ones((1, 16)))["params"]
        c = {"train_micro_batch_size_per_gpu": micro,
             "optimizer": {"type": "AdamW", "params": {"lr": 1e-2}},
             "zero_optimization": {"stage": 1},
             "mesh": mesh, "steps_per_print": 0}
        if tp:
            c["tensor_parallel"] = {"enabled": True}
        engine, *_ = deepspeed_tpu.initialize(
            model=model, model_parameters=params, config=c,
            logical_axes=logical_axes)
        return engine

    def train(engine, steps, seed):
        rng = np.random.default_rng(seed)
        losses = []
        for _ in range(steps):
            x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)
            loss = engine.forward(x, labels=x)
            engine.backward(loss)
            engine.step()
            losses.append(float(loss))
        return losses

    ref = train(build({"data": 8}, tp=False, micro=1), 3, seed=17)

    eng = build({"model": 2, "data": 4}, tp=True, micro=2,
                logical_axes=logical)
    win = eng.params["alpha"]
    wout = eng.params["beta"]
    assert tuple(win.sharding.spec) == (None, "model"), win.sharding.spec
    assert tuple(wout.sharding.spec) == ("model", None), wout.sharding.spec
    # moments follow their weights via LONGEST-SUFFIX lookup of the logical
    # tree in the optimizer state's paths (no regex can match 'alpha')
    mu_specs = [tuple(l.sharding.spec)
                for p, l in jax.tree_util.tree_leaves_with_path(eng.opt_state)
                if "alpha" in "/".join(str(getattr(k, "key", k)) for k in p)]
    assert mu_specs and all("model" in sp for sp in mu_specs), mu_specs
    got = train(eng, 3, seed=17)
    np.testing.assert_allclose(got, ref, rtol=1e-3, atol=1e-4)

    # WITHOUT metadata the same model stays replicated over model (the
    # regexes don't match 'win'/'wout') — the metadata is what engages TP
    eng2 = build({"model": 2, "data": 4}, tp=True, micro=2)
    assert "model" not in tuple(eng2.params["alpha"].sharding.spec)
