"""Granite 4.0-H (the dense hybrid) through the normal path against the plain
float32 reference (``benchmark/reference/granite_hybrid.py``, which imports
nothing from the program) at a small size on seeded weights, all on the CPU
with the kernels interpreted: loss, logits and gradients leaf by leaf; the
reference made wrong in the ways the cell's limits have to tell; ``scan_layers``
(refused for the period's unlike layers, the unrolled model over Mamba layers
all alike); ``GraniteMoeHybridPolicy``'s
config, refusals and weight-name round trip; what the engine publishes.

Tolerances. The program in float32 differs from the reference by the order
of its sums (chunks of 32 tokens against one token at a time, flash blocks
against a whole softmax): loss 2e-6, logits 2e-5, gradients 2e-4 a leaf
(``A_log`` and ``dt_bias`` are sums of thousands of terms of either sign that
largely cancel, so their relative error is the largest). In bf16 the limits
are the rehearsal's: a few bf16 steps over four layers."""

import dataclasses
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import deepspeed_tpu  # noqa: E402
from benchmark import granite_cost  # noqa: E402
from benchmark.reference import granite_hybrid as reference  # noqa: E402
from deepspeed_tpu.comm.mesh import MeshContext, set_mesh_context  # noqa: E402
from deepspeed_tpu.models.llama import LayerSpec, LlamaConfig, init_llama  # noqa: E402
from deepspeed_tpu.module_inject.replace_module import (  # noqa: E402
    convert_hf_checkpoint, export_hf_checkpoint)
from deepspeed_tpu.module_inject.replace_policy import (GraniteMoeHybridPolicy,  # noqa: E402
                                                         Lfm2MoePolicy, policy_for)

# config.json of ibm-granite/granite-4.0-h-micro as the catalog has it
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": PERIOD + (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 3,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352}
# the same architecture small: 4 heads of 16 over 2 KV heads, 8 Mamba heads of
# 16 with a state of 32, chunks of 32, two Mamba layers, attention, one more
SMALL = dict(PUBLISHED, hidden_size=64, intermediate_size=96, shared_intermediate_size=96,
             num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=8,
             mamba_d_head=16, mamba_d_state=32, mamba_chunk_size=32,
             attention_multiplier=0.0625, num_hidden_layers=4,
             layer_types=["mamba", "mamba", "attention", "mamba"], vocab_size=256,
             max_position_embeddings=256)
SEQ = 80        # two chunks and a half: the last one padded


def small(dtype=jnp.float32, seed=5, **over):
    cfg = dataclasses.replace(GraniteMoeHybridPolicy().config_from_hf(dict(SMALL, **over)),
                              dtype=dtype)
    model, params = init_llama(cfg, seed=seed)
    ids = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (2, SEQ), dtype=np.int32))
    return cfg, model, params, ids


def rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def test_config_from_the_published_dictionary():
    cfg = GraniteMoeHybridPolicy().config_from_hf(PUBLISHED)
    assert isinstance(policy_for("granitemoehybrid"), GraniteMoeHybridPolicy)
    assert isinstance(policy_for("GraniteMoeHybridForCausalLM"), GraniteMoeHybridPolicy)
    assert (cfg.hidden_size, cfg.num_hidden_layers, cfg.vocab_size) == (2048, 40, 100352)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim_) == (32, 8, 64)
    assert cfg.pos_embedding == "none" and cfg.tie_word_embeddings
    assert (cfg.embed_scale, cfg.residual_multiplier, cfg.attn_scale, cfg.logit_scale) \
        == (12.0, 0.22, 1 / 64, 1 / 8)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state, cfg.mamba_n_groups,
            cfg.mamba_chunk_size, cfg.mamba_d_conv, cfg.mamba_conv_bias) \
        == (64, 64, 128, 1, 256, 4, True)
    kinds = [s.operator for s in cfg.layer_specs]
    assert kinds == PUBLISHED["layer_types"] and kinds.count("attention") == 4
    assert all(s.ffn == "dense" and s.ffn_width == 8192 for s in cfg.layer_specs)
    assert cfg.num_local_experts == 0
    # 76.19M a Mamba layer, 60.82M an attention layer, as ISSUE 33 counts them
    assert cfg.per_layer_elements() == 76_182_976
    attention = dataclasses.replace(cfg, layer_specs=(cfg.layer_specs[5], ))
    assert attention.per_layer_elements() == 60_821_504


@pytest.mark.parametrize("key,value", [
    ("num_local_experts", 72), ("mamba_n_groups", 8),
    ("position_embedding_type", "rope"), ("normalization_function", "layernorm"),
    ("mamba_proj_bias", True)])
def test_the_policy_refuses_by_name_what_is_not_built(key, value):
    with pytest.raises(ValueError, match=f"granitemoehybrid: {key}="):
        GraniteMoeHybridPolicy().config_from_hf(dict(PUBLISHED, **{key: value}))


def test_the_policy_refuses_layer_types_it_does_not_know():
    with pytest.raises(ValueError, match="layer_types"):
        GraniteMoeHybridPolicy().config_from_hf(
            dict(SMALL, layer_types=["mamba", "conv", "attention", "mamba"]))
    with pytest.raises(ValueError, match="mamba_expand"):
        GraniteMoeHybridPolicy().config_from_hf(dict(SMALL, mamba_n_heads=4))


def test_weight_map_round_trip_from_hf_named_tensors():
    cfg, _, params, _ = small()
    hf = export_hf_checkpoint("granitemoehybrid", cfg, params)
    shapes = {"model.embed_tokens.weight": (256, 64), "model.norm.weight": (64, ),
              "model.layers.0.input_layernorm.weight": (64, ),
              "model.layers.3.post_attention_layernorm.weight": (64, ),
              "model.layers.0.mamba.in_proj.weight": (128 + 192 + 8, 64),
              "model.layers.0.mamba.out_proj.weight": (64, 128),
              "model.layers.0.mamba.conv1d.weight": (192, 1, 4),   # torch Conv1d, depthwise
              "model.layers.0.mamba.conv1d.bias": (192, ),
              "model.layers.1.mamba.dt_bias": (8, ), "model.layers.1.mamba.A_log": (8, ),
              "model.layers.1.mamba.D": (8, ), "model.layers.3.mamba.norm.weight": (128, ),
              "model.layers.0.shared_mlp.input_linear.weight": (192, 64),
              "model.layers.0.shared_mlp.output_linear.weight": (64, 96),
              "model.layers.2.self_attn.q_proj.weight": (64, 64),
              "model.layers.2.self_attn.k_proj.weight": (32, 64),
              "model.layers.2.self_attn.o_proj.weight": (64, 64)}
    for name, shape in shapes.items():
        assert hf[name].shape == shape, name
    # embedding and final norm; two norms and two FFN tensors a layer; three
    # Mamba mixers of 8 tensors; one attention of 4
    assert len(hf) == 2 + 4 * 4 + 3 * 8 + 4
    assert not any("lm_head" in k or ".mlp." in k for k in hf)
    taps = params["model"]["layers_1"]["mamba"]["conv_weight"]      # [taps, channels]
    np.testing.assert_array_equal(hf["model.layers.1.mamba.conv1d.weight"][:, 0, :],
                                  np.asarray(taps).T)
    gate = params["model"]["layers_2"]["mlp"]["gate_proj"]["kernel"]
    np.testing.assert_array_equal(
        hf["model.layers.2.shared_mlp.input_linear.weight"][:96], np.asarray(gate).T)
    cfg2, back = convert_hf_checkpoint("granitemoehybrid", hf, dict(SMALL))
    assert cfg2 == dataclasses.replace(cfg, dtype=cfg2.dtype)
    flat = jax.tree_util.tree_leaves_with_path(params)
    flat_back = dict(jax.tree_util.tree_leaves_with_path(back))
    assert len(flat) == len(flat_back)
    for path, leaf in flat:
        np.testing.assert_array_equal(np.asarray(leaf), flat_back[path])
    with pytest.raises(KeyError, match="conv1d.weight"):
        hf.pop("model.layers.0.mamba.conv1d.weight")
        convert_hf_checkpoint("granitemoehybrid", hf, dict(SMALL))


def test_parameters_of_the_built_model_are_the_cost_files_count():
    cfg, _, params, _ = small()
    built = sum(leaf.size for leaf in jax.tree_util.tree_leaves(params))
    assert built == granite_cost.param_count(SMALL)
    # the seeded Mamba-2 parameters are the ones `assumed` states
    mamba = params["model"]["layers_0"]["mamba"]
    np.testing.assert_allclose(np.exp(np.asarray(mamba["A_log"])), np.arange(1, 9), rtol=1e-6)
    dt = np.log1p(np.exp(np.asarray(mamba["dt_bias"])))
    assert 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001
    assert np.all(np.asarray(mamba["D"]) == 1) and np.abs(mamba["conv_bias"]).max() <= 0.5
    assert np.abs(mamba["conv_bias"]).max() > 0.1


@pytest.fixture(scope="module")
def step():
    """The program in float32 (kernels interpreted) and the reference on the
    same weights and batch."""
    cfg, model, params, ids = small()
    positions = np.arange(SEQ)
    want = reference.step_parts(params, ids, SMALL, positions)
    loss, grads = jax.value_and_grad(
        lambda p: model.apply({"params": p}, ids, labels=ids))(params)
    logits, mods = model.apply({"params": params}, ids, mutable=["ssm_stats"])
    return cfg, params, ids, want, float(loss), grads, np.asarray(logits), mods["ssm_stats"]


def test_float32_program_matches_the_reference(step):
    cfg, params, ids, want, loss, grads, logits, sown = step
    assert loss == pytest.approx(want["ce"], rel=2e-6)
    assert rel(logits, want["logits"]) < 2e-5
    tops = [float(v["mamba"]["state_absmax"]) for v in sown["model"].values()]
    assert max(tops) == pytest.approx(want["state_absmax_chunks"], rel=1e-5)
    assert want["state_absmax"] >= want["state_absmax_chunks"]
    dts = [float(v["mamba"]["dt_mean"]) for v in sown["model"].values()]
    assert len(dts) == 3 and np.mean(dts) == pytest.approx(want["dt_mean"], rel=1e-5)


LEAVES = sorted(jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_leaves_with_path(
    jax.eval_shape(lambda: small()[2])))


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradients_match_jax_grad_of_the_reference_leaf_by_leaf(step, leaf):
    grads, want = step[5], step[3]["grads"]
    got = dict((jax.tree_util.keystr(p), g) for p, g in
               jax.tree_util.tree_leaves_with_path(grads))[leaf]
    ref = dict((jax.tree_util.keystr(p), g) for p, g in
               jax.tree_util.tree_leaves_with_path(want))[leaf]
    assert got.shape == ref.shape and rel(got, ref) < 2e-4


@pytest.mark.parametrize("wrong,least", [
    ("no_softplus", 1e-1), ("no_residual_multiplier", 1e-1), ("rope", 3e-3),
    ("no_carry", 3e-3), ("bf16_decay", 3e-4), ("bf16_state", 3e-4)])
def test_a_reference_made_wrong_is_told_from_the_program(step, wrong, least):
    """What the calibration leans on: each wrong way moves the logits by far
    more than the 2e-5 the sound program differs by."""
    cfg, params, ids, want, _, _, logits, _ = step
    bad = reference.step_parts(params, ids, SMALL, np.arange(SEQ), wrong={wrong},
                               gradients=False)
    assert not rel(logits, bad["logits"]) < least


def test_bf16_compute_stays_near_the_reference(step):
    want = step[3]
    cfg, model, params, ids = small(dtype=jnp.bfloat16)
    loss = float(model.apply({"params": params}, ids, labels=ids))
    logits = np.asarray(model.apply({"params": params}, ids))
    assert loss == pytest.approx(want["ce"], rel=1e-3)
    err = (np.linalg.norm(logits - want["logits"], axis=-1)
           / np.linalg.norm(want["logits"], axis=-1))
    assert np.median(err) < 3e-2 and err.max() < 1e-1


def test_scan_layers_refuses_the_periods_unlike_layers():
    """ROADMAP R9a: a scan over runs of equal layers is not built; the cell
    runs its ten layers unrolled."""
    cfg = small()[0]
    with pytest.raises(ValueError, match="layer_specs of 2 kinds"):
        init_llama(dataclasses.replace(cfg, scan_layers=True), seed=0)


def _mamba_layers_alike(scan):
    return dataclasses.replace(
        GraniteMoeHybridPolicy().config_from_hf(dict(
            SMALL, num_hidden_layers=3, layer_types=["mamba"] * 3)),
        dtype=jnp.float32, scan_layers=scan)


def _stacked(params):
    """A per-layer tree as the one scan over all layers holds it."""
    layers = [params["model"][f"layers_{i}"] for i in range(3)]
    model = {k: v for k, v in params["model"].items() if not k.startswith("layers_")}
    model["layers"] = {"layer": jax.tree_util.tree_map(lambda *x: jnp.stack(x), *layers)}
    return {**params, "model": model}


def test_scan_over_mamba_layers_all_alike_is_the_unrolled_model():
    cfg = _mamba_layers_alike(False)
    model, params = init_llama(cfg, seed=3)
    scan_model, born = init_llama(_mamba_layers_alike(True), seed=3)
    stacked = _stacked(params)
    assert jax.tree_util.tree_structure(born) == jax.tree_util.tree_structure(stacked)
    ids = jnp.asarray(np.random.default_rng(3).integers(0, cfg.vocab_size, (2, 40),
                                                        dtype=np.int32))

    def loss(model, p):
        return model.apply({"params": p}, ids, labels=ids, mutable=["ssm_stats"])

    (want, sown), want_grads = jax.value_and_grad(
        lambda p: loss(model, p), has_aux=True)(params)
    (got, sown_scan), grads = jax.value_and_grad(
        lambda p: loss(scan_model, p), has_aux=True)(stacked)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(_stacked(want_grads))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6)
    # what the layers sowed rides the scan stacked: the same numbers
    total = lambda tree: sum(float(jnp.sum(x)) for x in jax.tree_util.tree_leaves(tree))  # noqa: E731
    assert total(sown_scan) == pytest.approx(total(sown), rel=1e-5)


@pytest.mark.parametrize("scan", [False, True])
def test_the_engine_publishes_the_state_and_the_layers_by_kind(scan):
    from deepspeed_tpu.comm import reset_mesh_context
    from deepspeed_tpu.observability import get_registry
    from deepspeed_tpu.models.llama import LlamaForCausalLM
    if scan:    # only layers all alike scan: three Mamba layers
        hf = dict(SMALL, num_hidden_layers=3, layer_types=["mamba"] * 3)
        cfg = dataclasses.replace(_mamba_layers_alike(False), remat=True)
        _, per_layer = init_llama(cfg, seed=7)
        cfg, params = dataclasses.replace(cfg, scan_layers=True), _stacked(per_layer)
        ids = small(seed=7)[3]
        kinds_want = {"mamba+dense": 3.0}
    else:
        hf = SMALL
        cfg, _, params, ids = small(seed=7)
        cfg, per_layer = dataclasses.replace(cfg, remat=True), params
        kinds_want = {"mamba+dense": 3.0, "attention+dense": 1.0}
    reset_mesh_context()
    set_mesh_context(MeshContext.create(devices=jax.devices()[:1]))
    reg = get_registry()
    reg.reset()     # the registry is the process's: zero what other engines set
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=LlamaForCausalLM(cfg), model_parameters=params,
        config={"train_batch_size": 2, "steps_per_print": 0,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
    kinds = {m.labels["kind"]: m.value for m in reg.series("ds_model_layers") if m.value}
    assert kinds == kinds_want
    want = reference.step_parts(per_layer, ids, hf, [0], gradients=False)
    loss = float(engine.train_batch(iter([(ids, ids)])))
    assert loss == pytest.approx(want["ce"], rel=2e-6)
    stats = engine.ssm_stats()
    assert set(stats) == {"state_absmax", "dt_mean"} and engine.moe_stats() is None
    assert float(stats["state_absmax"]) == pytest.approx(want["state_absmax_chunks"],
                                                         rel=1e-5)
    assert float(stats["dt_mean"]) == pytest.approx(want["dt_mean"], rel=1e-5)
    engine.train_batch(iter([(ids, ids)]))      # publishes the step before
    assert reg.get("ds_ssm_state_absmax").value == pytest.approx(
        float(stats["state_absmax"]))
    assert reg.get("ds_ssm_dt_mean").value == pytest.approx(float(stats["dt_mean"]))
    assert engine._train_step_fused._cache_size() == 1
    reset_mesh_context()


def test_a_model_without_a_state_space_layer_has_no_ssm_stats():
    from deepspeed_tpu.comm import reset_mesh_context
    cfg = LlamaConfig.tiny(dtype=jnp.float32)
    model, params = init_llama(cfg, seed=0)
    reset_mesh_context()
    set_mesh_context(MeshContext.create(devices=jax.devices()[:1]))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_batch_size": 2, "steps_per_print": 0,
                "optimizer": {"type": "SGD", "params": {"lr": 1e-3}}})
    ids = jnp.ones((2, 16), jnp.int32)
    engine.train_batch(iter([(ids, ids)]))
    assert engine.ssm_stats() is None and engine.moe_stats() is None
    assert LayerSpec("mamba").operator == "mamba"
    reset_mesh_context()
