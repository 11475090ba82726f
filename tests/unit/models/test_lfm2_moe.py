"""LFM2-MoE through the normal path against the plain float32 reference
(``benchmark/reference/lfm2_moe.py``, which imports nothing from the program)
at a small size on seeded weights with a non-zero selection bias, all on the
CPU: the loss, logits, gradients and routing counts of a chip's share; the
eight shares of one expert layer adding up to the uncut layer; the router by
hand; per-head q/k norm; ``Lfm2MoePolicy``'s config and weight-name map.
"""

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
from benchmark.reference import lfm2_moe as reference  # noqa: E402
from benchmark.runners.train_steps_lfm2_moe import seed_selection_bias  # noqa: E402
from deepspeed_tpu.comm.mesh import MeshContext, set_mesh_context  # noqa: E402
from deepspeed_tpu.models.llama import (LayerSpec, LlamaAttention, LlamaConfig,  # noqa: E402
                                        LlamaForCausalLM, LlamaMoEBlock, init_llama,
                                        unbox_params)
from deepspeed_tpu.module_inject.replace_module import (  # noqa: E402
    convert_hf_checkpoint, export_hf_checkpoint)
from deepspeed_tpu.module_inject.replace_policy import (Lfm2MoePolicy,  # noqa: E402
                                                         policy_for)

# config.json of LiquidAI/LFM2-24B-A2B as the catalog has it
PERIOD = ["conv", "conv", "full_attention", "conv"]
PUBLISHED = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
             "intermediate_size": 11776, "layer_types": PERIOD * 10,
             "max_position_embeddings": 128000, "model_type": "lfm2_moe",
             "moe_intermediate_size": 1536, "norm_eps": 1e-05,
             "norm_topk_prob": True, "num_attention_heads": 32,
             "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
             "num_hidden_layers": 40, "num_key_value_heads": 8,
             "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
             "routed_scaling_factor": 1, "use_expert_bias": True,
             "vocab_size": 65536}
# the same architecture small: a dense layer and one period, 16 experts, 4
# heads of 16 over 2 KV heads
SMALL = dict(PUBLISHED, hidden_size=64, intermediate_size=160,
             moe_intermediate_size=32, num_attention_heads=4,
             num_key_value_heads=2, num_experts=16, num_hidden_layers=5,
             layer_types=["conv", "full_attention", "conv", "conv", "conv"],
             num_dense_layers=1, vocab_size=256, max_position_embeddings=128)
HELD = 2            # of 16: eight shares, as the cell's 8 of 64


def small(held=HELD, share=0, dtype=jnp.float32, seed=5, **over):
    cfg = dataclasses.replace(
        Lfm2MoePolicy().config_from_hf(dict(SMALL, **over)), dtype=dtype,
        attn_impl="xla", moe_experts_held=held, moe_share_index=share)
    model, params = init_llama(cfg, seed=seed)
    params = seed_selection_bias(params, seed, 0.05)
    ids = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (2, 40), dtype=np.int32))   # 40: no block's multiple
    return cfg, model, params, ids


def program(model, params, ids, labels=None):
    out, mods = model.apply({"params": params}, ids, labels, mutable=["moe_stats"])
    stats = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(mods["moe_stats"])[0]:
        stats[path[-1].key] = stats.get(path[-1].key, 0) + leaf
    return out, stats


def test_config_from_the_published_dictionary():
    cfg = Lfm2MoePolicy().config_from_hf(PUBLISHED)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size) == (2048, 11776, 65536)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim_) == (32, 8, 64)
    assert cfg.rope_theta == 1_000_000 and cfg.rms_norm_eps == 1e-5
    assert cfg.tie_word_embeddings and cfg.qk_norm == "head" and cfg.conv_L_cache == 3
    assert (cfg.num_local_experts, cfg.num_experts_per_tok) == (64, 4)
    assert cfg.moe_scoring == "sigmoid" and cfg.moe_selection_bias
    assert cfg.moe_renormalize and cfg.moe_renorm_eps == 1e-6
    assert cfg.routed_scaling_factor == 1.0 and cfg.router_aux_loss_coef == 0.0
    assert cfg.moe_experts_held is None and cfg.experts_held_ == 64
    specs = cfg.layer_specs
    assert len(specs) == 40 and sum(s.operator == "attention" for s in specs) == 10
    assert [i for i, s in enumerate(specs) if s.operator == "attention"] \
        == list(range(2, 40, 4))
    assert specs[0] == specs[1] == LayerSpec("conv", "dense", 11776)
    assert specs[2] == LayerSpec("attention", "moe", 1536)
    assert all(s == LayerSpec("conv", "moe", 1536) for s in specs[3:6])
    # the largest layer: a conv operator (16.8M) with 64 experts (603.9M),
    # the router and two norms; with 8 held (75.5M) it still is: 92.4M
    # against the dense layer's 89.1M
    conv = 4 * 2048 * 2048 + 3 * 2048
    assert cfg.per_layer_elements() \
        == conv + 64 * 3 * 2048 * 1536 + 2048 * 64 + 2 * 2048
    share = dataclasses.replace(cfg, moe_experts_held=8)
    assert share.per_layer_elements() \
        == conv + 8 * 3 * 2048 * 1536 + 2048 * 64 + 2 * 2048 == 92_416_000
    assert conv + 3 * 2048 * 11776 + 2 * 2048 == 89_139_200
    assert isinstance(policy_for("lfm2_moe"), Lfm2MoePolicy)
    assert isinstance(policy_for("Lfm2MoeForCausalLM"), Lfm2MoePolicy)
    for bad in (dict(conv_bias=True), dict(layer_types=PERIOD),
                dict(layer_types=["sliding_attention"] * 40),
                dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"})):
        with pytest.raises(ValueError):
            Lfm2MoePolicy().config_from_hf(dict(PUBLISHED, **bad))


def test_per_layer_elements_without_a_spec_is_unchanged():
    assert LlamaConfig.tiny().per_layer_elements() \
        == 64 * 64 * 2 + 64 * 32 * 2 + 3 * 64 * 128 + 2 * 64
    moe = LlamaConfig.tiny(num_local_experts=4)
    assert moe.per_layer_elements() \
        == 64 * 64 * 2 + 64 * 32 * 2 + 4 * 3 * 64 * 128 + 64 * 4 + 2 * 64


def test_scan_layers_refuses_layers_of_several_kinds():
    cfg, _, params, ids = small()
    with pytest.raises(ValueError, match="layer_specs of 3 kinds"):
        init_llama(dataclasses.replace(cfg, scan_layers=True), seed=0)
    alike = dataclasses.replace(cfg, scan_layers=True, num_hidden_layers=2,
                                layer_specs=(LayerSpec("conv", "moe", 32), ) * 2)
    model, stacked = init_llama(alike, seed=0)
    assert stacked["model"]["layers"]["layer"]["conv"]["conv_weight"].shape == (2, 3, 64)
    assert np.isfinite(np.asarray(model.apply({"params": stacked}, ids))).all()


def test_weight_map_round_trip_from_hf_named_tensors():
    cfg, _, params, _ = small(held=None)
    hf = export_hf_checkpoint("lfm2_moe", cfg, params)
    shapes = {"model.embed_tokens.weight": (256, 64),
              "model.embedding_norm.weight": (64, ),
              "model.layers.0.operator_norm.weight": (64, ),
              "model.layers.4.ffn_norm.weight": (64, ),
              "model.layers.0.conv.in_proj.weight": (192, 64),
              "model.layers.0.conv.out_proj.weight": (64, 64),
              "model.layers.0.conv.conv.weight": (64, 1, 3),     # torch Conv1d, depthwise
              "model.layers.0.feed_forward.w1.weight": (160, 64),
              "model.layers.0.feed_forward.w2.weight": (64, 160),
              "model.layers.1.self_attn.q_proj.weight": (64, 64),
              "model.layers.1.self_attn.k_proj.weight": (32, 64),
              "model.layers.1.self_attn.out_proj.weight": (64, 64),
              "model.layers.1.self_attn.q_layernorm.weight": (16, ),
              "model.layers.1.self_attn.k_layernorm.weight": (16, ),
              "model.layers.1.feed_forward.gate.weight": (16, 64),
              "model.layers.1.feed_forward.expert_bias": (16, ),
              "model.layers.1.feed_forward.experts.15.w1.weight": (32, 64),
              "model.layers.4.feed_forward.experts.0.w2.weight": (64, 32)}
    for name, shape in shapes.items():
        assert hf[name].shape == shape, name
    # embedding and final norm; two norms a layer; 4 conv operators of 3, one
    # attention of 6; the dense FFN's 3; 4 routers with bias and 16 experts of 3
    assert len(hf) == 2 + 2 * 5 + 4 * 3 + 6 + 3 + 4 * (2 + 3 * 16)    # nothing else
    assert not any("lm_head" in k or "block_sparse_moe" in k or ".mlp." in k
                   or "self_attn.o_proj" in k for k in hf)
    tap = params["model"]["layers_2"]["conv"]["conv_weight"]     # [taps, channels]
    np.testing.assert_array_equal(hf["model.layers.2.conv.conv.weight"][:, 0, :],
                                  np.asarray(tap).T)
    cfg2, back = convert_hf_checkpoint("lfm2_moe", hf, dict(SMALL))
    assert cfg2 == dataclasses.replace(cfg, dtype=cfg2.dtype, attn_impl=cfg2.attn_impl)
    flat = jax.tree_util.tree_leaves_with_path(params)
    flat_back = dict(jax.tree_util.tree_leaves_with_path(back))
    assert len(flat) == len(flat_back)
    for path, leaf in flat:
        np.testing.assert_array_equal(np.asarray(leaf), flat_back[path])
    with pytest.raises(KeyError, match="conv.conv.weight"):
        hf.pop("model.layers.0.conv.conv.weight")
        convert_hf_checkpoint("lfm2_moe", hf, dict(SMALL))


def test_float32_program_matches_the_reference_for_its_share():
    cfg, model, params, ids = small()
    got, _ = program(model, params, ids)
    want, margin = reference.logits_and_margin(params, ids, SMALL)
    assert np.abs(np.asarray(want)).max() > 0.3 and float(margin.min()) >= 0
    # float32 on both sides: what is left is the order of the sums
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=2e-5)
    loss, stats = program(model, params, ids, ids)
    parts = reference.loss_parts(params, ids, SMALL)
    assert float(loss) == pytest.approx(float(parts["ce"]), rel=2e-6)
    # counts over the router's 16 experts, four expert layers: none dropped
    np.testing.assert_array_equal(np.asarray(stats["expert_counts"]),
                                  np.asarray(parts["counts"]))
    assert stats["expert_counts"].shape == (16, )
    assert int(stats["expert_counts"].sum()) == ids.size * 4 * 4
    assert int(stats["rows_held"]) == int(parts["rows_held"]) \
        == int(stats["expert_counts"][:HELD].sum())
    assert int(stats["share_fallback"]) == 0


def test_a_second_share_matches_the_reference_given_the_same_share():
    cfg, model, params, ids = small(share=3)
    got, stats = program(model, params, ids)
    want, _ = reference.logits_and_margin(params, ids, SMALL, first_expert=3 * HELD)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=2e-5)
    assert int(stats["rows_held"]) \
        == int(stats["expert_counts"][3 * HELD:4 * HELD].sum())
    first, _ = reference.logits_and_margin(params, ids, SMALL)
    assert np.abs(np.asarray(first) - np.asarray(want)).max() > 1e-2


def _layer(tree, i):
    return tree["model"][f"layers_{i}"]


def _groups(tree):
    """Gradient leaves by parameter group."""
    moe, attn = _layer(tree, 3)["block_sparse_moe"], _layer(tree, 1)["self_attn"]
    conv, mlp = _layer(tree, 2)["conv"], _layer(tree, 0)["mlp"]
    return {"embedding and head": tree["model"]["embed_tokens"]["embedding"],
            "final norm": tree["model"]["norm"]["weight"],
            "layer norms": jnp.stack([_layer(tree, 2)["operator_norm"]["weight"],
                                      _layer(tree, 2)["ffn_norm"]["weight"]]),
            "q/k norms": jnp.stack([attn["q_norm"]["weight"], attn["k_norm"]["weight"]]),
            "attention": jnp.concatenate([attn[p]["kernel"].ravel() for p in
                                          ("q_proj", "k_proj", "v_proj", "o_proj")]),
            "conv projections": jnp.concatenate([conv["in_proj"]["kernel"].ravel(),
                                                 conv["out_proj"]["kernel"].ravel()]),
            "conv taps": conv["conv_weight"],
            "first conv": _layer(tree, 0)["conv"]["in_proj"]["kernel"],
            "dense ffn": jnp.concatenate([mlp[p]["kernel"].ravel() for p in
                                          ("gate_proj", "up_proj", "down_proj")]),
            "router": moe["gate"]["kernel"],
            "experts in": jnp.stack([moe["w1"], moe["w3"]]),
            "experts out": moe["w2"]}


GROUPS = ("embedding and head", "final norm", "layer norms", "q/k norms",
          "attention", "conv projections", "conv taps", "first conv",
          "dense ffn", "router", "experts in", "experts out")


@pytest.fixture(scope="module")
def gradients():
    """One step's gradients through ``deepspeed_tpu.initialize`` (the fused
    step's own backward, float32) and ``jax.grad`` of the reference."""
    cfg, model, params, ids = small()
    set_mesh_context(MeshContext.create(devices=jax.devices()[:1]))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_batch_size": 2, "steps_per_print": 0,
                "optimizer": {"type": "SGD", "params": {"lr": 1.0}}})
    before = jax.tree_util.tree_map(np.asarray, engine.params)
    loss = float(engine.train_batch(iter([(ids, ids)])))
    stats = engine.moe_stats()
    # plain SGD at lr 1: the parameters moved by minus the gradient
    got = jax.tree_util.tree_map(lambda a, b: a - np.asarray(b), before, engine.params)
    want = jax.grad(lambda p: reference.cross_entropy(p, ids, SMALL))(params)
    return got, want, loss, stats, float(reference.cross_entropy(params, ids, SMALL))


def test_the_engines_step_returns_the_reference_loss_and_counts(gradients):
    got, want, loss, stats, ref_loss = gradients
    assert loss == pytest.approx(ref_loss, rel=2e-6)
    assert stats["expert_counts"].shape == (16, )
    assert int(stats["expert_counts"].sum()) == 80 * 4 * 4
    assert int(stats["rows_held"]) == int(stats["expert_counts"][:HELD].sum()) > 0
    assert int(stats["share_fallback"]) == 0
    # the selection bias is a buffer: no gradient, the step leaves it alone
    for i in (1, 2, 3, 4):
        assert not np.asarray(_layer(got, i)["block_sparse_moe"]["expert_bias"]).any()
        assert not np.asarray(_layer(want, i)["block_sparse_moe"]["expert_bias"]).any()


@pytest.mark.parametrize("group", GROUPS)
def test_gradients_of_one_step_match_jax_grad_of_the_reference(gradients, group):
    got, want = (np.asarray(_groups(g)[group]) for g in gradients[:2])
    assert np.abs(want).max() > 0
    # float32 on both sides; relative to the group's largest gradient, the
    # order of the sums leaves 1e-6 to 1e-5
    assert np.abs(got - want).max() <= 5e-5 * np.abs(want).max()


def test_step_parts_is_the_loss_its_gradient_and_the_last_logits_in_one_pass():
    """What the cell's runner asks of the reference at the timed sizes: one
    compiled pass a sequence, gradients summed on the host."""
    _, _, params, ids = small()
    parts = reference.step_parts(params, ids, SMALL, last=7)
    plain = reference.loss_parts(params, ids, SMALL)
    assert parts["ce"] == pytest.approx(float(plain["ce"]), rel=1e-6)
    np.testing.assert_array_equal(parts["counts"], np.asarray(plain["counts"]))
    assert parts["rows_held"] == int(plain["rows_held"])
    logits, margin = reference.logits_and_margin(params, ids, SMALL, last=7)
    np.testing.assert_allclose(parts["logits"], np.asarray(logits), rtol=0, atol=1e-5)
    np.testing.assert_allclose(parts["margin"], np.asarray(margin), rtol=0, atol=1e-5)
    want = jax.grad(lambda p: reference.cross_entropy(p, ids, SMALL))(params)
    for (path, got), leaf in zip(jax.tree_util.tree_leaves_with_path(parts["grads"]),
                                 jax.tree_util.tree_leaves(want)):
        assert isinstance(got, np.ndarray), path
        np.testing.assert_allclose(got, np.asarray(leaf), rtol=0,
                                   atol=2e-5 * float(np.abs(leaf).max()) + 1e-12)
    assert reference.step_parts(params, ids, SMALL, last=7,
                                gradients=False)["grads"] is None


def test_the_reference_can_be_the_router_that_weights_by_the_biased_score():
    """The wrong router the cell has to tell from the published one: same
    choice, weights from ``s + bias``."""
    _, _, params, _ = small()
    moe = _layer(params, 3)["block_sparse_moe"]
    h = jnp.asarray(np.random.default_rng(5).normal(size=(40, 64)), jnp.float32)
    chosen, p, _ = reference.route(h, moe, 4, True, 1.0)
    same, biased, _ = reference.route(h, moe, 4, True, 1.0, weigh_biased=True)
    np.testing.assert_array_equal(np.asarray(chosen), np.asarray(same))
    s = jax.nn.sigmoid(h @ moe["gate"]["kernel"]) + moe["expert_bias"]
    picked = np.take_along_axis(np.asarray(s), np.asarray(chosen), axis=1)
    np.testing.assert_allclose(np.asarray(biased),
                               picked / (picked.sum(1, keepdims=True) + 1e-6), rtol=1e-5)
    assert np.abs(np.asarray(biased) - np.asarray(p)).max() > 1e-3


def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """What each chip's block gives for its experts, added over the eight
    chips that share the layer, is the uncut reference's layer: the same
    router (every chip holds it alike), every expert counted once."""
    cfg, _, params, ids = small(held=None)           # all 16 experts' matrices
    moe = _layer(params, 3)["block_sparse_moe"]
    h = jnp.asarray(np.random.default_rng(8).normal(size=(2, 40, 64)), jnp.float32)
    want, counts, _ = reference.moe_block(h.reshape(-1, 64), moe, 4)
    ffn_cfg = dataclasses.replace(cfg, intermediate_size=32)
    total, held_rows = 0.0, []
    for share in range(8):
        own = slice(share * HELD, (share + 1) * HELD)
        part = {**moe, **{k: moe[k][own] for k in ("w1", "w3", "w2")}}
        block = LlamaMoEBlock(dataclasses.replace(
            ffn_cfg, moe_experts_held=HELD, moe_share_index=share))
        out, mods = block.apply({"params": part}, h, mutable=["moe_stats"])
        total = total + out
        held_rows.append(int(mods["moe_stats"]["rows_held"]))
        np.testing.assert_array_equal(
            np.asarray(mods["moe_stats"]["expert_counts"]), np.asarray(counts))
        # and the reference given that share says the same of the part
        ref_part, _, _ = reference.moe_block(h.reshape(-1, 64), part, 4,
                                             first_expert=share * HELD)
        np.testing.assert_allclose(np.asarray(out).reshape(-1, 64),
                                   np.asarray(ref_part), rtol=0, atol=2e-6)
    assert sum(held_rows) == 80 * 4 and min(held_rows) > 0
    np.testing.assert_allclose(np.asarray(total).reshape(-1, 64), np.asarray(want),
                               rtol=0, atol=5e-6)
    # all experts held is the uncut layer
    whole, _ = LlamaMoEBlock(ffn_cfg).apply({"params": moe}, h, mutable=["moe_stats"])
    np.testing.assert_allclose(np.asarray(whole).reshape(-1, 64), np.asarray(want),
                               rtol=0, atol=5e-6)


def test_a_block_that_holds_every_expert_is_the_all_experts_path_bit_for_bit():
    """``moe_experts_held`` equal to the router's width is today's block:
    the same function over the same operands, in bf16."""
    cfg, _, params, _ = small(held=None, dtype=jnp.bfloat16)
    moe = _layer(params, 2)["block_sparse_moe"]
    h = jnp.asarray(np.random.default_rng(9).normal(size=(2, 40, 64)), jnp.bfloat16)
    ffn_cfg = dataclasses.replace(cfg, intermediate_size=32)

    def grads(block_cfg):
        def f(p, x):
            return jnp.sum(LlamaMoEBlock(block_cfg).apply({"params": p}, x)
                           .astype(jnp.float32) ** 2)
        return jax.grad(f, (0, 1))(moe, h), LlamaMoEBlock(block_cfg).apply({"params": moe}, h)

    (gp, gx), out = grads(ffn_cfg)
    (gp16, gx16), out16 = grads(dataclasses.replace(ffn_cfg, moe_experts_held=16))
    assert out.dtype == jnp.bfloat16 and np.abs(np.asarray(out, np.float32)).max() > 0
    np.testing.assert_array_equal(np.asarray(out, np.float32), np.asarray(out16, np.float32))
    np.testing.assert_array_equal(np.asarray(gx, np.float32), np.asarray(gx16, np.float32))
    for a, b in zip(jax.tree_util.tree_leaves(gp), jax.tree_util.tree_leaves(gp16)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _route_direct(cfg, logits, bias):
    """What the block's router makes of ``logits``."""
    import flax.linen as nn

    class Probe(LlamaMoEBlock):
        @nn.compact
        def __call__(self, x):
            return self._route(x)
    return Probe(cfg).apply({"params": {"expert_bias": jnp.asarray(bias, jnp.float32)}},
                            jnp.asarray(logits, jnp.float32))


def test_the_router_by_hand():
    """Six experts, top-2. s = sigmoid(logits) = 0.9, 0.8, 0.7, 0.6, 0.2, 0.1;
    a bias of +0.25 on expert 2 and +0.35 on expert 3 makes s + bias
    0.9, 0.8, 0.95, 0.95: experts 2 and 3 are chosen, weighted by their
    UNBIASED scores 0.7 and 0.6 over their sum + 1e-6, times the factor."""
    s = np.array([[0.9, 0.8, 0.7, 0.6, 0.2, 0.1]])
    logits = np.log(s / (1 - s))
    base = dataclasses.replace(
        Lfm2MoePolicy().config_from_hf(SMALL), num_local_experts=6,
        num_experts_per_tok=2, hidden_size=6)
    scores, w, idx = _route_direct(base, logits, np.zeros(6))
    np.testing.assert_allclose(np.asarray(scores), s, rtol=1e-6)
    assert sorted(np.asarray(idx)[0]) == [0, 1]          # no bias: the two largest
    np.testing.assert_allclose(sorted(np.asarray(w)[0]),
                               [0.8 / (1.7 + 1e-6), 0.9 / (1.7 + 1e-6)], rtol=1e-6)
    bias = np.array([0, 0, 0.25, 0.36, 0, 0])
    _, w, idx = _route_direct(base, logits, bias)
    order = np.argsort(np.asarray(idx)[0])
    assert list(np.asarray(idx)[0][order]) == [2, 3]     # the bias moves the choice
    np.testing.assert_allclose(np.asarray(w)[0][order],  # and not the weight
                               [0.7 / (1.3 + 1e-6), 0.6 / (1.3 + 1e-6)], rtol=1e-6)
    assert float(np.asarray(w).sum()) == pytest.approx(1.3 / (1.3 + 1e-6), rel=1e-7)
    assert float(np.asarray(w).sum()) < 1.0              # the 1e-6 is there
    _, w3, _ = _route_direct(dataclasses.replace(base, routed_scaling_factor=2.5),
                             logits, bias)
    np.testing.assert_allclose(np.asarray(w3), 2.5 * np.asarray(w), rtol=1e-6)
    _, raw, _ = _route_direct(dataclasses.replace(base, moe_renormalize=False),
                              logits, bias)
    np.testing.assert_allclose(sorted(np.asarray(raw)[0]), [0.6, 0.7], rtol=1e-6)
    # without the selection bias the block has no such parameter
    plain = dataclasses.replace(base, moe_selection_bias=False)
    _, _, idx = _route_direct(plain, logits, bias)
    assert sorted(np.asarray(idx)[0]) == [0, 1]
    # softmax scoring (Mixtral) is what it was
    soft = dataclasses.replace(base, moe_scoring="softmax", moe_selection_bias=False,
                               moe_renorm_eps=0.0)
    scores, w, idx = _route_direct(soft, logits, bias)
    p = np.exp(logits) / np.exp(logits).sum()
    np.testing.assert_allclose(np.asarray(scores), p, rtol=1e-6)
    np.testing.assert_allclose(sorted(np.asarray(w)[0]),
                               sorted(p[0][:2] / p[0][:2].sum()), rtol=1e-6)
    with pytest.raises(ValueError, match="moe_scoring"):
        _route_direct(dataclasses.replace(base, moe_scoring="tanh"), logits, bias)


def test_per_head_qk_norm_is_not_the_flat_one():
    cfg = LlamaConfig.tiny(dtype=jnp.float32, attn_impl="xla")
    x = jnp.asarray(np.random.default_rng(2).normal(size=(1, 16, 64)), jnp.float32)
    pos = jnp.arange(16)[None]
    from deepspeed_tpu.models.llama import precompute_rope
    cos, sin = precompute_rope(cfg.head_dim_, 128, cfg.rope_theta)
    outs = {}
    for kind in (False, "flat", True, "head"):
        attn = LlamaAttention(dataclasses.replace(cfg, qk_norm=kind))
        params = unbox_params(attn.init(jax.random.PRNGKey(0), x, cos, sin, pos)["params"])
        if kind:
            width = {"head": (16, ), "flat": None, True: None}[kind]
            assert params["q_norm"]["weight"].shape == (width or (64, ))
            assert params["k_norm"]["weight"].shape == (width or (32, ))
        outs[kind] = np.asarray(attn.apply({"params": params}, x, cos, sin, pos))
    np.testing.assert_array_equal(outs["flat"], outs[True])
    for a, b in ((False, "flat"), (False, "head"), ("flat", "head")):
        assert np.abs(outs[a] - outs[b]).max() > 1e-3, (a, b)
    # by hand: each head's 16 values to unit RMS
    attn = LlamaAttention(dataclasses.replace(cfg, qk_norm="head", pos_embedding="none"))
    params = unbox_params(attn.init(jax.random.PRNGKey(0), x, cos, sin, pos)["params"])
    q = (x @ params["q_proj"]["kernel"]).reshape(1, 16, 4, 16)
    k = (x @ params["k_proj"]["kernel"]).reshape(1, 16, 2, 16)
    v = (x @ params["v_proj"]["kernel"]).reshape(1, 16, 2, 16)
    unit = lambda t: t / jnp.sqrt(jnp.mean(t * t, -1, keepdims=True) + cfg.rms_norm_eps)
    want = jax.nn.dot_product_attention(unit(q), unit(k), v, is_causal=True)
    want = want.reshape(1, 16, 64) @ params["o_proj"]["kernel"]
    got = attn.apply({"params": params}, x, cos, sin, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5)


def test_bf16_compute_stays_near_the_reference():
    cfg, _, params, ids = small(seed=11)
    model = LlamaForCausalLM(dataclasses.replace(cfg, dtype=jnp.bfloat16))
    got = np.asarray(model.apply({"params": params}, ids), np.float32)
    want, margin = reference.logits_and_margin(params, ids, SMALL)
    err = (np.linalg.norm(got - np.asarray(want), axis=-1)
           / np.linalg.norm(np.asarray(want), axis=-1))
    # bf16 carries 8 bits and the gated convolution multiplies three such
    # values; a token whose 4th and 5th scores tie within that may choose
    # another expert, so a few positions may sit far out
    assert np.median(err) < 3e-2 and np.mean(err > 1e-1) < 0.1
    loss, _ = program(model, params, ids, ids)
    assert float(loss) == pytest.approx(
        float(reference.cross_entropy(params, ids, SMALL)), rel=2e-3)


@pytest.mark.parametrize("wrong", ["not renormalised", "chosen without the bias",
                                   "no q/k norm"])
def test_a_wrong_router_or_norm_fails_the_comparison(wrong):
    cfg, model, params, ids = small()
    want = np.asarray(reference.logits_and_margin(params, ids, SMALL)[0])
    rel = lambda x: np.linalg.norm(np.asarray(x) - want) / np.linalg.norm(want)
    assert rel(model.apply({"params": params}, ids)) < 1e-5
    over = {"not renormalised": dict(moe_renormalize=False),
            "chosen without the bias": dict(moe_selection_bias=False),
            "no q/k norm": dict(qk_norm=False)}[wrong]
    bad = LlamaForCausalLM(dataclasses.replace(cfg, **over)).apply(
        {"params": params}, ids)
    assert rel(bad) > 1e-2, wrong


def test_the_engine_publishes_the_rows_held_and_the_layers_by_kind():
    from deepspeed_tpu.comm import reset_mesh_context
    from deepspeed_tpu.observability import get_registry
    cfg, model, params, ids = small(seed=7)
    reset_mesh_context()
    set_mesh_context(MeshContext.create(devices=jax.devices()[:1]))
    reg = get_registry()
    reg.reset()     # the registry is the process's: zero what other engines set
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_batch_size": 2, "steps_per_print": 0,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
    kinds = {m.labels["kind"]: m.value for m in reg.series("ds_model_layers") if m.value}
    assert kinds == {"conv+dense": 1.0, "attention+moe": 1.0, "conv+moe": 3.0}
    held, routed, fallback = (reg.counter(n) for n in (
        "ds_moe_rows_held_total", "ds_moe_tokens_routed_total",
        "ds_moe_share_fallback_total"))
    before = held.value, routed.value, fallback.value
    engine.train_batch(iter([(ids, ids)]))
    stats = engine.moe_stats()
    assert held.value == before[0]                  # published one dispatch later
    bias = np.asarray(_layer(engine.params, 3)["block_sparse_moe"]["expert_bias"]).copy()
    engine.train_batch(iter([(ids, ids)]))
    assert held.value - before[0] == int(stats["rows_held"]) > 0
    assert routed.value - before[1] == ids.size * 4 * 4 == int(stats["expert_counts"].sum())
    assert fallback.value == before[2]
    assert reg.get("ds_moe_rows_held_share").value == pytest.approx(
        int(stats["rows_held"]) / (ids.size * 4 * 4))
    counts = np.asarray(stats["expert_counts"])
    assert reg.get("ds_moe_expert_load_max_over_mean").value \
        == pytest.approx(counts.max() / counts.mean())
    # the selection bias is a buffer: AdamW leaves it where it was seeded
    after = np.asarray(_layer(engine.params, 3)["block_sparse_moe"]["expert_bias"])
    np.testing.assert_array_equal(after, bias)
    assert np.abs(bias).max() > 0.01
    assert engine._train_step_fused._cache_size() == 1
    reset_mesh_context()


def test_serving_refuses_a_model_it_cannot_run():
    from deepspeed_tpu.inference.v2.model import RaggedLlamaModel
    cfg, _, params, _ = small()
    with pytest.raises(NotImplementedError, match="layer_specs"):
        RaggedLlamaModel(cfg, params)
