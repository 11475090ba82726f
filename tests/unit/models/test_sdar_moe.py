"""SDAR-MoE under block-diffusion training through the normal path against
the plain float32 reference (``benchmark/reference/sdar_moe.py``, which
imports nothing from the program) at a small size on seeded weights, all on
the CPU: the loss, logits, gradients and routing counts of a chip's share;
the reference made wrong (leak, shift, causal, unit weights, ...) differing;
the eight shares of one layer adding up to the uncut layer;
``SdarMoePolicy``'s config and weight-name map; the engine's step with its
own noising, counters and gauges.
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
from benchmark.reference import sdar_moe as reference  # noqa: E402
from deepspeed_tpu.comm.mesh import MeshContext, set_mesh_context  # noqa: E402
from deepspeed_tpu.models.llama import (LlamaConfig, LlamaDecoderLayer,  # noqa: E402
                                        LlamaForCausalLM, init_llama)
from deepspeed_tpu.module_inject.replace_module import (  # noqa: E402
    convert_hf_checkpoint, export_hf_checkpoint)
from deepspeed_tpu.module_inject.replace_policy import (SdarMoePolicy,  # noqa: E402
                                                         policy_for)
from deepspeed_tpu.runtime.data_pipeline import DiffusionBatch, noise_batch  # noqa: E402

# config.json of JetLM/SDAR-30B-A3B-Chat as the catalog has it
PUBLISHED = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
             "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
             "max_position_embeddings": 32768, "max_window_layers": 48,
             "mlp_only_layers": [], "model_type": "sdar_moe",
             "moe_intermediate_size": 768, "norm_topk_prob": True,
             "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
             "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
             "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
             "tie_word_embeddings": False, "use_sliding_window": False,
             "vocab_size": 151936}
# the same architecture small: 2 layers, 16 experts top-4, 8 heads of 16 over
# one KV head (group 8, as published), blocks of 4
SMALL = dict(PUBLISHED, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
             num_attention_heads=8, num_key_value_heads=1, head_dim=16, num_experts=16,
             num_experts_per_tok=4, num_hidden_layers=2, vocab_size=256,
             max_position_embeddings=128, block_length=4)
HELD = 2            # of 16: eight shares, as the cell's 16 of 128
SEQ = 48            # data tokens a sequence: 96 positions, no tile's multiple


def small(held=HELD, share=0, dtype=jnp.float32, seed=5, attn_impl="xla", **over):
    cfg = dataclasses.replace(
        SdarMoePolicy().config_from_hf(dict(SMALL, **over)), dtype=dtype,
        attn_impl=attn_impl, moe_experts_held=held, moe_share_index=share)
    model, params = init_llama(cfg, seed=seed)
    ids = np.random.default_rng(seed).integers(0, cfg.diffusion_mask_id_, (2, SEQ),
                                               dtype=np.int32)
    batch = noise_batch(ids, [seed, 0], cfg.diffusion_block_length,
                        cfg.diffusion_mask_id_)
    return cfg, model, params, batch


def program(model, params, batch, with_loss=True):
    args, kw = batch.model_args()
    if not with_loss:
        args, kw = args[:1], {"positions": kw["positions"]}
    out, mods = model.apply({"params": params}, *args, **kw,
                            mutable=["moe_stats", "diffusion_stats"])
    stats = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(mods)[0]:
        stats[path[-1].key] = stats.get(path[-1].key, 0) + leaf
    return out, stats


def every_position(batch):
    rows, seq = batch.targets.shape
    return np.tile(np.arange(seq), (rows, 1))


def test_config_from_the_published_dictionary():
    cfg = SdarMoePolicy().config_from_hf(PUBLISHED)
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size) == (2048, 768, 151936)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim_) == (32, 4, 128)
    assert cfg.rope_theta == 1_000_000 and cfg.rms_norm_eps == 1e-6
    assert (cfg.num_local_experts, cfg.num_experts_per_tok) == (128, 8)
    assert cfg.moe_scoring == "softmax" and cfg.moe_renormalize
    assert cfg.moe_renorm_eps == 0.0 and not cfg.moe_selection_bias
    assert cfg.qk_norm == "head" and not cfg.tie_word_embeddings
    assert cfg.router_aux_loss_coef == 0.0 and cfg.layer_specs is None
    assert cfg.objective == "block_diffusion" and cfg.block_diffusion_
    assert cfg.diffusion_block_length == 4 and cfg.diffusion_mask_id_ == 151935
    assert isinstance(policy_for("sdar_moe"), SdarMoePolicy)
    assert isinstance(policy_for("SDARMoeForCausalLM"), SdarMoePolicy)
    for key, value in (("mlp_only_layers", [3]), ("decoder_sparse_step", 2),
                       ("attention_bias", True), ("use_sliding_window", True)):
        with pytest.raises(ValueError, match="sdar_moe"):
            SdarMoePolicy().config_from_hf(dict(PUBLISHED, **{key: value}))
    assert LlamaConfig().objective == "causal_lm" and not LlamaConfig().block_diffusion_
    with pytest.raises(ValueError, match="unknown objective"):
        LlamaConfig(objective="diffusion").block_diffusion_


def test_weight_map_round_trip_from_hf_named_tensors():
    cfg, _, params, _ = small(held=None)
    hf = export_hf_checkpoint("sdar_moe", cfg, params)
    shapes = {"model.embed_tokens.weight": (256, 64), "lm_head.weight": (256, 64),
              "model.norm.weight": (64, ),
              "model.layers.0.input_layernorm.weight": (64, ),
              "model.layers.1.post_attention_layernorm.weight": (64, ),
              "model.layers.0.self_attn.q_proj.weight": (128, 64),
              "model.layers.0.self_attn.k_proj.weight": (16, 64),
              "model.layers.0.self_attn.o_proj.weight": (64, 128),
              "model.layers.1.self_attn.q_norm.weight": (16, ),
              "model.layers.1.self_attn.k_norm.weight": (16, ),
              "model.layers.1.mlp.gate.weight": (16, 64),
              "model.layers.1.mlp.experts.15.gate_proj.weight": (32, 64),
              "model.layers.0.mlp.experts.0.up_proj.weight": (32, 64),
              "model.layers.0.mlp.experts.7.down_proj.weight": (64, 32)}
    for name, shape in shapes.items():
        assert hf[name].shape == shape, name
    # embedding, head, final norm; a layer: two norms, attention's six, the
    # router, 16 experts of three
    assert len(hf) == 3 + 2 * (2 + 6 + 1 + 3 * 16)
    assert not any("block_sparse_moe" in k or ".mlp.gate_proj" in k for k in hf)
    cfg2, back = convert_hf_checkpoint("sdar_moe", hf, dict(SMALL))
    assert cfg2 == dataclasses.replace(cfg, dtype=cfg2.dtype, attn_impl=cfg2.attn_impl)
    flat = jax.tree_util.tree_leaves_with_path(params)
    flat_back = dict(jax.tree_util.tree_leaves_with_path(back))
    assert len(flat) == len(flat_back)
    for path, leaf in flat:
        np.testing.assert_array_equal(np.asarray(leaf), flat_back[path])


@pytest.fixture(scope="module")
def sound():
    """``small()`` as the cases below all build it (one seed, so one set of
    parameters and one batch) and the sound reference's step on it, made
    once: (cfg, model, params, batch, positions, the reference's parts)."""
    cfg, model, params, batch = small()
    at = every_position(batch)
    return cfg, model, params, batch, at, reference.step_parts(
        params, batch, SMALL, at)


@pytest.mark.parametrize("attn_impl", ["xla", "flash"])
def test_float32_program_matches_the_reference_for_its_share(attn_impl, sound):
    """Loss, every noisy position's logits and the routing, the attention by
    XLA under the mask and by the interpreted kernels."""
    cfg, _, params, batch, at, want = sound
    model = LlamaForCausalLM(dataclasses.replace(cfg, attn_impl=attn_impl))
    with jax.default_matmul_precision("highest"):
        loss, stats = program(model, params, batch)
        logits, _ = program(model, params, batch, with_loss=False)
    assert logits.shape == (2, SEQ, 256)            # the noisy half alone
    np.testing.assert_allclose(float(loss), want["ce"], rtol=2e-6)
    np.testing.assert_allclose(np.asarray(logits), want["logits"], atol=3e-5)
    np.testing.assert_array_equal(np.asarray(stats["expert_counts"]), want["counts"])
    assert int(stats["rows_held"]) == want["rows_held"]
    assert int(stats["masked_tokens"]) == want["masked_tokens"] \
        == int((batch.weights > 0).sum())
    assert int(np.asarray(stats["expert_counts"]).sum()) == 2 * 2 * SEQ * 4 * 2


def test_gradients_match_jax_grad_of_the_reference(sound):
    cfg, model, params, batch, _, want = sound
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda p: program(model, p, batch)[0])(params)
    compared = 0
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(want["grads"])):
        if not np.any(w) and not np.any(g):
            # a seeded router this small may send a layer's held experts no
            # row at all: then neither side has a gradient there
            assert "block_sparse_moe" in jax.tree_util.keystr(path) \
                or "post_attention_layernorm" in jax.tree_util.keystr(path)
            continue
        err = np.linalg.norm(np.asarray(g) - w) / np.linalg.norm(w)
        assert err < 2e-5, (jax.tree_util.keystr(path), err)
        compared += 1
    assert compared >= 22


@pytest.mark.parametrize("wrong", reference.WRONG)
def test_a_wrong_reference_fails_the_comparison(wrong, sound):
    """Each wrong model the cell's ``correct`` has to tell: its loss or its
    gradients lie far from the program's."""
    _, _, params, batch, at, right = sound
    bad = reference.step_parts(params, batch, SMALL, at, wrong={wrong})
    loss_off = abs(bad["ce"] - right["ce"]) / right["ce"]
    grad_off = max(np.linalg.norm(b - s) / np.linalg.norm(s) for b, s in zip(
        jax.tree_util.tree_leaves(bad["grads"]), jax.tree_util.tree_leaves(right["grads"]))
        if np.any(s))
    assert max(loss_off, grad_off) > 5e-2, (wrong, loss_off, grad_off)
    if wrong == "leak":
        # a noisy query that sees its own clean token: the loss can only fall
        # as training goes on, and already moves at initialisation
        assert loss_off > 1e-4


def test_the_configurations_own_precision_is_not_a_wrong_reference(sound):
    """A reference at bf16 operands is the program's arithmetic: it moves
    the gradients by what rounding does and no more, where fp8 moves them
    ten times as far."""
    _, _, params, batch, at, right = sound

    def off(wrong):
        bad = reference.step_parts(params, batch, SMALL, at, wrong={wrong})
        return max(np.linalg.norm(b - s) / np.linalg.norm(s) for b, s in zip(
            jax.tree_util.tree_leaves(bad["grads"]),
            jax.tree_util.tree_leaves(right["grads"])) if np.any(s))

    assert reference.OWN_PRECISION == "bf16" and "bf16" not in reference.WRONG
    assert off("bf16") < 0.3 * off("fp8")


def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_layer():
    """What each chip's layer gives for its experts, added over the eight
    chips that share the layer with the attention residual every chip
    computes alike counted once, is the uncut reference's layer."""
    cfg, _, params, batch = small(held=None)            # all 16 experts' matrices
    lp = params["model"]["layers_1"]
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(size=(2, 2 * SEQ, 64)), jnp.float32)
    positions = jnp.asarray(batch.positions)
    with jax.default_matmul_precision("highest"):
        want, counts = reference.layer(x, lp, positions, SMALL)
        alike = reference.attention_residual(x, lp, positions, SMALL)
        from deepspeed_tpu.models.llama import precompute_rope
        cos, sin = precompute_rope(16, 128, 1e6)
        total, held_rows = 0.0, []
        for share in range(8):
            own = slice(share * HELD, (share + 1) * HELD)
            moe = lp["block_sparse_moe"]
            part = {**lp, "block_sparse_moe": {
                **moe, **{k: moe[k][own] for k in ("w1", "w3", "w2")}}}
            layer = LlamaDecoderLayer(dataclasses.replace(
                cfg, moe_experts_held=HELD, moe_share_index=share), 1)
            out, mods = layer.apply({"params": part}, x, cos, sin, positions,
                                    mutable=["moe_stats"])
            stats = mods["moe_stats"]["block_sparse_moe"]
            held_rows.append(int(stats["rows_held"]))
            np.testing.assert_array_equal(np.asarray(stats["expert_counts"]),
                                          np.asarray(counts))
            ref_part, _ = reference.layer(x, part, positions, SMALL,
                                          first_expert=share * HELD)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref_part), atol=5e-6)
            total = total + (out - alike)
    assert sum(held_rows) == 2 * 2 * SEQ * 4 and min(held_rows) > 0
    np.testing.assert_allclose(np.asarray(total + alike), np.asarray(want), atol=2e-5)


def test_bf16_compute_stays_near_the_reference():
    cfg, model, params, batch = small(dtype=jnp.bfloat16)
    want = reference.step_parts(params, batch, SMALL, every_position(batch),
                                gradients=False)
    loss, _ = program(model, params, batch)
    logits, _ = program(model, params, batch, with_loss=False)
    assert abs(float(loss) - want["ce"]) / want["ce"] < 2e-3
    err = (np.linalg.norm(np.asarray(logits, np.float32) - want["logits"], axis=-1)
           / np.linalg.norm(want["logits"], axis=-1))
    assert np.median(err) < 2e-2


def test_the_objective_refuses_what_its_mask_cannot_carry(sound):
    cfg, model, params, batch = sound[:4]
    args, kw = batch.model_args()
    with pytest.raises(ValueError, match="loss_weights"):
        model.apply({"params": params}, *args, positions=kw["positions"])
    with pytest.raises(ValueError, match="padding mask"):
        model.apply({"params": params}, args[0], positions=kw["positions"],
                    attn_mask=jnp.ones_like(args[0]))
    with pytest.raises(ValueError, match="multiple of the block length"):
        model.apply({"params": params}, args[0][:, :-2])
    causal = LlamaForCausalLM(dataclasses.replace(cfg, objective="causal_lm"))
    with pytest.raises(ValueError, match="block-diffusion objective"):
        causal.apply({"params": params}, *args, **kw)


def test_the_engine_noises_raw_ids_and_publishes_the_objectives_counters():
    """``train_batch`` on raw ids noises them with the config's seed and the
    step (``ds.train.noise``); a ``DiffusionBatch`` passes as made; the fused
    step returns the masked tokens beside the loss and the registry carries
    them one dispatch late."""
    from deepspeed_tpu.observability import get_registry, get_tracer
    cfg, model, params, batch = small(dtype=jnp.bfloat16)
    params = jax.tree_util.tree_map(np.asarray, params)     # each engine its own copy
    set_mesh_context(MeshContext.create(devices=jax.devices()[:1]))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_batch_size": 2, "optimizer": {"type": "AdamW",
                                                     "params": {"lr": 1e-3}},
                "bf16": {"enabled": True}, "steps_per_print": 0, "seed": 11})
    ids = np.asarray(batch.targets)
    reg = get_registry()
    before = getattr(reg.get("ds_diffusion_masked_tokens_total"), "value", 0.0)
    want = [noise_batch(ids, [11, step], 4, cfg.diffusion_mask_id_) for step in range(2)]
    losses = []
    for step in range(2):
        losses.append(float(engine.train_batch(iter([ids]))))
        stats = engine.diffusion_stats()
        masked = int((want[step].weights > 0).sum())
        assert stats["masked_tokens"] == masked
        assert stats["mask_rate"] == masked / ids.size
        np.testing.assert_allclose(
            stats["t_mean_masked"],
            np.mean(1.0 / want[step].weights[want[step].weights > 0]), rtol=1e-5)
    assert all(np.isfinite(losses))
    # the same batch, made by the caller: the same step as the engine's own
    engine2, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_batch_size": 2, "optimizer": {"type": "AdamW",
                                                     "params": {"lr": 1e-3}},
                "bf16": {"enabled": True}, "steps_per_print": 0, "seed": 99})
    assert float(engine2.train_batch(iter([want[0]]))) == losses[0]
    assert isinstance(want[0], DiffusionBatch)
    engine.train_batch(iter([ids]))         # publishes the two steps before it
    published = reg.get("ds_diffusion_masked_tokens_total").value - before
    assert published >= sum(int((w.weights > 0).sum()) for w in want)
    assert 0.0 < reg.get("ds_diffusion_mask_rate").value < 1.0
    kinds = {m.labels["kind"]: m.value for m in reg.series("ds_model_layers")}
    assert kinds.get("attention+moe") == 2.0
    assert any(s["name"] == "ds.train.noise" for s in get_tracer().scopes("ds.train."))
    assert engine.moe_stats().keys() >= {"expert_counts", "rows_held"}
    assert not any(k.startswith("diffusion") for k in engine.moe_stats())
