"""OLMoE through the normal path against the plain float32 reference
(``benchmark/reference/olmoe.py``, which imports nothing from the program)
at a small size on seeded weights, all on the CPU: logits, the loss with the
router's balance term, gradients of every parameter group, the per-expert
counts the fused step returns, and ``OlmoePolicy``'s config and weight map.
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
from benchmark.reference import olmoe as reference  # noqa: E402
from deepspeed_tpu.models.llama import LlamaForCausalLM, init_llama  # noqa: E402
from deepspeed_tpu.module_inject.replace_module import (  # noqa: E402
    convert_hf_checkpoint, export_hf_checkpoint)
from deepspeed_tpu.module_inject.replace_policy import (OlmoePolicy,  # noqa: E402
                                                         policy_for)
from deepspeed_tpu.ops.grouped_matmul import (expert_counts, moe_dense_mlp,  # noqa: E402
                                              moe_grouped_mlp)

# the catalog's config.json for allenai/OLMoE-1B-7B-0125-Instruct
PUBLISHED = {"attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
             "hidden_size": 2048, "intermediate_size": 1024,
             "max_position_embeddings": 4096, "model_type": "olmoe",
             "norm_topk_prob": False, "num_attention_heads": 16,
             "num_experts": 64, "num_experts_per_tok": 8,
             "num_hidden_layers": 16, "num_key_value_heads": 16,
             "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
             "tie_word_embeddings": False, "vocab_size": 50304}
# the same architecture small: two layers, 8 experts, top-4, 4 heads of 16
SMALL = dict(PUBLISHED, hidden_size=64, intermediate_size=32,
             num_attention_heads=4, num_key_value_heads=4, num_experts=8,
             num_experts_per_tok=4, num_hidden_layers=2, vocab_size=256,
             max_position_embeddings=128, router_aux_loss_coef=0.01)


def small(dtype=jnp.float32, seed=3, **over):
    cfg = dataclasses.replace(OlmoePolicy().config_from_hf(dict(SMALL, **over)),
                              dtype=dtype, attn_impl="xla")
    model, params = init_llama(cfg, seed=seed)
    ids = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (2, 48), dtype=np.int32))
    return cfg, model, params, ids


def program_loss(model, params, ids):
    """The engine's contract: the sown balance terms are added to the loss."""
    loss, mods = model.apply({"params": params}, ids, ids,
                             mutable=["aux_loss", "moe_stats"])
    aux = sum(jnp.sum(a) for a in jax.tree_util.tree_leaves(mods["aux_loss"]))
    counts = sum(jax.tree_util.tree_leaves(mods["moe_stats"]))
    return loss + aux, counts


def test_config_from_the_published_dictionary():
    cfg = OlmoePolicy().config_from_hf(PUBLISHED)
    assert (cfg.hidden_size, cfg.intermediate_size) == (2048, 1024)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim_) \
        == (16, 16, 128)
    assert (cfg.num_local_experts, cfg.num_experts_per_tok) == (64, 8)
    assert cfg.moe_renormalize is False and cfg.qk_norm and not cfg.post_norm
    assert cfg.shared_expert_intermediate_size is None and not cfg.attention_bias
    assert cfg.vocab_size == 50304 and cfg.rope_theta == 10000
    assert cfg.num_hidden_layers == 16 and not cfg.tie_word_embeddings
    assert cfg.clip_qkv is None and cfg.moe_grouped
    assert cfg.router_aux_loss_coef == 0.01      # OlmoeConfig's default
    # one layer: experts 402.7M, attention 16.8M, router 0.13M (ISSUE 26)
    assert cfg.per_layer_elements() == 419_565_568
    assert isinstance(policy_for("olmoe"), OlmoePolicy)
    assert isinstance(policy_for("OlmoeForCausalLM"), OlmoePolicy)
    assert OlmoePolicy().config_from_hf(
        dict(PUBLISHED, norm_topk_prob=True)).moe_renormalize is True
    with pytest.raises(ValueError):
        OlmoePolicy().config_from_hf(dict(PUBLISHED, attention_bias=True))


def test_weight_map_round_trip_from_hf_named_tensors():
    cfg, _, params, _ = small()
    hf = export_hf_checkpoint("olmoe", cfg, params)
    for name in ("model.layers.1.self_attn.q_norm.weight",
                 "model.layers.1.self_attn.k_norm.weight",
                 "model.layers.0.mlp.gate.weight",
                 "model.layers.0.mlp.experts.7.gate_proj.weight",
                 "model.layers.0.mlp.experts.0.up_proj.weight",
                 "model.layers.1.mlp.experts.3.down_proj.weight",
                 "model.layers.0.input_layernorm.weight", "lm_head.weight"):
        assert name in hf, name
    assert not any("block_sparse_moe" in k or ".mlp.gate_proj" in k for k in hf)
    # torch's [out, in]: an expert's gate is [intermediate, hidden]
    assert hf["model.layers.0.mlp.experts.7.gate_proj.weight"].shape == (32, 64)
    assert hf["model.layers.0.mlp.gate.weight"].shape == (8, 64)
    cfg2, back = convert_hf_checkpoint("olmoe", hf, dict(SMALL))
    assert cfg2 == dataclasses.replace(cfg, dtype=cfg2.dtype,
                                       attn_impl=cfg2.attn_impl)
    flat = jax.tree_util.tree_leaves_with_path(params)
    flat_back = dict(jax.tree_util.tree_leaves_with_path(back))
    assert len(flat) == len(flat_back)
    for path, leaf in flat:
        np.testing.assert_array_equal(np.asarray(leaf), flat_back[path])


def test_logits_match_transformers_olmoe():
    """The policy and the reference against the public implementation."""
    transformers = pytest.importorskip("transformers")
    torch = pytest.importorskip("torch")
    hf_cfg = transformers.OlmoeConfig(**{k: v for k, v in SMALL.items()
                                         if k != "model_type"})
    torch.manual_seed(26)
    hf_model = transformers.OlmoeForCausalLM(hf_cfg).eval()
    with torch.no_grad():   # the norms start at one: make them matter
        for n, p in hf_model.named_parameters():
            if "norm" in n:
                p.normal_(1.0, 0.2)
    cfg, params = convert_hf_checkpoint("olmoe", hf_model.state_dict(),
                                        hf_cfg.to_dict())
    ids = np.array([[1, 5, 9, 42, 17, 3, 200, 77]], dtype=np.int32)
    with torch.no_grad():
        want = hf_model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
    ours = LlamaForCausalLM(dataclasses.replace(cfg, dtype=jnp.float32,
                                                attn_impl="xla"))
    got = np.asarray(ours.apply({"params": params}, jnp.asarray(ids)))
    # float32 on both sides, another order of the same sums
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)
    ref = np.asarray(reference.logits(params, jnp.asarray(ids), SMALL))
    np.testing.assert_allclose(ref, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("grouped", [True, False], ids=["grouped", "dense"])
def test_float32_program_matches_the_reference(grouped):
    cfg, model, params, ids = small()
    model = LlamaForCausalLM(dataclasses.replace(cfg, moe_grouped=grouped))
    # float32 on both sides: what is left is the order of the sums (the
    # program sorts rows by expert; XLA's CPU dot is not "highest"-exact)
    got = np.asarray(model.apply({"params": params}, ids))
    want = np.asarray(reference.logits(params, ids, SMALL))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-5)
    loss, counts = program_loss(model, params, ids)
    parts = reference.loss_parts(params, ids, SMALL)
    assert float(parts["aux"]) > 0.01    # two layers of about coef * 1
    assert float(loss) == pytest.approx(float(parts["ce"] + parts["aux"]), rel=2e-6)
    # per-expert assignments, summed over the two layers: none dropped
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(parts["counts"]))
    assert int(counts.sum()) == ids.size * 4 * 2
    assert float(reference.cross_entropy(params, ids, SMALL)) \
        == pytest.approx(float(loss), rel=2e-6)
    np.testing.assert_array_equal(
        np.asarray(reference.expert_counts(params, ids, SMALL)), np.asarray(counts))


def _groups(tree):
    """Gradient leaves by parameter group."""
    layer = tree["model"]["layers_1"]
    moe = layer["block_sparse_moe"]
    return {"embedding": tree["model"]["embed_tokens"]["embedding"],
            "head": tree["model"]["lm_head"]["kernel"],
            "final norm": tree["model"]["norm"]["weight"],
            "layer norms": jnp.stack([layer["input_layernorm"]["weight"],
                                      layer["post_attention_layernorm"]["weight"]]),
            "q/k norms": jnp.stack([layer["self_attn"]["q_norm"]["weight"],
                                    layer["self_attn"]["k_norm"]["weight"]]),
            "attention": jnp.stack([layer["self_attn"][p]["kernel"] for p in
                                    ("q_proj", "k_proj", "v_proj", "o_proj")]),
            "router": moe["gate"]["kernel"],
            "experts in": jnp.stack([moe["w1"], moe["w3"]]),
            "experts out": moe["w2"],
            "first layer": tree["model"]["layers_0"]["block_sparse_moe"]["w2"]}


GROUPS = ("embedding", "head", "final norm", "layer norms", "q/k norms",
          "attention", "router", "experts in", "experts out", "first layer")


@pytest.fixture(scope="module")
def gradients():
    cfg, model, params, ids = small()
    got = jax.grad(lambda p: program_loss(model, p, ids)[0])(params)
    want = jax.grad(lambda p: reference.cross_entropy(p, ids, SMALL))(params)
    return _groups(got), _groups(want)


@pytest.mark.parametrize("group", GROUPS)
def test_gradients_match_jax_grad_of_the_reference(gradients, group):
    got, want = (np.asarray(g[group]) for g in gradients)
    assert np.abs(want).max() > 0
    # float32 on both sides; relative to the group's largest gradient, the
    # order of the sums leaves 1e-6 to 1e-5
    assert np.abs(got - want).max() <= 5e-5 * np.abs(want).max()


def test_bf16_compute_stays_near_the_reference():
    cfg, _, params, ids = small()
    model = LlamaForCausalLM(dataclasses.replace(cfg, dtype=jnp.bfloat16))
    bf16 = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16), params)
    got = np.asarray(model.apply({"params": bf16}, ids), np.float32)
    want = np.asarray(reference.logits(params, ids, SMALL))
    err = (np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1))
    # bf16 carries 8 bits: 2^-9 relative a rounding, a few dozen roundings
    # deep; a token whose 4th and 5th router logits tie within that chooses
    # another expert, so a few positions may sit far out
    assert np.median(err) < 2e-2 and np.mean(err > 5e-2) < 0.1
    loss, _ = program_loss(model, bf16, ids)
    assert float(loss) == pytest.approx(
        float(reference.cross_entropy(params, ids, SMALL)), rel=2e-3)


def test_a_renormalised_top_k_fails_the_comparison():
    cfg, model, params, ids = small()
    wrong = LlamaForCausalLM(dataclasses.replace(cfg, moe_renormalize=True))
    want = np.asarray(reference.logits(params, ids, SMALL))
    ok = np.asarray(model.apply({"params": params}, ids))
    bad = np.asarray(wrong.apply({"params": params}, ids))
    rel = lambda x: np.linalg.norm(x - want) / np.linalg.norm(want)
    assert rel(ok) < 1e-5 and rel(bad) > 5e-2
    # and the reference's own switch says the same thing
    np.testing.assert_allclose(
        bad, np.asarray(reference.logits(params, ids, dict(SMALL, norm_topk_prob=True))),
        rtol=0, atol=2e-5)


def test_grouped_matches_the_dense_oracle_at_64_experts_with_one_empty():
    rng = np.random.default_rng(26)
    T, H, F, E, k = 96, 32, 16, 64, 8
    x = jnp.asarray(rng.normal(size=(T, H)) * 0.5, jnp.float32)
    w1, w3 = (jnp.asarray(rng.normal(size=(E, H, F)) * 0.2, jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(rng.normal(size=(E, F, H)) * 0.2, jnp.float32)
    logits = jnp.asarray(rng.normal(size=(T, E)), jnp.float32).at[:, 17].set(-1e9)
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), k)    # not renormalised
    counts = np.asarray(expert_counts(idx, E))
    assert counts[17] == 0 and counts.sum() == T * k and (counts > 0).sum() == 63
    np.testing.assert_array_equal(
        counts, np.bincount(np.asarray(idx).ravel(), minlength=E))
    got = moe_grouped_mlp(x, w1, w3, w2, idx, w)
    want = moe_dense_mlp(x, w1, w3, w2, idx, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)
    # the reference's block computes the same from the router's weights
    moe = {"gate": {"kernel": jnp.zeros((H, E))}, "w1": w1, "w3": w3, "w2": w2}
    out, balance, ref_counts, _ = reference.moe_block(x, moe, k, capacity=T)
    assert float(balance) == pytest.approx(1.0)   # a uniform router: E * sum(1/E * 1/E)
    assert int(ref_counts.sum()) == T * k


@pytest.mark.parametrize("capacity", [None, 40])
def test_reference_experts_see_only_their_own_rows(capacity):
    """An expert's row list padded to any length at least its count gives
    the same block output."""
    _, _, params, ids = small()
    moe = params["model"]["layers_0"]["block_sparse_moe"]
    h = jnp.asarray(np.random.default_rng(1).normal(size=(48, 64)), jnp.float32)
    out, _, counts, margin = reference.moe_block(h, moe, 4, capacity=capacity)
    assert int(counts.max()) <= 40 and int(counts.sum()) == 48 * 4
    probs = jax.nn.softmax(h @ moe["gate"]["kernel"], -1)
    # the margin: the 4th largest router logit less the 5th
    ranked = np.sort(np.asarray(h @ moe["gate"]["kernel"]), axis=-1)[:, ::-1]
    np.testing.assert_allclose(np.asarray(margin), ranked[:, 3] - ranked[:, 4],
                               rtol=1e-4, atol=1e-5)
    w, idx = jax.lax.top_k(probs, 4)
    want = moe_dense_mlp(h, moe["w1"], moe["w3"], moe["w2"], idx, w)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_fused_step_returns_the_counts_and_publishes_the_gauges(caplog):
    from deepspeed_tpu.comm import reset_mesh_context
    from deepspeed_tpu.utils.logging import logger
    from deepspeed_tpu.comm.mesh import MeshContext, set_mesh_context
    from deepspeed_tpu.observability import get_registry
    cfg, model, params, ids = small()
    reset_mesh_context()
    # one device, as the cell: eight would not divide the batch of 2
    set_mesh_context(MeshContext.create(devices=jax.devices()[:1]))
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, model_parameters=params,
        config={"train_batch_size": 2, "steps_per_print": 0,
                "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 0}})
    reg = get_registry()
    routed = reg.counter("ds_moe_tokens_routed_total")
    before = routed.value
    want = reference.loss_parts(engine.params, ids, SMALL)
    logger.addHandler(caplog.handler)
    loss = engine.train_batch(iter([(ids, ids)]))
    stats = engine.moe_stats()
    # float32 compute on both sides: the counts are exact
    np.testing.assert_array_equal(stats["expert_counts"], np.asarray(want["counts"]))
    assert float(stats["aux_loss"]) == pytest.approx(float(want["aux"]), rel=1e-5)
    assert loss == pytest.approx(float(want["ce"] + want["aux"]), rel=1e-5)
    assert routed.value == before            # published one dispatch later
    engine.train_batch(iter([(ids, ids)]))
    assert routed.value - before == ids.size * 4 * 2
    load = reg.get("ds_moe_expert_load_max_over_mean").value
    counts = np.asarray(stats["expert_counts"])
    assert load == pytest.approx(counts.max() / counts.mean())
    assert reg.get("ds_moe_aux_loss").value == pytest.approx(float(want["aux"]), rel=1e-5)
    assert engine._train_step_fused._cache_size() == 1
    # the engine's kernel line, once: which grouped matmul the trace took (a
    # CPU, float32: XLA's; its backward is jax's own and has no count)
    engine.train_batch(iter([(ids, ids)]))
    logger.removeHandler(caplog.handler)
    lines = [r.getMessage() for r in caplog.records if "kernels: " in r.getMessage()]
    assert len(lines) == 1 and "kernels: grouped_matmul[" in lines[0] \
        and "rows=ragged_dot:" in lines[0] and "d_rows" not in lines[0], lines
    reset_mesh_context()
