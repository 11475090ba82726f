"""Kimi-VL-A3B's language model (``deepseek_v3`` blocks: latent attention, a
leading dense layer, a sigmoid router with a selection bias beside an ungated
shared expert) in ``models/llama.py`` against the plain reference
``benchmark/reference/kimi_vl.py`` on seeded weights at a small size: logits,
loss and gradients leaf by leaf in float32, bf16 within stated limits, the
eight shares of an expert layer adding up to the uncut layer, the policy on
the catalog's keys and on the cell's file. Everything is compiled once a
module."""

import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kimi_vl as reference
from deepspeed_tpu.models import llama
from deepspeed_tpu.module_inject.replace_policy import DeepseekV3Policy, policy_for

ROOT = pathlib.Path(__file__).parents[3]
CONFIG = ROOT / "benchmark" / "configs" / "kimi-vl-a3b-instruct-ep8-train1.json"
# tiny widths with the published structure: heads of [32 | 16] and 32, a
# 32-wide latent, 16 experts top-6 scaled by 2.446, two shared experts' width
HF = dict(vocab_size=256, max_position_embeddings=512, hidden_size=64,
          intermediate_size=128, moe_intermediate_size=32, num_hidden_layers=2,
          num_attention_heads=4, n_shared_experts=2, n_routed_experts=16,
          routed_scaling_factor=2.446, kv_lora_rank=32, q_lora_rank=None,
          qk_rope_head_dim=16, v_head_dim=32, qk_nope_head_dim=32,
          topk_method="noaux_tc", n_group=1, topk_group=1, num_experts_per_tok=6,
          moe_layer_freq=1, first_k_dense_replace=1, norm_topk_prob=True,
          scoring_func="sigmoid", seq_aux=True, num_key_value_heads=4,
          hidden_act="silu", rms_norm_eps=1e-5, rope_theta=800000, rope_scaling=None,
          attention_bias=False, tie_word_embeddings=False)
ROWS, SEQ = 2, 64


def _seeded(cfg, seed=3):
    """Seeded float32 parameters with the selection bias drawn (born zero, it
    would not tell the top-6 of ``s + b`` from that of ``s``)."""
    _, params = llama.init_llama(cfg, seed=seed)
    rng = np.random.default_rng(seed)
    for name, lp in params["model"].items():
        if "block_sparse_moe" in lp:
            lp["block_sparse_moe"]["expert_bias"] = jnp.asarray(
                0.05 * rng.standard_normal(cfg.num_local_experts), jnp.float32)
    return params


@pytest.fixture(scope="module")
def small():
    """The uncut small model in float32, its ids, and the reference's step."""
    cfg = dataclasses.replace(DeepseekV3Policy().config_from_hf(HF), dtype=jnp.float32)
    params = _seeded(cfg)
    ids = np.random.default_rng(0).integers(0, HF["vocab_size"], (ROWS, SEQ), dtype=np.int32)
    at = np.stack([np.arange(0, SEQ - 1, 4)] * ROWS)
    want = reference.step_parts(params, ids, HF, at)
    return {"cfg": cfg, "params": params, "ids": jnp.asarray(ids), "at": at, "want": want}


def _program(cfg, params, ids):
    model = llama.LlamaForCausalLM(cfg)

    @jax.jit
    def both(p):
        loss, grads = jax.value_and_grad(
            lambda p: model.apply({"params": p}, ids, labels=ids))(p)
        return loss, grads, model.apply({"params": p}, ids,
                                        mutable=["moe_stats", "mla_stats"])

    loss, grads, (logits, sown) = both(params)
    return float(loss), grads, np.asarray(logits, np.float32), sown


def _rel(a, b):
    return float(np.linalg.norm(np.asarray(a, np.float32) - b) / np.linalg.norm(b))


def test_float32_program_matches_the_reference(small):
    """Loss to 1e-5, the logits to 1e-4, every gradient leaf to 2e-3 (the
    router's kernel: sums of either sign), the counts exactly, the latent's
    statistics to 1e-5; the selection bias has no gradient on either side."""
    loss, grads, logits, sown = _program(small["cfg"], small["params"], small["ids"])
    want = small["want"]
    assert abs(loss - want["ce"]) <= 1e-5 * want["ce"]
    got = np.stack([logits[r, small["at"][r]] for r in range(ROWS)])
    assert _rel(got, want["logits"]) <= 1e-4
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want["grads"])):
        name = jax.tree_util.keystr(path)
        if "expert_bias" in name:
            assert not np.any(g) and not np.any(w)
            continue
        assert _rel(g, w) <= 2e-3, (name, _rel(g, w))
    moe = sown["moe_stats"]["model"]["layers_1"]["block_sparse_moe"]
    assert np.array_equal(np.asarray(moe["expert_counts"]), want["counts"])
    assert int(want["counts"].sum()) == ROWS * SEQ * HF["num_experts_per_tok"]
    mla = sown["mla_stats"]["model"]
    for name in ("latent_rms", "k_rope_rms"):
        mean = np.mean([float(mla[f"layers_{i}"]["self_attn"][name]) for i in range(2)])
        assert abs(mean - want[name]) <= 1e-5 * want[name], name


def test_bf16_program_lies_within_stated_limits_of_the_reference(small):
    """bf16 compute on the same float32 masters (read here: loss 6e-4; logits'
    relative distance by position, median 1.4e-2 and 90th percentile 2.2e-2,
    the worst 0.24 at a flipped near-tie; leaves outside the expert block
    6e-2 to 8.4e-2, inside it 0.11 to 0.22): each limit twice its reading or
    so, and a tenth of what a wrong model reads."""
    cfg = dataclasses.replace(small["cfg"], dtype=jnp.bfloat16)
    loss, grads, logits, _ = _program(cfg, small["params"], small["ids"])
    want = small["want"]
    assert abs(loss - want["ce"]) <= 2e-3 * want["ce"]
    got = np.stack([logits[r, small["at"][r]] for r in range(ROWS)])
    err = (np.linalg.norm(got - want["logits"], axis=-1)
           / np.linalg.norm(want["logits"], axis=-1)).ravel()
    assert np.median(err) <= 3e-2 and np.quantile(err, 0.9) <= 5e-2
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want["grads"])):
        name = jax.tree_util.keystr(path)
        if "expert_bias" in name:
            continue
        routed = "block_sparse_moe" in name or "layers_1']['ffn_norm" in name
        assert _rel(g, w) <= (0.45 if routed else 0.16), (name, _rel(g, w))


@pytest.mark.parametrize("wrong", reference.WRONG)
def test_each_wrong_reference_is_another_model(small, wrong):
    """What the cell's calibration relies on: every ``wrong`` way moves the
    float32 logits by more than a hundred times what the program differs by."""
    got = reference.step_parts(small["params"], np.asarray(small["ids"]), HF, small["at"],
                               wrong={wrong}, gradients=False)
    assert _rel(got["logits"], small["want"]["logits"]) >= 1e-2, wrong


def test_the_eight_shares_add_up_to_the_uncut_layer(small):
    """An expert layer as eight chips hold it: each share's routed part (the
    program's layer less what every chip computes alike), summed, plus the
    attention residual and the shared expert counted once, is the uncut
    reference's layer. The normaliser is over all six chosen in every share."""
    cfg, params = small["cfg"], small["params"]
    lp = params["model"]["layers_1"]
    x = jax.random.normal(jax.random.PRNGKey(5), (ROWS, SEQ, HF["hidden_size"]))
    positions = jnp.broadcast_to(jnp.arange(SEQ)[None], (ROWS, SEQ))
    uncut, _, counts, _ = reference.layer(x, lp, positions, HF)
    moe = lp["block_sparse_moe"]
    alike, *_ = reference.layer(
        x, {**lp, "block_sparse_moe": {**moe, "w2": jnp.zeros_like(moe["w2"])}},
        positions, HF)       # no routed part: the residual, attention, the shared expert
    cos, sin = llama.precompute_rope(cfg.rotary_dim, cfg.max_position_embeddings,
                                     cfg.rope_theta)
    held, total, rows = 2, 0.0, 0
    for share in range(8):
        share_cfg = dataclasses.replace(cfg, moe_experts_held=held, moe_share_index=share)
        mine = {k: moe[k][share * held:(share + 1) * held] for k in ("w1", "w3", "w2")}
        out, sown = llama.LlamaDecoderLayer(share_cfg, 1).apply(
            {"params": {**lp, "block_sparse_moe": {**moe, **mine}}}, x, cos, sin,
            positions, mutable=["moe_stats", "mla_stats"])
        total = total + (out - alike)
        stats = sown["moe_stats"]["block_sparse_moe"]
        assert np.array_equal(np.asarray(stats["expert_counts"]), np.asarray(counts))
        rows += int(stats["rows_held"])
    np.testing.assert_allclose(total + alike, uncut, rtol=2e-4, atol=2e-5)
    assert rows == int(counts.sum())
    assert float(jnp.abs(uncut - alike).max()) > 0.1      # the routed part is not nothing


def test_the_kernels_under_the_model_and_its_recomputation_keep_the_residuals(small):
    """``attn_impl="flash"`` (interpreted here) under ``remat`` with no policy:
    loss and gradients as XLA's path gives them, and the layer keeps the
    kernel's output and log-sum-exp by name: ``ds_remat_kept_bytes`` counts
    tokens x heads x (v_head_dim x 4 B + 4) a layer in float32."""
    from deepspeed_tpu.observability.xla import kept_residual_bytes
    cfg = dataclasses.replace(small["cfg"], attn_impl="flash", remat=True)
    ids = jnp.tile(small["ids"], (1, 2))                    # 128 positions: one tile
    model = llama.LlamaForCausalLM(cfg)
    fn = jax.jit(jax.value_and_grad(lambda p: model.apply({"params": p}, ids, labels=ids)))
    loss, grads = fn(small["params"])
    plain = llama.LlamaForCausalLM(small["cfg"])
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: plain.apply({"params": p}, ids, labels=ids)))(small["params"])
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * float(want_loss)
    for g, w in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want)):
        if np.any(w):
            assert _rel(g, np.asarray(w)) <= 2e-3
    traced = fn.trace(small["params"])
    assert str(traced.jaxpr).count("mla_fwd") >= 2
    tokens = ROWS * 2 * SEQ
    assert kept_residual_bytes(traced.jaxpr) == 2 * tokens * 4 * (32 * 4 + 4)


def test_policy_reads_the_catalogs_keys_and_the_cells_file():
    """``deepseek_v3`` from the catalog row's ``config`` (the published model)
    and from the cell's file (the chip's cut): latent operator in every
    layer, one leading dense layer, the rest experts; the published widths."""
    row = next(json.loads(line) for line in
               open("/opt/skills/guides/model-configs/architectures.jsonl")
               if '"Kimi-VL-A3B-Instruct"' in line) if pathlib.Path(
        "/opt/skills/guides/model-configs/architectures.jsonl").exists() else None
    body = json.loads(CONFIG.read_text())
    published = {**body, **body["published"]}
    for hf, depth, experts, vocab in (
            [(row["config"], 27, 64, 163840)] if row else []) + [
            (published, 27, 64, 163840), (body, 6, 8, 20480)]:
        cfg = policy_for("deepseek_v3").config_from_hf(hf)
        assert cfg.num_hidden_layers == depth and cfg.vocab_size == vocab
        assert [s.ffn for s in cfg.layer_specs] == ["dense"] + ["moe"] * (depth - 1)
        assert {s.operator for s in cfg.layer_specs} == {"latent"}
        assert (cfg.layer_specs[0].ffn_width, cfg.layer_specs[1].ffn_width) == (11264, 1408)
        assert (cfg.hidden_size, cfg.num_attention_heads, cfg.kv_lora_rank) == (2048, 16, 512)
        assert (cfg.head_dim_ - cfg.rotary_dim, cfg.rotary_dim, cfg.v_head_dim) == (128, 64, 128)
        assert (cfg.head_dim_, cfg.rotary_dim, cfg.rope_interleaved) == (192, 64, True)
        assert (cfg.num_local_experts, cfg.num_experts_per_tok) == (experts, 6)
        assert (cfg.moe_scoring, cfg.moe_selection_bias, cfg.moe_renormalize) == (
            "sigmoid", True, True)
        assert (cfg.routed_scaling_factor, cfg.moe_renorm_eps) == (2.446, 1e-20)
        assert (cfg.shared_expert_intermediate_size, cfg.shared_expert_gated) == (2816, False)
        assert not cfg.tie_word_embeddings and cfg.rope_theta == 800000
    if row:     # the file holds every published key as published but the three cut
        for key, value in row["config"].items():
            assert body[key] == value or key in body["reduced"], key
    for key, value in (("q_lora_rank", 1536), ("moe_layer_freq", 2),
                       ("rope_scaling", {"type": "yarn"}), ("scoring_func", "softmax")):
        with pytest.raises(ValueError, match="deepseek_v3"):
            DeepseekV3Policy().config_from_hf({**body, key: value})
    # expert groups are read since PR 48 (they were refused by name before it)
    grouped = DeepseekV3Policy().config_from_hf({**body, "n_group": 8, "topk_group": 4})
    assert (grouped.moe_n_group, grouped.moe_topk_group) == (8, 4)
    assert (cfg.moe_n_group, cfg.moe_topk_group, cfg.attn_output_gate) == (1, 1, None)


def test_the_shared_expert_has_two_forms_and_a_weight_map():
    """Qwen2-MoE's shared expert keeps its sigmoid gate (a parameter of its
    own); DeepSeek's has none. The policy maps every leaf of the tree."""
    cfg = DeepseekV3Policy().config_from_hf(HF)
    gated = dataclasses.replace(cfg, shared_expert_gated=True)
    for c, has_gate in ((gated, True), (cfg, False)):
        shapes = jax.eval_shape(lambda c=c: llama.init_llama(c, seed=0)[1])
        moe = shapes["model"]["layers_1"]["block_sparse_moe"]
        assert ("shared_expert_gate" in moe) == has_gate
        assert moe["shared_expert"]["gate_proj"]["kernel"].shape == (64, 64)
    policy = DeepseekV3Policy()
    policy.bind(cfg)
    mapped = set()
    for layer in range(2):
        gate, experts = policy.moe_map(layer, cfg.num_local_experts)
        mapped |= {path for path, _ in policy.weight_map(layer).values()}
        mapped |= {path for path, _ in gate.values()} | set(experts)
    mapped |= {path for path, _ in policy.global_map(False).values()}
    leaves = {"/".join(str(k.key) for k in path)
              for path, _ in jax.tree_util.tree_flatten_with_path(shapes["model"])[0]}
    assert leaves == mapped
