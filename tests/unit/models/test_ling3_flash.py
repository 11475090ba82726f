"""Ling-3.0-flash's blocks (``bailing_hybrid``: Kimi Delta Attention layers,
every ``layer_group_size``-th one head-gated latent attention, a group-limited
sigmoid router) in ``models/llama.py`` against the plain reference
``benchmark/reference/ling3_flash.py`` on seeded weights at a small size:
logits, loss, gradients leaf by leaf, expert and group counts and the linear
layers' statistics in float32, bf16 within stated limits; the head gate on and
off in ``LatentAttention`` with Kimi-VL's parameters unchanged; group-limited
routing against a count by hand; four shares over two groups adding up to the
uncut layer; the chunk kernels under the model's recomputation; the policy on
the catalog's row and on the cell's file. Everything is compiled once a
module."""

import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import ling3_flash as reference
from deepspeed_tpu.models import llama
from deepspeed_tpu.module_inject.replace_policy import (BailingHybridPolicy,
                                                        DeepseekV3Policy, policy_for)

ROOT = pathlib.Path(__file__).parents[3]
CONFIG = ROOT / "benchmark" / "configs" / "ling-3.0-flash-ep64-train1.json"
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
# tiny widths with the published structure: two KDA layers and an MLA layer
# (a group of three), a leading dense layer, 16 experts in 4 groups of which 2
# are kept, top-4
HF = dict(model_type="bailing_hybrid", vocab_size=256, max_position_embeddings=512,
          hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
          moe_shared_expert_intermediate_size=32, num_hidden_layers=3, layer_group_size=3,
          first_k_dense_replace=1, num_attention_heads=2, num_key_value_heads=2,
          head_dim=32, kv_lora_rank=32, q_lora_rank=None, qk_nope_head_dim=32,
          qk_rope_head_dim=16, qk_head_dim=48, rotary_dim=16, v_head_dim=32,
          num_experts=16, num_experts_per_tok=4, num_shared_experts=1, n_group=4,
          topk_group=2, norm_topk_prob=True, routed_scaling_factor=2.5,
          score_function="sigmoid", topk_method="noaux_tc",
          moe_router_enable_expert_bias=True, short_conv_kernel_size=4,
          kda_safe_gate=True, kda_lower_bound=-5, no_kda_lora=True, linear_silu=True,
          use_qk_norm=True, hidden_act="silu", rms_norm_eps=1e-6, rope_theta=6e6,
          rope_interleave=True, rope_scaling=None, tie_word_embeddings=False,
          mtp_loss_scaling_factor=0, num_nextn_predict_layers=1, kda_chunk_size=16)
ROWS, SEQ = 2, 48
KDA_LEAVES = ("f_proj", "A_log", "dt_bias", "b_proj", "q_conv_weight", "k_conv_weight",
              "v_conv_weight", "g_proj", "o_norm")


def _seeded(cfg, seed=3):
    """Seeded float32 parameters, the selection bias drawn (born zero, the
    choice by ``s + b`` would be the choice by ``s``)."""
    _, params = llama.init_llama(cfg, seed=seed, seq_len=SEQ)
    rng = np.random.default_rng(seed)
    for lp in params["model"].values():
        if "block_sparse_moe" in lp:
            moe = lp["block_sparse_moe"]
            moe["expert_bias"] = jnp.asarray(
                0.05 * rng.standard_normal(moe["expert_bias"].shape), jnp.float32)
    return params


def _config(hf=HF, **over):
    cfg = BailingHybridPolicy().config_from_hf(hf)
    return dataclasses.replace(cfg, dtype=jnp.float32,
                               kda_chunk_size=hf.get("kda_chunk_size", 64), **over)


@pytest.fixture(scope="module")
def small():
    """The uncut small model in float32, its ids, and the reference's step."""
    cfg = _config()
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
                                        mutable=["moe_stats", "kda_stats"])

    loss, grads, (logits, sown) = both(params)
    return float(loss), grads, np.asarray(logits, np.float32), sown


def _rel(a, b):
    return float(np.linalg.norm(np.asarray(a, np.float32) - b) / np.linalg.norm(b))


def _kernel_calls(closed_jaxpr):
    from jax._src import core
    calls = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                calls[eqn.params["name"]] = calls.get(eqn.params["name"], 0) + 1
            for sub in core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(closed_jaxpr.jaxpr)
    return calls


def _sown_sums(sown):
    moe = [lp["block_sparse_moe"] for lp in sown["moe_stats"]["model"].values()]
    kda = [lp["self_attn"] for lp in sown["kda_stats"]["model"].values()]
    return (sum(np.asarray(m["expert_counts"]) for m in moe),
            sum(np.asarray(m["group_counts"]) for m in moe),
            {"state_absmax": max(float(k["state_absmax"]) for k in kda),
             "decay_mean": float(np.mean([float(k["decay_mean"]) for k in kda])),
             "beta_mean": float(np.mean([float(k["beta_mean"]) for k in kda]))})


def test_float32_program_matches_the_reference(small):
    """Loss to 1e-5, the logits to 1e-4, every gradient leaf to 2e-3 (the new
    leaves are all there: nine a KDA layer, the MLA layer's gate), the
    router's expert and group counts exactly, the linear layers' statistics
    to 1e-5."""
    loss, grads, logits, sown = _program(small["cfg"], small["params"], small["ids"])
    want = small["want"]
    assert abs(loss - want["ce"]) <= 1e-5 * want["ce"]
    got = np.stack([logits[r, small["at"][r]] for r in range(ROWS)])
    assert _rel(got, want["logits"]) <= 1e-4
    names = []
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(want["grads"])):
        name = jax.tree_util.keystr(path)
        names.append(name)
        if "expert_bias" in name:
            assert not np.any(g) and not np.any(w), name
            continue
        assert np.any(w) and _rel(g, w) <= 2e-3, (name, _rel(g, w))
    for layer in (0, 1):
        for leaf in KDA_LEAVES:
            assert any(f"layers_{layer}']['self_attn']['{leaf}" in n for n in names), leaf
    assert any("layers_2']['self_attn']['gate_proj" in n for n in names)
    counts, groups, stats = _sown_sums(sown)
    assert np.array_equal(counts, want["counts"]) and counts.sum() == ROWS * SEQ * 4 * 2
    assert np.array_equal(groups, want["group_counts"]) and groups.sum() == ROWS * SEQ * 2 * 2
    for name, value in stats.items():
        assert abs(value - want[name]) <= 1e-5 * abs(want[name]), name


def test_bf16_program_lies_within_stated_limits_of_the_reference(small):
    """bf16 compute on the same float32 masters: each limit about twice its
    reading at this size (logits' relative distance by position, median 2.5e-2
    and 90th percentile 0.40: with 4 of 16 experts chosen and weighted 2.5 a
    flipped near-tie is a large part of a token's stream)."""
    cfg = dataclasses.replace(small["cfg"], dtype=jnp.bfloat16)
    loss, grads, logits, sown = _program(cfg, small["params"], small["ids"])
    want = small["want"]
    assert abs(loss - want["ce"]) <= 6e-3 * want["ce"]
    got = np.stack([logits[r, small["at"][r]] for r in range(ROWS)])
    err = (np.linalg.norm(got - want["logits"], axis=-1)
           / np.linalg.norm(want["logits"], axis=-1)).ravel()
    assert np.median(err) <= 5e-2 and np.quantile(err, 0.9) <= 0.8
    worst = {False: 0.0, True: 0.0}
    for (path, g), w in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree_util.tree_leaves(want["grads"])):
        name = jax.tree_util.keystr(path)
        if np.any(w):
            routed = "block_sparse_moe" in name or "ffn_norm" in name
            worst[routed] = max(worst[routed], _rel(g, w))
    # read 0.32 outside the expert blocks and 0.37 inside them
    assert worst[False] <= 0.6 and worst[True] <= 0.7, worst
    _, _, stats = _sown_sums(sown)
    assert abs(stats["decay_mean"] - want["decay_mean"]) <= 2e-2 * want["decay_mean"]
    assert 0.8 <= stats["state_absmax"] / want["state_absmax"] <= 1.25


@pytest.mark.parametrize("wrong", reference.WRONG)
def test_each_wrong_reference_is_another_model(small, wrong):
    """What the cell's calibration relies on: every ``wrong`` way moves the
    float32 logits by far more than the float32 program differs by (1e-4);
    the rounded state and fp8 least."""
    got = reference.step_parts(small["params"], np.asarray(small["ids"]), HF, small["at"],
                               wrong={wrong}, gradients=False)
    least = {"bf16_state": 1e-3, "fp8": 1e-2}.get(wrong, 2e-2)
    assert _rel(got["logits"], small["want"]["logits"]) >= least, wrong


def test_the_head_gate_is_a_field_and_off_leaves_kimi_vls_operator_as_it_was():
    """``attn_output_gate=None`` (every ``deepseek_v3`` configuration): the
    operator's parameters are the five Kimi-VL has and its program has no gate;
    ``"head"`` adds one ``hidden x heads`` matrix and multiplies each head's
    output by ``sigmoid(gate)`` before ``o_proj``; groups of 1 add nothing to
    the router's program either."""
    hf = {**HF, "model_type": "deepseek_v3", "n_routed_experts": 16, "n_shared_experts": 1,
          "n_group": 1, "topk_group": 1, "scoring_func": "sigmoid", "num_hidden_layers": 2}
    plain = dataclasses.replace(DeepseekV3Policy().config_from_hf(hf), dtype=jnp.float32)
    assert plain.attn_output_gate is None and (plain.moe_n_group, plain.moe_topk_group) == (1, 1)
    gated = dataclasses.replace(plain, attn_output_gate="head")
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 64))
    cos, sin = llama.precompute_rope(16, 64, 6e6)
    positions = jnp.arange(16)[None]
    p_plain = llama.unbox_params(llama.LatentAttention(plain).init(
        jax.random.PRNGKey(1), x, cos, sin, positions))["params"]
    p_gated = llama.unbox_params(llama.LatentAttention(gated).init(
        jax.random.PRNGKey(1), x, cos, sin, positions))["params"]
    assert sorted(p_plain) == ["kv_a_layernorm", "kv_a_proj_with_mqa", "kv_b_proj", "o_proj",
                               "q_proj"]
    assert sorted(set(p_gated) - set(p_plain)) == ["gate_proj"]
    assert p_gated["gate_proj"]["kernel"].shape == (64, 2)
    for name in p_plain:
        for a, b in zip(jax.tree_util.tree_leaves(p_plain[name]),
                        jax.tree_util.tree_leaves(p_gated[name])):
            np.testing.assert_array_equal(a, b)
    text = str(jax.make_jaxpr(lambda p: llama.LatentAttention(plain).apply(
        {"params": p}, x, cos, sin, positions))(p_plain))
    assert "logistic" not in text
    out = llama.LatentAttention(plain).apply({"params": p_plain}, x, cos, sin, positions)
    # a gate of zeros halves every head: sigmoid(0) = 1/2, and o_proj is linear
    p_gated["gate_proj"]["kernel"] = jnp.zeros_like(p_gated["gate_proj"]["kernel"])
    half = llama.LatentAttention(gated).apply({"params": p_gated}, x, cos, sin, positions)
    np.testing.assert_allclose(half, 0.5 * out, rtol=1e-5, atol=1e-6)
    moe = llama.LlamaMoEBlock(dataclasses.replace(plain, intermediate_size=32))
    p = llama.unbox_params(moe.init(jax.random.PRNGKey(2), x))["params"]
    _, sown = moe.apply({"params": p}, x, mutable=["moe_stats"])
    assert "group_counts" not in sown["moe_stats"]
    with pytest.raises(ValueError, match="attn_output_gate"):
        llama.LatentAttention(dataclasses.replace(plain, attn_output_gate="token")).init(
            jax.random.PRNGKey(1), x, cos, sin, positions)


def test_group_limited_routing_against_a_count_by_hand():
    """16 experts in 4 groups, 2 kept, top-4: token by token in numpy, a
    group's score the sum of its two largest biased scores, the choice the 4
    largest biased scores inside the kept groups, the weights from the
    unbiased scores over their sum times 2.5."""
    cfg = dataclasses.replace(_config(), intermediate_size=32)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 40, 64))
    moe = llama.LlamaMoEBlock(cfg)
    params = llama.unbox_params(moe.init(jax.random.PRNGKey(5), x))["params"]
    rng = np.random.default_rng(1)
    params["expert_bias"] = jnp.asarray(0.2 * rng.standard_normal(16), jnp.float32)
    _, sown = moe.apply({"params": params}, x, mutable=["moe_stats"])
    logits = np.asarray(x[0], np.float64) @ np.asarray(params["gate"]["kernel"], np.float64)
    s = 1.0 / (1.0 + np.exp(-logits))
    biased = s + np.asarray(params["expert_bias"], np.float64)
    counts, groups = np.zeros(16, int), np.zeros(4, int)
    for t in range(40):
        score = [np.sort(biased[t, g * 4:(g + 1) * 4])[-2:].sum() for g in range(4)]
        kept = np.argsort(score)[-2:]
        groups[kept] += 1
        inside = [e for e in range(16) if e // 4 in kept]
        chosen = sorted(inside, key=lambda e: biased[t, e])[-4:]
        counts[chosen] += 1
    stats = sown["moe_stats"]
    assert np.array_equal(np.asarray(stats["expert_counts"]), counts)
    assert np.array_equal(np.asarray(stats["group_counts"]), groups)
    with pytest.raises(ValueError, match="groups"):
        llama.LlamaMoEBlock(dataclasses.replace(cfg, moe_scoring="softmax",
                                                moe_selection_bias=False)).init(
            jax.random.PRNGKey(5), x)


def test_four_shares_over_two_groups_add_up_to_the_uncut_layer():
    """An expert layer as four chips hold it (8 experts in 2 groups of which
    1 is kept, 2 experts a chip): each share's routed part (the program's
    layer less what every chip computes alike: the residual, the KDA mixer
    and the shared expert), summed, plus that part counted once, is the uncut
    reference's layer."""
    hf = {**HF, "num_experts": 8, "n_group": 2, "topk_group": 1, "num_experts_per_tok": 2}
    cfg = _config(hf)
    params = _seeded(cfg, seed=7)
    lp = params["model"]["layers_1"]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, SEQ, 64))
    uncut, _, (counts, groups) = reference.layer(x[0], lp, hf)
    moe = lp["block_sparse_moe"]
    alike, _, _ = reference.layer(
        x[0], {**lp, "block_sparse_moe": {**moe, "w2": jnp.zeros_like(moe["w2"])}}, hf)
    cos, sin = llama.precompute_rope(16, 64, 6e6)
    positions = jnp.arange(SEQ)[None]
    held, total, rows = 2, 0.0, 0
    for share in range(4):
        share_cfg = dataclasses.replace(cfg, moe_experts_held=held, moe_share_index=share)
        mine = {k: moe[k][share * held:(share + 1) * held] for k in ("w1", "w3", "w2")}
        out, sown = llama.LlamaDecoderLayer(share_cfg, 1).apply(
            {"params": {**lp, "block_sparse_moe": {**moe, **mine}}}, x, cos, sin,
            positions, mutable=["moe_stats", "kda_stats"])
        total = total + (out[0] - alike)
        stats = sown["moe_stats"]["block_sparse_moe"]
        assert np.array_equal(np.asarray(stats["expert_counts"]), np.asarray(counts))
        assert np.array_equal(np.asarray(stats["group_counts"]), np.asarray(groups))
        rows += int(stats["rows_held"])
    np.testing.assert_allclose(total + alike, uncut, rtol=2e-4, atol=2e-5)
    assert rows == int(counts.sum()) == SEQ * 2 and int(groups.sum()) == SEQ
    assert float(jnp.abs(uncut - alike).max()) > 0.05      # the routed part is not nothing


@pytest.mark.parametrize("groups,kept,fallback", [(2, 1, 0), (1, 1, 1)],
                         ids=["inside_a_group", "no_groups"])
def test_a_share_inside_a_group_has_static_rows_for_every_token_that_keeps_it(
        groups, kept, fallback):
    """8 experts, 2 held, top-2, a bias that turns every token to experts
    0-3 (group 0 of 2, one kept): the share gets about twice its even share.
    With groups the block reckons its static rows from that (``crowding`` =
    n_group / topk_group) and takes no pass over all rows; the same choice
    without groups outruns twice the even share and takes it."""
    from deepspeed_tpu.ops.grouped_matmul import share_rows
    cfg = dataclasses.replace(
        _config({**HF, "num_experts": 8, "num_experts_per_tok": 2, "n_group": groups,
                 "topk_group": kept}),
        intermediate_size=32, moe_experts_held=2, moe_share_index=0)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 2 * SEQ, 64))
    moe = llama.LlamaMoEBlock(cfg)
    params = llama.unbox_params(moe.init(jax.random.PRNGKey(5), x))["params"]
    params["expert_bias"] = jnp.where(jnp.arange(8) < 4, 2.0, 0.0)
    _, sown = moe.apply({"params": params}, x, mutable=["moe_stats"])
    stats = sown["moe_stats"]
    assert int(stats["expert_counts"][:4].sum()) == 2 * SEQ * 2
    assert int(stats["rows_held"]) > share_rows(2 * SEQ * 2, 2, 8)
    assert int(stats["share_fallback"]) == fallback


@pytest.mark.parametrize("room", [0, 2], ids=["no_room", "room_for_both"])
def test_the_chunk_kernels_under_the_model_and_its_recomputation(room):
    """Heads of 128 (the kernels' width; interpreted here) under ``remat``
    with no policy: loss and gradients as the recurrence gives them; a layer
    whose plan keeps ``ds.kda.scan`` runs ``kda_chunk_fwd`` once a step, one
    without runs it again in its backward; ``ds_remat_kept_bytes`` counts the
    output and the float32 chunk states."""
    from deepspeed_tpu.observability.xla import kept_residual_bytes
    from deepspeed_tpu.ops import remat
    from deepspeed_tpu.ops.kda import scan_bytes
    hf = {**HF, "head_dim": 128, "num_attention_heads": 1, "num_key_value_heads": 1,
          "num_hidden_layers": 2, "kda_chunk_size": 64}
    seq = 128
    plain = _config(hf)
    params = _seeded(plain)
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 256, (1, seq), dtype=np.int32))
    patch = pytest.MonkeyPatch()
    try:
        patch.setattr(llama, "interpret_kernels", lambda: False)
        model = llama.LlamaForCausalLM(plain)
        want_loss, want = jax.jit(jax.value_and_grad(
            lambda p: model.apply({"params": p}, ids, labels=ids)))(params)
    finally:
        patch.undo()
    scan = scan_bytes(1, seq, 1, 128, 128, 64, 4)
    try:
        patch.setattr(remat, "step_reserve_bytes", lambda *a: 0)
        patch.setattr(remat, "device_memory", lambda: (10**9, 10**9 - room * 10**8))
        remat.forget_plans()
        model = llama.LlamaForCausalLM(dataclasses.replace(plain, remat=True))
        fn = jax.jit(jax.value_and_grad(lambda p: model.apply({"params": p}, ids, labels=ids)))
        loss, grads = fn(params)
        traced = fn.trace(params)
    finally:
        patch.undo()
        remat.forget_plans()
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * float(want_loss)
    for g, w in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want)):
        if np.any(w):
            assert _rel(g, np.asarray(w)) <= 2e-3
    calls = _kernel_calls(traced.jaxpr)
    assert calls["kda_chunk_bwd"] == 2
    assert calls["kda_chunk_fwd"] == (2 if room else 4), calls
    kept = kept_residual_bytes(traced.jaxpr)
    less = kept_residual_bytes(traced.jaxpr, tuple(n for n in remat.KEPT_NAMES
                                                   if n != remat.KDA_SCAN))
    assert kept - less == (2 * scan if room else 0)


@pytest.mark.parametrize("head_dim,heads,block", [(128, 2, 2), (32, 2, None)],
                         ids=["the_kernels_two_heads_a_step", "the_recurrence"])
def test_the_engine_says_which_block_of_heads_the_chunk_kernels_took(head_dim, heads, block):
    """``head_block`` and ``grid_steps`` ride in ``kda_stats`` beside the
    largest ``|S|`` where the chunk kernels run (interpreted here at heads of
    128), and come out as ``ds_kda_head_block`` / ``ds_kda_grid_steps`` with
    the step's publish; where the recurrence runs neither exists.
    ``fused_rows`` says who made the row norms and the beta products: 1.0
    where they rode inside the kernels, 0.0 where XLA made them around the
    recurrence, and ``ds_kda_fused_rows`` shows it either way."""
    import deepspeed_tpu
    from deepspeed_tpu.comm import MeshContext, reset_mesh_context, set_mesh_context
    from deepspeed_tpu.observability import get_registry
    hf = {**HF, "head_dim": head_dim, "num_attention_heads": heads,
          "num_key_value_heads": heads, "num_hidden_layers": 2, "kda_chunk_size": 64}
    cfg = _config(hf)
    ids = jnp.asarray(np.random.default_rng(4).integers(0, 256, (1, 128), dtype=np.int32))
    reset_mesh_context()
    set_mesh_context(MeshContext.create(devices=jax.devices()[:1]))
    reg = get_registry()
    reg.reset()
    try:
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=llama.LlamaForCausalLM(cfg), model_parameters=_seeded(cfg),
            config={"train_batch_size": 1, "steps_per_print": 0,
                    "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}}})
        engine.train_batch(iter([(ids, ids)]))
        stats = engine.kda_stats()
        engine.train_batch(iter([(ids, ids)]))      # publishes the step before
        assert float(stats["state_absmax"]) > 0.0
        assert float(stats["fused_rows"]) == (0.0 if block is None else 1.0)
        assert reg.get("ds_kda_fused_rows").value == float(stats["fused_rows"])
        if block is None:
            assert set(stats) == {"state_absmax", "decay_mean", "beta_mean", "fused_rows"}
            unset = reg.get("ds_kda_head_block")    # the registry is the process's
            assert unset is None or unset.value == 0
        else:
            steps = heads // block * (128 // 64)
            assert (float(stats["head_block"]), float(stats["grid_steps"])) == (block, steps)
            assert reg.get("ds_kda_head_block").value == block
            assert reg.get("ds_kda_grid_steps").value == steps
        assert reg.get("ds_kda_state_absmax").value == pytest.approx(
            float(stats["state_absmax"]))
    finally:
        reset_mesh_context()


def _row():
    for line in CATALOG.read_text().splitlines():
        row = json.loads(line)
        if row["name"] == "Ling-3.0-flash":
            return row
    raise AssertionError("no Ling-3.0-flash row in the catalog")


def test_the_policy_reads_the_catalog_row_and_refuses_what_is_not_built():
    """The published config: its 42 layers hold clamped SwiGLUs (layers 35-41
    of the experts, 34-41 of the shared expert), refused by name; its first 34
    read as five KDA layers to one MLA layer, two leading dense layers, the
    router 512 wide in 8 groups of which 4 are kept, top-8, 2.5; an MTP weight
    above 0, an unbounded gate and a low-rank gate are refused by name."""
    row = _row()["config"]
    assert policy_for("bailing_hybrid").__class__ is BailingHybridPolicy
    with pytest.raises(ValueError, match="swiglu_limit_list"):
        BailingHybridPolicy().config_from_hf(row)
    cfg = BailingHybridPolicy().config_from_hf({**row, "num_hidden_layers": 34})
    kinds = [s.operator for s in cfg.layer_specs]
    assert kinds == (["kda"] * 5 + ["latent"]) * 5 + ["kda"] * 4
    assert [s.ffn for s in cfg.layer_specs] == ["dense"] * 2 + ["moe"] * 32
    assert {s.ffn_width for s in cfg.layer_specs} == {6144, 768}
    assert (cfg.num_local_experts, cfg.num_experts_per_tok, cfg.moe_n_group,
            cfg.moe_topk_group, cfg.routed_scaling_factor) == (512, 8, 8, 4, 2.5)
    assert (cfg.num_attention_heads, cfg.kda_head_dim, cfg.kda_d_conv, cfg.kda_gate_floor) == (
        32, 128, 4, -5.0)
    assert (cfg.head_dim_, cfg.rotary_dim, cfg.v_head_dim, cfg.kv_lora_rank,
            cfg.attn_output_gate, cfg.rope_interleaved) == (192, 64, 128, 512, "head", True)
    assert cfg.shared_expert_intermediate_size == 768 and not cfg.shared_expert_gated
    ok = {**row, "num_hidden_layers": 6}
    for key, value in (("mtp_loss_scaling_factor", 0.1), ("kda_safe_gate", False),
                       ("use_kda_lora", True), ("use_nGPT", True)):
        with pytest.raises(ValueError, match=key):
            BailingHybridPolicy().config_from_hf({**ok, key: value})


def test_the_cells_file_is_the_row_but_for_what_it_lists_and_counts_its_parameters():
    """Every key of the row stands in the file as published but for
    ``reduced``; ``layer_types`` gives published layers 1-6; the model built
    from it has the parameters ``benchmark/ling3_cost.py`` counts:
    767,009,056."""
    import importlib
    from benchmark import ling3_cost
    row, config = _row()["config"], json.loads(CONFIG.read_text())
    assert config["source"] == _row()["source_url"]
    for key, value in row.items():
        if key in config["reduced"]:
            assert config[key] != value and config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert config["layer_types"] == ["kda"] * 4 + ["mla", "kda"] and config["layer_offset"] == 1
    assert "each layer shared over 64 chips" in config["deployment"]
    cfg = importlib.import_module(
        "benchmark.runners.train_steps_ling3_flash").model_config(config)
    assert [s.operator + "+" + s.ffn for s in cfg.layer_specs] == [
        "kda+dense", "kda+moe", "kda+moe", "kda+moe", "latent+moe", "kda+moe"]
    assert (cfg.num_local_experts, cfg.experts_held_, cfg.vocab_size) == (512, 8, 19648)
    model = llama.LlamaForCausalLM(cfg)
    shapes = jax.eval_shape(lambda: llama.unbox_params(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"])
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert n == ling3_cost.param_count(config) == 767_009_056
    assert ling3_cost.bytes_at_rest(config) == 12 * n
