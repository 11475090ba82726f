"""Qwen3-Next's blocks (``qwen3_next``: Gated DeltaNet layers, every
``full_attention_interval``-th one gated softmax attention, a softmax router
beside a gated shared expert) in ``models/llama.py`` against the plain
reference ``benchmark/reference/qwen3_next.py`` on seeded weights at a small
size: logits, loss, gradients leaf by leaf, expert counts, the linear layers'
statistics and the attention gate's mean in float32, bf16 within stated
limits; each wrong reference another model; sixteen shares adding up to the
uncut expert layer; the chunk kernels under the model's recomputation; what
the engine says; the policy on the catalog's row, on the cell's file and round
an HF-named tree. Everything is compiled once a module."""

import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import qwen3_next as reference
from deepspeed_tpu.models import llama
from deepspeed_tpu.module_inject.replace_policy import Qwen3NextPolicy, policy_for

ROOT = pathlib.Path(__file__).parents[3]
CONFIG = ROOT / "benchmark" / "configs" / "qwen3-next-80b-a3b-ep16-train1.json"
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
# tiny widths with the published structure: three GDN layers (2 key heads under
# 4 value heads) and a gated attention layer (4 query heads on 2 key heads, a
# quarter of a head rotated), 16 experts, top-4, a gated shared expert
HF = dict(model_type="qwen3_next", vocab_size=256, max_position_embeddings=512,
          hidden_size=64, intermediate_size=160, moe_intermediate_size=32,
          shared_expert_intermediate_size=32, num_hidden_layers=4,
          full_attention_interval=4, num_attention_heads=4, num_key_value_heads=2,
          head_dim=32, partial_rotary_factor=0.25, rope_theta=1e7, rope_scaling=None,
          linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
          linear_value_head_dim=16, linear_conv_kernel_dim=4, num_experts=16,
          num_experts_per_tok=4, norm_topk_prob=True, decoder_sparse_step=1,
          mlp_only_layers=[], hidden_act="silu", rms_norm_eps=1e-6,
          tie_word_embeddings=False, gdn_chunk_size=16)
ROWS, SEQ = 2, 48
GDN_LEAVES = ("in_proj_qkvz", "in_proj_ba", "conv_weight", "A_log", "dt_bias", "norm_weight",
              "out_proj")
ATTN_LEAVES = ("q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm")


def _seeded(cfg, seed=3):
    """Seeded float32 parameters with the zero-centred norms' weights moved
    off zero and the GDN norm's off one (seeded as they are born, ``1 + w`` and
    ``w`` would not tell ``w`` from ``1 + w`` apart by a gradient)."""
    _, params = llama.init_llama(cfg, seed=seed, seq_len=SEQ)
    rng = np.random.default_rng(seed)

    def moved(path, leaf):
        name = jax.tree_util.keystr(path)
        if name.endswith("['weight']") or "norm_weight" in name:
            return leaf + jnp.asarray(0.1 * rng.standard_normal(leaf.shape), jnp.float32)
        return leaf

    return jax.tree_util.tree_map_with_path(moved, params)


def _config(hf=HF, **over):
    cfg = Qwen3NextPolicy().config_from_hf(hf)
    return dataclasses.replace(cfg, dtype=jnp.float32,
                               gdn_chunk_size=hf.get("gdn_chunk_size", 64), **over)


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
                                        mutable=["moe_stats", "gdn_stats", "attn_stats"])

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
    gdn = [lp["self_attn"] for lp in sown["gdn_stats"]["model"].values()]
    attn = [lp["self_attn"] for lp in sown["attn_stats"]["model"].values()]
    return (sum(np.asarray(m["expert_counts"]) for m in moe),
            {"state_absmax": max(float(k["state_absmax"]) for k in gdn),
             "decay_mean": float(np.mean([float(k["decay_mean"]) for k in gdn])),
             "beta_mean": float(np.mean([float(k["beta_mean"]) for k in gdn])),
             "gate_mean": float(np.mean([float(k["gate_mean"]) for k in attn]))})


def test_float32_program_matches_the_reference(small):
    """Loss to 1e-5, the logits to 1e-4, every gradient leaf to 2e-3 (the new
    leaves are all there: seven a GDN layer, the attention layer's doubled
    ``q_proj`` and its two norms, the shared expert's gate), the router's
    counts exactly, the statistics to 1e-5."""
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
        assert np.any(w) and _rel(g, w) <= 2e-3, (name, _rel(g, w))
    for layer in (0, 1, 2):
        for leaf in GDN_LEAVES:
            assert any(f"layers_{layer}']['self_attn']['{leaf}" in n for n in names), leaf
    for leaf in ATTN_LEAVES:
        assert any(f"layers_3']['self_attn']['{leaf}" in n for n in names), leaf
    assert sum("shared_expert_gate" in n for n in names) == 4
    q_proj = small["params"]["model"]["layers_3"]["self_attn"]["q_proj"]["kernel"]
    assert q_proj.shape == (64, 2 * 4 * 32)
    counts, stats = _sown_sums(sown)
    assert np.array_equal(counts, want["counts"]) and counts.sum() == ROWS * SEQ * 4 * 4
    for name, value in stats.items():
        assert abs(value - want[name]) <= 1e-5 * abs(want[name]), name


def test_bf16_program_lies_within_stated_limits_of_the_reference(small):
    """bf16 compute on the same float32 masters: each limit about a third
    over its reading at this size (the chip's calibration holds the cell's)."""
    cfg = dataclasses.replace(small["cfg"], dtype=jnp.bfloat16)
    loss, grads, logits, sown = _program(cfg, small["params"], small["ids"])
    want = small["want"]
    assert abs(loss - want["ce"]) <= 6e-3 * want["ce"]
    got = np.stack([logits[r, small["at"][r]] for r in range(ROWS)])
    err = (np.linalg.norm(got - want["logits"], axis=-1)
           / np.linalg.norm(want["logits"], axis=-1)).ravel()
    assert np.median(err) <= 5e-2 and np.quantile(err, 0.9) <= 0.4, (
        np.median(err), np.quantile(err, 0.9))
    errs = {jax.tree_util.keystr(path): _rel(g, w) for (path, g), w in zip(
        jax.tree_util.tree_flatten_with_path(grads)[0],
        jax.tree_util.tree_leaves(want["grads"]))}
    # 16 experts, top-4, widths of 64: flipped near-ties in four routers are a
    # large part of every gradient upstream of them. Read: the median leaf
    # 0.37, the worst but the gate's 0.99 (the first router's kernel), the
    # first layer's A_log and dt_bias 1.9 (a sum of differences along 48
    # tokens of four heads)
    gate = {n: e for n, e in errs.items() if n.endswith(("['A_log']", "['dt_bias']"))}
    rest = {n: e for n, e in errs.items() if n not in gate}
    assert np.median(list(errs.values())) <= 0.6 and max(rest.values()) <= 1.3, (
        sorted(errs.items(), key=lambda kv: -kv[1])[:6])
    assert len(gate) == 6 and all(np.isfinite(e) for e in gate.values())
    _, stats = _sown_sums(sown)
    assert abs(stats["decay_mean"] - want["decay_mean"]) <= 2e-2 * want["decay_mean"]
    assert 0.8 <= stats["state_absmax"] / want["state_absmax"] <= 1.25


@pytest.mark.parametrize("wrong", reference.WRONG)
def test_each_wrong_reference_is_another_model(small, wrong):
    """What the cell's calibration relies on: every ``wrong`` way moves the
    float32 logits by far more than the float32 program differs by (1e-4);
    the rounded state least."""
    got = reference.step_parts(small["params"], np.asarray(small["ids"]), HF, small["at"],
                               wrong={wrong}, gradients=False)
    moved = _rel(got["logits"], small["want"]["logits"])
    assert not moved <= {"bf16_state": 5e-4}.get(wrong, 5e-3), (wrong, moved)


def test_the_gated_attention_is_a_field_and_off_leaves_the_operator_as_it_was():
    """``attn_output_gate=None`` (every other configuration): ``q_proj`` is
    ``heads x head`` wide and the program has no gate; ``"elementwise"``
    doubles it (the queries, then the gates) and multiplies every output value
    by ``sigmoid(gate)`` before ``o_proj``; a per-head norm under
    ``norm_plus_one`` is zero-centred; ``"head"`` belongs to the latent
    operator."""
    cfg = dataclasses.replace(_config(), attn_output_gate=None)
    gated = dataclasses.replace(cfg, attn_output_gate="elementwise")
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 64))
    cos, sin = llama.precompute_rope(8, 64, 1e7)
    positions = jnp.arange(16)[None]
    args = (x, cos, sin, positions)
    p_plain = llama.unbox_params(llama.LlamaAttention(cfg, 3).init(
        jax.random.PRNGKey(1), *args))["params"]
    p_gated = llama.unbox_params(llama.LlamaAttention(gated, 3).init(
        jax.random.PRNGKey(1), *args))["params"]
    assert p_plain["q_proj"]["kernel"].shape == (64, 128)
    assert p_gated["q_proj"]["kernel"].shape == (64, 256)
    assert not np.any(p_plain["q_norm"]["weight"])          # zero-centred: born 0
    text = str(jax.make_jaxpr(lambda p: llama.LlamaAttention(cfg, 3).apply(
        {"params": p}, *args))(p_plain))
    assert "logistic" not in text
    out = llama.LlamaAttention(cfg, 3).apply({"params": p_plain}, *args)
    # the plain queries and a gate of zeros: sigmoid(0) = 1/2, and o_proj is linear
    p_gated = {**p_plain, "q_proj": {"kernel": jnp.concatenate(
        [p_plain["q_proj"]["kernel"], jnp.zeros((64, 128))], axis=1)}}
    half, sown = llama.LlamaAttention(gated, 3).apply({"params": p_gated}, *args,
                                                      mutable=["attn_stats"])
    np.testing.assert_allclose(half, 0.5 * out, rtol=1e-5, atol=1e-6)
    assert float(sown["attn_stats"]["gate_mean"]) == 0.5
    with pytest.raises(ValueError, match="attn_output_gate"):
        llama.LlamaAttention(dataclasses.replace(cfg, attn_output_gate="head"), 3).init(
            jax.random.PRNGKey(1), *args)


def test_sixteen_shares_add_up_to_the_uncut_expert_layer():
    """An expert layer as sixteen chips hold it (32 experts, 2 a chip, top-4):
    each share's routed part (the program's block less what every chip
    computes alike: the gated shared expert), summed, plus that part counted
    once, is the uncut reference's block; every share counts the same
    assignments over the router's width, and their rows add up to all."""
    hf = {**HF, "num_experts": 32}
    cfg = dataclasses.replace(_config(hf), intermediate_size=32)
    params = _seeded(_config(hf), seed=7)
    moe = params["model"]["layers_1"]["block_sparse_moe"]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, SEQ, 64))
    uncut, counts = reference.moe_block(x[0], moe, hf)
    alike, _ = reference.moe_block(x[0], {**moe, "w2": jnp.zeros_like(moe["w2"])}, hf)
    held, total, rows = 2, 0.0, 0
    for share in range(16):
        share_cfg = dataclasses.replace(cfg, moe_experts_held=held, moe_share_index=share)
        mine = {k: moe[k][share * held:(share + 1) * held] for k in ("w1", "w3", "w2")}
        out, sown = llama.LlamaMoEBlock(share_cfg).apply(
            {"params": {**moe, **mine}}, x, mutable=["moe_stats"])
        total = total + (out[0] - alike)
        stats = sown["moe_stats"]
        assert np.array_equal(np.asarray(stats["expert_counts"]), np.asarray(counts))
        rows += int(stats["rows_held"])
    np.testing.assert_allclose(total + alike, uncut, rtol=2e-4, atol=2e-5)
    assert rows == int(counts.sum()) == SEQ * 4
    assert float(jnp.abs(uncut - alike).max()) > 0.05      # the routed part is not nothing
    ungated, _ = reference.moe_block(x[0], moe, hf, wrong={"ungated_shared"})
    assert float(jnp.abs(ungated - uncut).max()) > 0.05    # nor is the gate


@pytest.mark.parametrize("room", [0, 2], ids=["no_room", "room_for_all"])
def test_the_chunk_kernels_under_the_model_and_its_recomputation(room):
    """Heads of 128 (the kernels' width; interpreted here), one key head under
    two value heads, under ``remat`` with no policy: loss and gradients as the
    recurrence gives them; a layer whose plan keeps ``ds.gdn.scan`` runs
    ``gdn_chunk_fwd`` once a step, one without runs it again in its backward;
    the kept bytes count the output and the float32 chunk states."""
    from deepspeed_tpu.observability.xla import kept_residual_bytes
    from deepspeed_tpu.ops import remat
    from deepspeed_tpu.ops.gdn import scan_bytes
    hf = {**HF, "linear_key_head_dim": 128, "linear_value_head_dim": 128,
          "linear_num_key_heads": 1, "linear_num_value_heads": 2,
          "num_hidden_layers": 2, "gdn_chunk_size": 64}
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
    scan = scan_bytes(1, seq, 2, 128, 128, 64, 4)
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
    assert calls["gdn_chunk_bwd"] == 2
    assert calls["gdn_chunk_fwd"] == (2 if room else 4), calls
    kept = kept_residual_bytes(traced.jaxpr)
    less = kept_residual_bytes(traced.jaxpr, tuple(n for n in remat.KEPT_NAMES
                                                   if n != remat.GDN_SCAN))
    assert kept - less == (2 * scan if room else 0)
    assert remat.GDN_SCAN in remat.CANDIDATE_NAMES


def test_the_engine_gives_the_linear_layers_statistics_and_the_gates_mean():
    """``engine.gdn_stats()`` (the largest ``|S|``, the mean decay and beta,
    ``fused_rows``) and ``engine.attn_stats()`` (the attention gate's mean) of
    the newest fused step, beside the router's and apart from them; the gauges
    with the step's publish; the layers by kind."""
    import deepspeed_tpu
    from deepspeed_tpu.comm import MeshContext, reset_mesh_context, set_mesh_context
    from deepspeed_tpu.observability import get_registry
    cfg = _config()
    ids = jnp.asarray(np.random.default_rng(4).integers(0, 256, (1, SEQ), dtype=np.int32))
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
        gdn, attn, moe = engine.gdn_stats(), engine.attn_stats(), engine.moe_stats()
        engine.train_batch(iter([(ids, ids)]))      # publishes the step before
        assert set(gdn) == {"state_absmax", "decay_mean", "beta_mean", "fused_rows"}
        assert float(gdn["state_absmax"]) > 0.0 and 0.0 < float(gdn["decay_mean"]) < 1.0
        assert float(gdn["fused_rows"]) == 0.0      # heads of 16: the recurrence
        assert set(attn) == {"gate_mean"} and 0.3 < float(attn["gate_mean"]) < 0.7
        assert set(moe) == {"expert_counts"} and engine.kda_stats() is None
        assert reg.get("ds_gdn_state_absmax").value == pytest.approx(
            float(gdn["state_absmax"]))
        assert reg.get("ds_attn_gate_mean").value == pytest.approx(float(attn["gate_mean"]))
        # the registry is the process's: kinds another test's model set read 0
        kinds = {m.labels["kind"]: m.value for m in reg.series("ds_model_layers")
                 if m.value}
        assert kinds == {"gdn+moe": 3.0, "attention+moe": 1.0}
    finally:
        reset_mesh_context()


def _row():
    for line in CATALOG.read_text().splitlines():
        row = json.loads(line)
        if row["name"] == "Qwen3-Next-80B-A3B-Instruct":
            return row
    raise AssertionError("no Qwen3-Next-80B-A3B-Instruct row in the catalog")


def test_the_policy_reads_the_catalog_row_and_refuses_what_is_not_built():
    """The published config: 48 layers, every fourth gated attention at head
    256 with a quarter rotated, the others Gated DeltaNet at 16 key heads under
    32 value heads of 128; 512 experts 512 wide, top-10 renormalised, a gated
    shared expert; zero-centred norms. Dense layers among the sparse, biases
    and a rope scaling are refused by name."""
    row = _row()["config"]
    assert policy_for("qwen3_next").__class__ is Qwen3NextPolicy
    cfg = Qwen3NextPolicy().config_from_hf(row)
    kinds = [spec.operator for spec in cfg.layer_specs]
    assert len(kinds) == 48 and kinds[:8] == ["gdn"] * 3 + ["attention"] + ["gdn"] * 3 + [
        "attention"] and kinds.count("attention") == 12
    assert all(spec.ffn == "moe" and spec.ffn_width == 512 for spec in cfg.layer_specs)
    assert (cfg.head_dim, cfg.rotary_dim, cfg.num_attention_heads,
            cfg.num_key_value_heads) == (256, 64, 16, 2)
    assert (cfg.gdn_k_heads, cfg.gdn_v_heads, cfg.gdn_k_head_dim, cfg.gdn_v_head_dim,
            cfg.gdn_d_conv) == (16, 32, 128, 128, 4)
    assert (cfg.num_local_experts, cfg.num_experts_per_tok, cfg.moe_renormalize,
            cfg.moe_scoring) == (512, 10, True, "softmax")
    assert cfg.shared_expert_intermediate_size == 512 and cfg.shared_expert_gated
    assert cfg.norm_plus_one and cfg.qk_norm == "head"
    assert cfg.attn_output_gate == "elementwise" and not cfg.tie_word_embeddings
    assert cfg.rope_theta == 1e7 and cfg.rms_norm_eps == 1e-6
    for key, bad in (("mlp_only_layers", [3]), ("decoder_sparse_step", 2),
                     ("attention_bias", True), ("rope_scaling", {"type": "yarn"}),
                     ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match=key):
            Qwen3NextPolicy().config_from_hf({**row, key: bad})


def test_the_cells_file_is_the_published_config_cut_three_ways_and_counts_its_parameters():
    """No key of the catalog's row differs in the file but the three in
    ``reduced``; the count by ``jax.eval_shape`` of the model the runner
    builds is the file's and the issue's: 625,667,136 parameters (a GDN mixer
    33,718,464, the attention mixer 27,263,488, an expert block 104,859,648,
    embedding and head 77,791,232), 7.51 GB at rest."""
    from benchmark import qwen3next_cost
    from benchmark.runners.train_steps_qwen3_next import model_config
    body, row = json.loads(CONFIG.read_text()), _row()
    assert body["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if body.get(k, "absent") != v}
    assert differs == set(body["reduced"]) == {"num_hidden_layers", "num_experts",
                                               "vocab_size"}
    assert body["published"] == {k: row["config"][k] for k in body["reduced"]}
    assert "each layer shared over 16 chips" in body["deployment"]
    for key in ("gdn_init", "gdn_chunk_size", "no_mtp", "no_balance_loss"):
        assert key in body["assumed"], key
    cfg = model_config(body)
    assert [s.operator for s in cfg.layer_specs] == ["gdn", "gdn", "gdn", "attention"]
    assert (cfg.num_local_experts, cfg.experts_held_, cfg.vocab_size) == (512, 32, 18992)
    shapes = jax.eval_shape(lambda: llama.init_llama(cfg, seed=0, dtype=jnp.float32)[1])

    def count(tree):
        return sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))

    model = shapes["model"]
    assert count(model["layers_0"]["self_attn"]) == 33_718_464
    assert count(model["layers_3"]["self_attn"]) == 27_263_488
    assert count(model["layers_0"]["block_sparse_moe"]) == 104_859_648
    assert count(model["embed_tokens"]) + count(model["lm_head"]) == 77_791_232
    assert count(shapes) == body["parameters"] == 625_667_136
    assert qwen3next_cost.param_count(body) == 625_667_136
    assert round(qwen3next_cost.bytes_at_rest(body) / 1e9, 2) == 7.51
    # the ZeRO-3 budget's unit counts the router and the experts held
    assert cfg.per_layer_elements() == 33_718_464 + 32 * 3_145_728 + 1_048_576 + 2 * 2048


def test_the_policy_round_trips_an_hf_named_tree_through_its_three_layouts():
    """An HF-named seeded tree -> ours -> HF again, bit for bit; and the
    layouts by hand: ``in_proj_qkvz`` and ``in_proj_ba`` key head by key head
    in the checkpoint and kind by kind in the tree, ``q_proj`` a head's query
    beside its gate in the checkpoint and the queries before the gates in the
    tree, the convolution's ``[C, 1, L]`` as taps ``[L, C]``."""
    from deepspeed_tpu.module_inject.replace_module import (convert_hf_checkpoint,
                                                            export_hf_checkpoint)
    cfg = _config()
    params = _seeded(cfg, seed=11)
    exported = export_hf_checkpoint("qwen3_next", cfg, params)
    p = "model.layers.0.linear_attn."
    for name, shape in ((p + "in_proj_qkvz.weight", (2 * 32 + 2 * 64, 64)),
                        (p + "in_proj_ba.weight", (8, 64)), (p + "conv1d.weight", (128, 1, 4)),
                        (p + "norm.weight", (16, )), (p + "A_log", (4, )),
                        ("model.layers.3.self_attn.q_proj.weight", (256, 64)),
                        ("model.layers.3.self_attn.q_norm.weight", (32, )),
                        ("model.layers.0.mlp.shared_expert_gate.weight", (1, 64)),
                        ("model.layers.0.mlp.experts.15.down_proj.weight", (64, 32))):
        assert exported[name].shape == shape, (name, exported[name].shape)
    ours = params["model"]["layers_0"]["self_attn"]
    qkvz, ba = exported[p + "in_proj_qkvz.weight"], exported[p + "in_proj_ba.weight"]
    kernel = np.asarray(ours["in_proj_qkvz"]["kernel"])
    for head in range(2):       # [q 16 | k 16 | v 2 x 16 | z 2 x 16] a key head
        rows = qkvz[head * 96:(head + 1) * 96]
        np.testing.assert_array_equal(rows[:16], kernel[:, head * 16:(head + 1) * 16].T)
        np.testing.assert_array_equal(rows[16:32], kernel[:, 32 + head * 16:48 + head * 16].T)
        np.testing.assert_array_equal(rows[32:64], kernel[:, 64 + head * 32:96 + head * 32].T)
        np.testing.assert_array_equal(rows[64:], kernel[:, 128 + head * 32:160 + head * 32].T)
        b_a = ba[head * 4:(head + 1) * 4]       # [b 2 | a 2] a key head
        ours_ba = np.asarray(ours["in_proj_ba"]["kernel"])
        np.testing.assert_array_equal(b_a[:2], ours_ba[:, head * 2:head * 2 + 2].T)
        np.testing.assert_array_equal(b_a[2:], ours_ba[:, 4 + head * 2:6 + head * 2].T)
    q = exported["model.layers.3.self_attn.q_proj.weight"]
    ours_q = np.asarray(params["model"]["layers_3"]["self_attn"]["q_proj"]["kernel"])
    for head in range(4):       # [query 32 | gate 32] a head
        np.testing.assert_array_equal(q[head * 64:head * 64 + 32],
                                      ours_q[:, head * 32:(head + 1) * 32].T)
        np.testing.assert_array_equal(q[head * 64 + 32:(head + 1) * 64],
                                      ours_q[:, 128 + head * 32:128 + (head + 1) * 32].T)
    np.testing.assert_array_equal(exported[p + "conv1d.weight"][:, 0, :].T,
                                  np.asarray(ours["conv_weight"]))
    back_cfg, back = convert_hf_checkpoint("qwen3_next", exported, HF)
    assert back_cfg.layer_specs == cfg.layer_specs
    want = jax.tree_util.tree_flatten_with_path(params)[0]
    got = jax.tree_util.tree_leaves(back)
    assert len(want) == len(got)
    for (path, a), b in zip(want, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))
