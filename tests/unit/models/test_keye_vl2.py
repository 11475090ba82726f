"""Keye-VL-2.0-30B-A3B's language model (Qwen3-MoE's blocks whose attention is
a learned sparse attention: an indexer with one key a token scores every
earlier token, each query attends its ``topk``) in ``models/llama.py``
against the plain reference ``benchmark/reference/keye_vl2.py`` on seeded
weights at a small size: logits, loss, gradients leaf by leaf (the indexer's
exactly zero) and the choice in float32, bf16 within stated limits, the
eight shares of a layer adding up to the uncut layer, the kernels under the
model's recomputation, the policy on the catalog's keys and on the cell's
file. Everything is compiled once a module."""

import dataclasses
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import keye_vl2 as reference
from deepspeed_tpu.models import llama
from deepspeed_tpu.module_inject.replace_policy import KeyeVL2Policy, policy_for

ROOT = pathlib.Path(__file__).parents[3]
CONFIG = ROOT / "benchmark" / "configs" / "keye-vl-2.0-30b-a3b-ep8-train1.json"
CATALOG = pathlib.Path("/opt/skills/guides/model-configs/architectures.jsonl")
# tiny widths with the published structure: 8 query heads on one KV head (a
# group of 8), a 2 x 16 indexer with one key, top-16 of up to 64, 16 experts
# top-4
HF = dict(model_type="KeyeVL2", vocab_size=256, max_position_embeddings=512,
          hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
          num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=1, head_dim=16,
          num_experts=16, num_local_experts=16, num_experts_per_tok=4,
          norm_topk_prob=True, decoder_sparse_step=1, mlp_only_layers=[],
          hidden_act="silu", rms_norm_eps=1e-6, rope_theta=1e7,
          rope_scaling={"mrope_section": [2, 3, 3], "rope_type": "default",
                        "type": "default"},
          sa_config={"indexer_head_dim": 16, "indexer_num_heads": 2,
                     "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                     "q_chunk_size": 512, "topk": 16},
          attention_bias=False, tie_word_embeddings=False, sliding_window=None,
          use_sliding_window=False)
ROWS, SEQ, TOPK = 2, 64, 16
PAIRS = ROWS * sum(min(t + 1, TOPK) for t in range(SEQ))


def _seeded(cfg, seed=3):
    """Seeded float32 parameters, the indexer's LayerNorm bias drawn (born
    zero, it would not tell a LayerNorm with bias from one without)."""
    _, params = llama.init_llama(cfg, seed=seed, seq_len=SEQ)
    rng = np.random.default_rng(seed)
    for lp in params["model"].values():
        if "self_attn" in lp:
            norm = lp["self_attn"]["indexer_k_norm"]
            norm["bias"] = jnp.asarray(0.1 * rng.standard_normal(norm["bias"].shape),
                                       jnp.float32)
    return params


@pytest.fixture(scope="module")
def small():
    """The uncut small model in float32, its ids, and the reference's step."""
    cfg = dataclasses.replace(KeyeVL2Policy().config_from_hf(HF), dtype=jnp.float32)
    params = _seeded(cfg)
    ids = np.random.default_rng(0).integers(0, HF["vocab_size"], (ROWS, SEQ), dtype=np.int32)
    at = np.stack([np.arange(0, SEQ - 1, 4)] * ROWS)
    sample = np.stack([np.arange(3, SEQ, 6)] * ROWS)
    want = reference.step_parts(params, ids, HF, at, sample)
    return {"cfg": cfg, "params": params, "ids": jnp.asarray(ids), "at": at,
            "sample": sample, "want": want}


def _program(cfg, params, ids):
    model = llama.LlamaForCausalLM(cfg)

    @jax.jit
    def both(p):
        loss, grads = jax.value_and_grad(
            lambda p: model.apply({"params": p}, ids, labels=ids))(p)
        return loss, grads, model.apply({"params": p}, ids,
                                        mutable=["moe_stats", "dsa_stats", "dsa_choice"])

    loss, grads, (logits, sown) = both(params)
    return float(loss), grads, np.asarray(logits, np.float32), sown


def _kernel_calls(closed_jaxpr):
    """{kernel name: calls} of a traced program, a shared sub-program counted
    each time it is called."""
    from jax._src import core
    calls = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                calls[name] = calls.get(name, 0) + 1
            for sub in core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(closed_jaxpr.jaxpr)
    return calls


def _rel(a, b):
    return float(np.linalg.norm(np.asarray(a, np.float32) - b) / np.linalg.norm(b))


def _choice(sown, sample):
    """[rows, layers, n, T] bool from what the model sowed, as the cell's
    runner rebuilds it."""
    from deepspeed_tpu.ops.dsa_attention import chosen_keys
    rows = np.arange(ROWS)[:, None]
    out = []
    for i in range(2):
        s = sown["dsa_choice"]["model"][f"layers_{i}"]["self_attn"]
        qi, ki, w, kth = (s[name][0] for name in ("qi", "ki", "w", "kth"))
        out.append(np.asarray(chosen_keys(qi[rows, sample], ki, w[rows, sample],
                                          kth[rows, sample], jnp.asarray(sample), TOPK)))
    return np.stack(out, axis=1)


def test_float32_program_matches_the_reference(small):
    """Loss to 1e-5, the logits to 1e-4, every gradient leaf to 2e-3, the
    indexer's leaves EXACTLY zero on both sides, the router's counts and the
    pairs chosen exactly, the smallest chosen score's mean to 1e-5, and the
    very choice at the sampled queries of both layers."""
    loss, grads, logits, sown = _program(small["cfg"], small["params"], small["ids"])
    want = small["want"]
    assert abs(loss - want["ce"]) <= 1e-5 * want["ce"]
    got = np.stack([logits[r, small["at"][r]] for r in range(ROWS)])
    assert _rel(got, want["logits"]) <= 1e-4
    indexer = 0
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want["grads"])):
        name = jax.tree_util.keystr(path)
        if "indexer" in name:
            assert not np.any(g) and not np.any(w), name
            indexer += 1
            continue
        assert _rel(g, w) <= 2e-3, (name, _rel(g, w))
    assert indexer == 2 * 5
    counts = sum(np.asarray(sown["moe_stats"]["model"][f"layers_{i}"]["block_sparse_moe"][
        "expert_counts"]) for i in range(2))
    assert np.array_equal(counts, want["counts"])
    dsa = [sown["dsa_stats"]["model"][f"layers_{i}"]["self_attn"] for i in range(2)]
    assert [int(d["chosen_pairs"]) for d in dsa] == [PAIRS, PAIRS]
    assert want["chosen_pairs"].tolist() == [PAIRS, PAIRS]
    assert [float(d["causal_pairs"]) for d in dsa] == [ROWS * SEQ * (SEQ + 1) / 2] * 2
    kth = np.mean([float(d["kth_score_mean"]) for d in dsa])
    assert abs(kth - want["kth_score_mean"]) <= 1e-5 * abs(want["kth_score_mean"])
    mine = _choice(sown, small["sample"])
    assert mine.shape == want["choice"].shape == (ROWS, 2, small["sample"].shape[1], SEQ)
    assert (mine != want["choice"]).sum() <= 2      # a near-tie at float32's last place
    assert mine.sum(-1).max() == TOPK and want["choice"][:, :, 0].sum(-1).max() == 4


def test_bf16_program_lies_within_stated_limits_of_the_reference(small):
    """bf16 compute on the same float32 masters (read here: logits' relative
    distance by position, median 1.8e-2 and 90th percentile 0.18: where 16 of
    at most 64 keys are chosen a flipped near-tie is a sixteenth of a row's
    attention): each limit about twice its reading; the pairs chosen stay
    exact, and most of the choice is the reference's."""
    cfg = dataclasses.replace(small["cfg"], dtype=jnp.bfloat16)
    loss, grads, logits, sown = _program(cfg, small["params"], small["ids"])
    want = small["want"]
    assert abs(loss - want["ce"]) <= 2e-3 * want["ce"]
    got = np.stack([logits[r, small["at"][r]] for r in range(ROWS)])
    err = (np.linalg.norm(got - want["logits"], axis=-1)
           / np.linalg.norm(want["logits"], axis=-1)).ravel()
    assert np.median(err) <= 4e-2 and np.quantile(err, 0.9) <= 0.35
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(want["grads"])):
        name = jax.tree_util.keystr(path)
        if "indexer" in name:
            assert not np.any(g)
            continue
        routed = "block_sparse_moe" in name or "post_attention_layernorm" in name
        assert _rel(g, w) <= (0.6 if routed else 0.45), (name, _rel(g, w))
    assert [int(sown["dsa_stats"]["model"][f"layers_{i}"]["self_attn"]["chosen_pairs"])
            for i in range(2)] == [PAIRS, PAIRS]
    mine = _choice(sown, small["sample"])
    overlap = ((mine & want["choice"]).sum(-1) / mine.sum(-1)).mean(axis=(0, 2))
    assert overlap.min() >= 0.8, overlap


@pytest.mark.parametrize("wrong", reference.WRONG)
def test_each_wrong_reference_is_another_model(small, wrong):
    """What the cell's calibration relies on: every ``wrong`` way moves the
    float32 logits by more than a hundred times what the program differs by."""
    got = reference.step_parts(small["params"], np.asarray(small["ids"]), HF, small["at"],
                               small["sample"], wrong={wrong}, gradients=False)
    assert _rel(got["logits"], small["want"]["logits"]) >= 1e-2, wrong


def test_the_eight_shares_add_up_to_the_uncut_layer(small):
    """A layer as eight chips hold it: each share's routed part (the
    program's layer less what every chip computes alike: the residual and
    the sparse attention), summed, plus that part counted once, is the uncut
    reference's layer. Every share makes the same choice of keys."""
    cfg, params = small["cfg"], small["params"]
    lp = params["model"]["layers_1"]
    x = jax.random.normal(jax.random.PRNGKey(5), (1, SEQ, HF["hidden_size"]))
    positions = jnp.arange(SEQ)[None]
    sample = jnp.arange(SEQ)
    uncut, (counts, pairs, _, _) = reference.layer(x, lp, positions, sample, HF)
    moe = lp["block_sparse_moe"]
    alike, _ = reference.layer(
        x, {**lp, "block_sparse_moe": {**moe, "w2": jnp.zeros_like(moe["w2"])}},
        positions, sample, HF)
    cos, sin = llama.precompute_rope(cfg.head_dim_, cfg.max_position_embeddings,
                                     cfg.rope_theta)
    held, total, rows = 2, 0.0, 0
    for share in range(8):
        share_cfg = dataclasses.replace(cfg, moe_experts_held=held, moe_share_index=share)
        mine = {k: moe[k][share * held:(share + 1) * held] for k in ("w1", "w3", "w2")}
        out, sown = llama.LlamaDecoderLayer(share_cfg, 1).apply(
            {"params": {**lp, "block_sparse_moe": {**moe, **mine}}}, x, cos, sin,
            positions, mutable=["moe_stats", "dsa_stats"])
        total = total + (out - alike)
        stats = sown["moe_stats"]["block_sparse_moe"]
        assert np.array_equal(np.asarray(stats["expert_counts"]), np.asarray(counts))
        assert int(sown["dsa_stats"]["self_attn"]["chosen_pairs"]) == int(pairs.sum())
        rows += int(stats["rows_held"])
    np.testing.assert_allclose(total + alike, uncut, rtol=2e-4, atol=2e-5)
    assert rows == int(counts.sum())
    assert float(jnp.abs(uncut - alike).max()) > 0.05      # the routed part is not nothing


def test_the_kernels_under_the_model_and_its_recomputation_make_the_choice_once(small):
    """``attn_impl="flash"`` (interpreted here) under ``remat`` with no policy:
    loss and gradients as the dense path gives them, and the layer keeps by
    name the kernel's output and log-sum-exp and the choice's two int32 a
    token, so that ``dsa_index`` and ``dsa_fwd`` are not in the recomputed
    forward: ``ds_remat_kept_bytes`` counts tokens x (heads x (head_dim x 4 B
    + 4) + 8) a layer in float32."""
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
    text = str(traced.jaxpr)
    for kernel in ("dsa_index", "dsa_fwd", "dsa_bwd"):
        assert f"name={kernel}" in text, kernel
    tokens = ROWS * 2 * SEQ
    assert kept_residual_bytes(traced.jaxpr) == 2 * tokens * (8 * (16 * 4 + 4) + 8)
    # no memory report, no mask kept: each layer's backward makes it again from
    # the kept thresholds, and the selection is not in the recomputed forward
    assert _kernel_calls(traced.jaxpr) == {
        "dsa_index": 2, "dsa_fwd": 2, "dsa_mask": 2, "dsa_bwd": 2}


@pytest.mark.parametrize("masks", [0, 1, 2])
def test_the_mask_is_the_walks_first_candidate_and_a_layer_without_it_rebuilds(small, masks):
    """A chip with room for ``masks`` of the two layers' masks and a half
    (``ops/remat.py``'s walk takes ``ds.dsa.mask`` first, layer by layer, and
    stops at the first that does not fit): those layers' backward reads the
    forward's words, the others call ``dsa_mask`` once; the layers sow how
    many kept it, the kept bytes rise by seq x seq / 8 a row and kept mask
    less its thresholds (nothing reads them then: they are not in the
    program), and the gradients are the same to the bit whatever was kept."""
    from deepspeed_tpu.observability.xla import kept_residual_bytes
    from deepspeed_tpu.ops import remat
    cfg = dataclasses.replace(small["cfg"], attn_impl="flash", remat=True)
    ids = jnp.tile(small["ids"], (1, 2))
    model = llama.LlamaForCausalLM(cfg)
    fn = jax.jit(jax.value_and_grad(lambda p: model.apply({"params": p}, ids, labels=ids)))
    _, want = fn(small["params"])                           # the CPU: nothing to spend
    tokens, seq = ROWS * 2 * SEQ, 2 * SEQ
    always, mask = 2 * tokens * (8 * (16 * 4 + 4) + 8), ROWS * seq * seq // 8
    patch = pytest.MonkeyPatch()
    try:
        patch.setattr(remat, "step_reserve_bytes", lambda *a: 0)
        patch.setattr(remat, "device_memory",
                      lambda: (10**9, 10**9 - always - (2 * masks + 1) * mask // 2))
        remat.forget_plans()
        again = jax.jit(jax.value_and_grad(
            lambda p: model.apply({"params": p}, ids, labels=ids)))
        _, grads = again(small["params"])
        traced = again.trace(small["params"])
        plan = next(iter(remat._PLANS.values()))
        sown = jax.jit(lambda p: model.apply({"params": p}, ids, mutable=["dsa_stats"]))(
            small["params"])[1]["dsa_stats"]["model"]
    finally:
        patch.undo()
        remat.forget_plans()
    assert [names[2:] for names in plan] == [(remat.DSA_MASK, )] * masks + [()] * (2 - masks)
    calls = _kernel_calls(traced.jaxpr)
    assert calls.get("dsa_mask", 0) == 2 - masks and calls["dsa_index"] == 2
    assert sum(int(sown[f"layers_{i}"]["self_attn"]["masks_kept"]) for i in range(2)) == masks
    assert kept_residual_bytes(traced.jaxpr) == always + masks * (mask - tokens * 8)
    for g, w in zip(jax.tree_util.tree_leaves(grads), jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(g, w)


def test_policy_reads_the_catalogs_keys_and_the_cells_file():
    """``KeyeVL2`` from the catalog row's ``config`` (the published model) and
    from the cell's file (the chip's cut): Qwen3-MoE's keys, ``sa_config`` as
    the sparse attention's sizes, the default rotary under its
    ``mrope_section``; the file holds every published key as published but
    the four cut, and states what it assumed."""
    row = next(json.loads(line) for line in open(CATALOG)
               if '"Keye-VL-2.0-30B-A3B"' in line) if CATALOG.exists() else None
    body = json.loads(CONFIG.read_text())
    published = {**body, **body["published"]}
    for hf, depth, experts, vocab in (
            [(row["config"], 48, 128, 151936)] if row else []) + [
            (published, 48, 128, 151936), (body, 6, 16, 18992)]:
        cfg = policy_for(hf["model_type"]).config_from_hf(hf)
        assert cfg.num_hidden_layers == depth and cfg.vocab_size == vocab
        assert cfg.layer_specs is None and cfg.objective == "causal_lm"
        assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads,
                cfg.head_dim_, cfg.intermediate_size) == (2048, 32, 4, 128, 768)
        assert (cfg.dsa_topk, cfg.dsa_index_heads, cfg.dsa_index_head_dim) == (2048, 16, 64)
        assert (cfg.num_local_experts, cfg.num_experts_per_tok) == (experts, 8)
        assert (cfg.moe_scoring, cfg.moe_renormalize, cfg.qk_norm) == ("softmax", True, "head")
        assert cfg.shared_expert_intermediate_size is None
        assert not cfg.tie_word_embeddings and cfg.rope_theta == 1e7
        assert cfg.rms_norm_eps == 1e-6 and cfg.rotary_dim is None
    if row:
        assert body["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert body[key] == value or key in body["reduced"], key
    assert body["reduced"] == ["num_hidden_layers", "num_experts", "num_local_experts",
                               "vocab_size"]
    assert "each layer shared over 8 chips" in body["deployment"]
    for item in ("sparse_attention", "indexer_query", "indexer_key_norm_and_weight_scale",
                 "indexer_rope", "indexer_precision", "chunk_sizes", "ties",
                 "no_alignment_loss", "mrope_section", "qk_norm", "no_balance_loss"):
        assert item in body["assumed"], item
    for key, value in (("rope_scaling", {"rope_type": "yarn", "factor": 4.0}),
                       ("sa_config", None), ("attention_bias", True),
                       ("sa_config", {**body["sa_config"], "indexer_num_kv_heads": 2})):
        with pytest.raises(ValueError, match="KeyeVL2|sdar_moe"):
            KeyeVL2Policy().config_from_hf({**body, key: value})


def test_the_policy_maps_every_leaf_and_the_layer_counts_its_indexer():
    cfg = KeyeVL2Policy().config_from_hf(HF)
    shapes = jax.eval_shape(lambda: llama.init_llama(cfg, seed=0)[1])
    policy = KeyeVL2Policy()
    mapped = set()
    for layer in range(2):
        gate, experts = policy.moe_map(layer, cfg.num_local_experts)
        mapped |= {path for path, _ in policy.weight_map(layer).values()}
        mapped |= {path for path, _ in gate.values()} | set(experts)
    mapped |= {path for path, _ in policy.global_map(False).values()}
    leaves = {"/".join(str(k.key) for k in path)
              for path, _ in jax.tree_util.tree_flatten_with_path(shapes["model"])[0]}
    assert leaves == mapped
    layer = sum(int(np.prod(leaf.shape)) for leaf in
                jax.tree_util.tree_leaves(shapes["model"]["layers_0"]))
    # per_layer_elements leaves the two per-head norms out, as for every model
    assert cfg.per_layer_elements() == layer - 2 * HF["head_dim"]
    with pytest.raises(ValueError, match="learned sparse attention"):
        model = llama.LlamaForCausalLM(dataclasses.replace(cfg, sliding_window=8))
        model.apply({"params": jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes)}, jnp.zeros((1, 8), jnp.int32))
