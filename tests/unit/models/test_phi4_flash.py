"""Phi-4-mini-flash's blocks (SambaY with differential attention) through the
normal path against the plain float32 reference (``benchmark/reference/
phi4_flash.py``, which imports nothing of the program): logits, loss and the
gradient of every leaf on seeded weights, with the kernels interpreted;
differential attention against the two softmaxes written out, under the
window and causal, and the stacked single call against two calls; what passes
between layers beside the residual stream (the full layer's keys and values
collect the gradient of both their readers; a reader without its source is
refused by name); ``lambda_init`` by the PUBLISHED layer index; the
vocabulary's shares against the uncut model; ``Phi4FlashPolicy`` on the
catalog row's ``config`` (the 32 kinds, 3,852,562,944 parameters) and its
checkpoint names both ways; a window a layer in ``LayerSpec``; and the eight
older configurations' parameter trees as they were."""

import dataclasses
import hashlib
import importlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

from benchmark import phi4flash_cost  # noqa: E402
from benchmark.reference import phi4_flash as reference  # noqa: E402
from deepspeed_tpu.models import llama  # noqa: E402
from deepspeed_tpu.models.llama import (LayerSpec, LlamaConfig, LlamaForCausalLM,  # noqa: E402
                                        init_llama)
from deepspeed_tpu.module_inject.replace_module import (convert_hf_checkpoint,  # noqa: E402
                                                        export_hf_checkpoint)
from deepspeed_tpu.module_inject.replace_policy import Phi4FlashPolicy, policy_for  # noqa: E402

# config.json of microsoft/Phi-4-mini-flash-reasoning as the catalog has it
PUBLISHED = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 10240,
    "layer_norm_eps": 1e-05, "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40, "num_hidden_layers": 32,
    "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
    "vocab_size": 200064}
KINDS = ["mamba", "sliding_attention", "mamba", "full_attention", "gmu", "cross_attention"]
# published layers 14-19 at toy widths: what the cell's ``rehearse`` runs
SMALL = dict(PUBLISHED, hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=2, mamba_d_state=4, mamba_dt_rank=4, sliding_window=8,
             vocab_size=256, num_hidden_layers=6, layer_types=KINDS, layer_offset=14,
             published={"num_hidden_layers": 32})
SEQ = 160      # past a block of the scan (128) and twenty windows


def small_cfg(**over):
    return dataclasses.replace(Phi4FlashPolicy().config_from_hf(dict(SMALL, **over)),
                               dtype=jnp.float32)


@pytest.fixture(scope="module")
def seeded():
    cfg = small_cfg()
    _, params = init_llama(cfg, seed=3, seq_len=16)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (1, SEQ), 0, 256))
    at = np.arange(0, SEQ - 1, 7)[None]
    return cfg, params, ids, at, reference.step_parts(params, ids, SMALL, at)


def leaf_errors(got, want):
    return {jax.tree_util.keystr(p): float(np.linalg.norm(np.asarray(g) - w)
                                           / np.linalg.norm(w))
            for (p, g), w in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                                 jax.tree_util.tree_leaves(want))}


@pytest.mark.parametrize("remat", [False, True])
def test_logits_loss_gradients_and_statistics_are_the_references(seeded, remat):
    """Float32 on both sides, the scan and the attention as interpreted
    kernels: the loss to 1e-6, the logits to 1e-5, every leaf's gradient to
    1e-4 of its norm (``k_proj``'s bias has none: a constant on every key
    leaves the softmax as it is), with and without whole-layer recomputation
    (the carried keys, values and memory are inputs of the recomputed layers)."""
    cfg, params, ids, at, want = seeded
    model = LlamaForCausalLM(dataclasses.replace(cfg, remat=remat, ce_chunk_size=64))

    def loss(p):
        return model.apply({"params": p}, jnp.asarray(ids), jnp.asarray(ids),
                           mutable=["selscan_stats", "diffattn_stats"])

    (got, sown), grads = jax.value_and_grad(loss, has_aux=True)(params)
    assert abs(float(got) - want["ce"]) < 1e-6 * want["ce"]
    errs = leaf_errors(grads, want["grads"])
    noise = [n for n in errs if n.endswith("['k_proj']['bias']")]
    assert len(noise) == 2 and len(errs) == 100
    worst = max((e, n) for n, e in errs.items() if n not in noise)
    assert worst[0] < 1e-4, worst
    for leaf in ("A_log", "['D']", "dt_proj']['bias", "dt_proj']['kernel", "x_proj",
                 "conv_weight", "conv_bias", "lambda_q1", "lambda_k2", "subln",
                 "layers_4']['mamba']['in_proj", "layers_5']['self_attn']['q_proj"):
        assert any(leaf in n for n in errs), leaf
    logits = LlamaForCausalLM(cfg).apply({"params": params}, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(logits)[0, at[0]], want["logits"][0], atol=1e-5)
    scans = sown["selscan_stats"]["model"]
    tops = [float(scans[f"layers_{i}"]["mamba"]["state_absmax"]) for i in (0, 2)]
    dts = [float(scans[f"layers_{i}"]["mamba"]["dt_mean"]) for i in (0, 2)]
    assert abs(max(tops) - want["selscan_stats"]["state_absmax"]) < 1e-5
    assert abs(np.mean(dts) - want["selscan_stats"]["dt_mean"]) < 1e-7
    lams = [float(sown["diffattn_stats"]["model"][f"layers_{i}"]["self_attn"]["lambda_mean"])
            for i in (1, 3, 5)]
    np.testing.assert_allclose(lams, want["diffattn_stats"]["lambda_mean"], atol=1e-6)


def test_the_references_lambda_terms_sum_to_the_lambdas_gradient(seeded):
    """``lambda_terms`` of a differential layer: the terms of ``dL/d lambda``,
    one a token and pair, sum to the scalar that the four vectors' gradients
    are multiples of (``d lambda / d lambda_q1 = exp(lambda_q1 . lambda_k1)
    lambda_k1``), and the sum of their magnitudes is no less."""
    _, params, _, _, want = seeded
    assert sorted(want["lambda_terms"]) == ["layers_1", "layers_3", "layers_5"]
    for layer, (size, magnitudes) in want["lambda_terms"].items():
        a, g = (tree["model"][layer]["self_attn"] for tree in (params, want["grads"]))
        q1, k1 = (np.asarray(a[n], np.float64) for n in ("lambda_q1", "lambda_k1"))
        scalar = np.vdot(g["lambda_q1"], k1) / (np.exp(np.vdot(q1, k1)) * np.vdot(k1, k1))
        assert abs(abs(scalar) - size) < 1e-4 * size and 0 < size <= magnitudes


def test_the_references_value_terms_sum_to_the_two_biases_gradients(seeded):
    """``value_terms``: a softmax's rows sum to one, so a value projection's
    bias has the gradient ``(1 - lambda) G`` summed over the layers that read
    the values, ``G`` the gradient to a constant added to every token's ``(A1 -
    lambda A2) V`` (the full layer's collects from the cross layer too), and the
    operator norm's bias the three projections' bias gradients through their
    kernels. The terms' magnitudes, ``(1 + |lambda|) |G|`` a reader, are ``(1 +
    lambda) / |1 - lambda|`` of the value bias's where one layer reads."""
    _, _, _, _, want = seeded
    leaves = {jax.tree_util.keystr(path): g for path, g in
              jax.tree_util.tree_flatten_with_path(want["grads"])[0]}
    assert sorted(want["value_terms"]) == sorted(
        f"['model']['layers_{i}']{leaf}['bias']" for i in (1, 3)
        for leaf in ("['operator_norm']", "['self_attn']['v_proj']"))
    for leaf, (size, magnitudes) in want["value_terms"].items():
        assert abs(np.linalg.norm(leaves[leaf]) - size) < 1e-4 * size and size <= magnitudes
    lam = float(want["diffattn_stats"]["lambda_mean"][0])
    size, magnitudes = want["value_terms"]["['model']['layers_1']['self_attn']['v_proj']['bias']"]
    assert abs(magnitudes / size - (1 + lam) / abs(1 - lam)) < 1e-3 * magnitudes / size


@pytest.mark.parametrize("window", [None, 8])
def test_differential_attention_is_two_softmaxes_and_their_difference(window):
    """ONE layer, windowed and causal: the program's stacked call against the
    reference's two dense softmaxes a pair with the subtraction written out."""
    kind = "sliding_attention" if window else "full_attention"
    offset = 15 if window else 17
    hf = dict(SMALL, num_hidden_layers=1, layer_types=[kind], layer_offset=offset)
    cfg = dataclasses.replace(Phi4FlashPolicy().config_from_hf(hf), dtype=jnp.float32)
    assert cfg.layer_specs[0].window == (window or 0) and cfg.layer_specs[0].differential
    _, params = init_llama(cfg, seed=5, seq_len=16)
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (1, 64), 0, 256))
    got = LlamaForCausalLM(cfg).apply({"params": params}, jnp.asarray(ids))
    np.testing.assert_allclose(np.asarray(got)[0], reference.logits_of(params, ids[0], hf),
                               atol=1e-5)
    # and the window matters at this length
    other = reference.logits_of(params, ids[0], dict(hf, layer_types=[
        "full_attention" if window else "sliding_attention"]))
    assert np.abs(other - np.asarray(got)[0]).max() > 1e-3


def test_the_stacked_single_call_is_two_calls():
    """Query heads ``[even | odd]`` against key heads ``[even | odd]`` and the
    paired values once for each half, group 2: the first half of the output
    heads is ``A1 V``, the second ``A2 V``, each what a call of its own gives."""
    from deepspeed_tpu.ops.attention import flash_attention
    b, s, nq, nkv, d = 1, 128, 8, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, nq, d))
    k = jax.random.normal(ks[1], (b, s, nkv, d))
    v = jax.random.normal(ks[2], (b, s, nkv, d))
    paired = v.reshape(b, s, nkv // 2, 2 * d)
    for window in (None, 32):
        call = lambda qs, ks_, vs: flash_attention(      # noqa: E731
            qs, ks_, vs, causal=True, window=window, interpret=True)
        stacked = call(jnp.concatenate([q[:, :, 0::2], q[:, :, 1::2]], axis=2),
                       jnp.concatenate([k[:, :, 0::2], k[:, :, 1::2]], axis=2),
                       jnp.concatenate([paired, paired], axis=2))
        first = call(q[:, :, 0::2], k[:, :, 0::2], paired)
        second = call(q[:, :, 1::2], k[:, :, 1::2], paired)
        assert stacked.shape == (b, s, nq, 2 * d)
        np.testing.assert_allclose(stacked[:, :, :nq // 2], first, atol=1e-6)
        np.testing.assert_allclose(stacked[:, :, nq // 2:], second, atol=1e-6)


def test_the_full_layers_keys_and_values_collect_from_both_their_readers(seeded,
                                                                         monkeypatch):
    """Layer 17's ``k_proj`` and ``v_proj`` (the checkpoint's ``Wqkv``) feed its
    own attention and, handed on, layer 19's: the program's gradient is the
    sum of the two, each read off the reference with the other path cut (the
    keys and values under ``stop_gradient`` inside one layer's attention)."""
    cfg, params, ids, _, _ = seeded
    ids = jnp.asarray(ids[:, :64])
    model = LlamaForCausalLM(cfg)
    grads = jax.grad(lambda p: model.apply({"params": p}, ids, ids))(params)
    lam_init = {i: 0.8 - 0.6 * float(np.exp(-0.3 * i)) for i in (17, 19)}
    plain = reference.differential_attention

    def cut_in(layer):
        def attention(q, k, v, ap, init, *rest, **kw):
            if abs(init - lam_init[layer]) < 1e-12:
                k, v = jax.lax.stop_gradient((k, v))
            return plain(q, k, v, ap, init, *rest, **kw)
        return attention

    def reference_grads(cut):
        monkeypatch.setattr(reference, "differential_attention", cut_in(cut))
        fn = lambda p: reference._sequence_nll(        # noqa: E731
            p, None, ids[0], jnp.arange(4), SMALL, frozenset(), 0)[0] / 63
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.grad(fn))(params)["model"]["layers_3"]["self_attn"]

    # cut the cross layer's use, then the full layer's own
    own, handed = (reference_grads(cut) for cut in (19, 17))
    for proj in ("k_proj", "v_proj"):
        a, b = (np.asarray(g[proj]["kernel"]) for g in (own, handed))
        total = np.asarray(grads["model"]["layers_3"]["self_attn"][proj]["kernel"])
        assert np.linalg.norm(b) > 1e-2 * np.linalg.norm(a)     # both are there
        np.testing.assert_allclose(total, a + b, atol=1e-5 * np.abs(total).max())


def test_a_reader_without_its_source_is_refused_by_name():
    with pytest.raises(ValueError, match=r"published layers 18\.\.21 of 32: layer 0 "
                       r"\('gmu'\) reads the memory of layer -2, which is not an earlier"):
        Phi4FlashPolicy().config_from_hf(dict(
            SMALL, num_hidden_layers=4, layer_offset=18,
            layer_types=["gmu", "cross_attention", "gmu", "cross_attention"]))
    with pytest.raises(ValueError, match=r"layer 1 \('attention'\) reads the keys and "
                       r"values of layer 0, a 'mamba1' layer that makes none"):
        LlamaConfig.tiny(layer_specs=(
            LayerSpec("mamba1"), LayerSpec("attention", differential=True, kv_from=0)),
            num_hidden_layers=2).shared_sources()
    with pytest.raises(ValueError, match="read by the differential form alone"):
        LlamaConfig.tiny(layer_specs=(LayerSpec("attention"), LayerSpec(
            "attention", kv_from=0)), num_hidden_layers=2).shared_sources()
    with pytest.raises(ValueError, match="are not the kinds of published layers 14"):
        Phi4FlashPolicy().config_from_hf(dict(SMALL, layer_types=KINDS[::-1]))
    for key, value in (("mlp_bias", True), ("lm_head_bias", True), ("mb_per_layer", 4),
                       ("hidden_act", "gelu")):
        with pytest.raises(ValueError, match=f"phi4flash: {key}="):
            Phi4FlashPolicy().config_from_hf(dict(PUBLISHED, **{key: value}))
    assert small_cfg().shared_sources() == (None, None, None, None, 2, 3)
    assert LlamaConfig.tiny().shared_sources() == ()


def test_lambda_init_follows_the_published_layer_index(seeded):
    """With the four lambda vectors at zero a layer's lambda is its
    ``lambda_init = 0.8 - 0.6 exp(-0.3 i)``, ``i`` the published index: 15, 17
    and 19 for the kept layers 1, 3 and 5 under ``layer_offset`` 14."""
    cfg, params, ids, _, _ = seeded
    zeroed = jax.tree_util.tree_map_with_path(
        lambda p, a: jnp.zeros_like(a) if "lambda_" in jax.tree_util.keystr(p) else a,
        params)
    for offset, published in ((14, (15, 17, 19)), (0, (1, 3, 5))):
        model = LlamaForCausalLM(dataclasses.replace(cfg, layer_index_offset=offset))
        _, sown = model.apply({"params": zeroed}, jnp.asarray(ids[:, :32]),
                              mutable=["diffattn_stats"])
        got = [float(sown["diffattn_stats"]["model"][f"layers_{i}"]["self_attn"]
                     ["lambda_mean"]) for i in (1, 3, 5)]
        np.testing.assert_allclose(got, [0.8 - 0.6 * np.exp(-0.3 * i) for i in published],
                                   rtol=1e-6)
    assert abs(0.8 - 0.6 * np.exp(-0.3 * 17) - 0.796) < 5e-4


def test_the_vocabularys_shares_add_up_to_the_uncut_model(seeded):
    """Eight chips hold 32 rows each of the 256-row table. On a sequence drawn
    from ITS rows (a share runs without the exchange that would bring it the
    other chips' embeddings) each share's program gives the uncut reference's
    logits in its columns: side by side, the eight are the whole head."""
    cfg, params, _, _, _ = seeded
    table = params["model"]["embed_tokens"]["embedding"]
    share = LlamaForCausalLM(dataclasses.replace(cfg, vocab_size=32))

    @jax.jit
    def program(rows, ids):
        held = dict(params, model=dict(params["model"], embed_tokens={"embedding": rows}))
        return share.apply({"params": held}, ids)

    @jax.jit
    def uncut(ids):
        with jax.default_matmul_precision("highest"):
            x, _, _ = reference.hidden_states(params, ids, SMALL)
            return jnp.dot(x, table.T, precision=jax.lax.Precision.HIGHEST)

    for k in range(8):
        first = 32 * k
        ids = jax.random.randint(jax.random.PRNGKey(k), (1, 32), first, first + 32)
        got = program(table[first:first + 32], ids - first)
        assert got.shape == (1, 32, 32)
        np.testing.assert_allclose(got[0], uncut(ids[0])[:, first:first + 32], atol=1e-5)


def test_the_policy_reads_the_catalog_rows_config():
    assert isinstance(policy_for("phi4flash"), Phi4FlashPolicy)
    assert isinstance(policy_for("Phi4FlashForCausalLM"), Phi4FlashPolicy)
    cfg = Phi4FlashPolicy().config_from_hf(PUBLISHED)
    kinds = Phi4FlashPolicy.published_kinds(32)
    assert [kinds.count(k) for k in Phi4FlashPolicy.KINDS] == [9, 8, 1, 7, 7]
    assert kinds[14:20] == KINDS and kinds[16] == "mamba" and kinds[17] == "full_attention"
    specs = cfg.layer_specs
    assert [s.operator for s in specs].count("mamba1") == 9
    assert {i for i, s in enumerate(specs) if s.window} == set(range(1, 16, 2))
    assert all(s.window == 512 for s in specs if s.window)
    assert {s.memory_from for s in specs if s.operator == "gmu"} == {16}
    assert {s.kv_from for s in specs if s.kv_from >= 0} == {17}
    assert sum(s.kv_from >= 0 for s in specs) == 7 == sum(s.operator == "gmu" for s in specs)
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.intermediate_size, cfg.rms_norm_eps) == (2560, 40, 20, 10240, 1e-5)
    assert (cfg.mamba1_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
            cfg.mamba1_dt_rank) == (5120, 16, 4, 160)
    assert (cfg.norm_type, cfg.pos_embedding, cfg.tie_word_embeddings, cfg.attention_bias,
            cfg.attention_out_bias, cfg.mlp_bias, cfg.lm_head_bias,
            cfg.sliding_window) == ("layernorm", "none", True, True, True, False, False, None)
    model = LlamaForCausalLM(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0),
                                               jnp.ones((1, 8), jnp.int32))["params"])
    built = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes))
    whole = dict(PUBLISHED, layer_types=kinds)
    assert built == 3_852_562_944 == phi4flash_cost.param_count(whole)
    # the cell's cut: published layers 14-19 and an eighth of the vocabulary
    cut = Phi4FlashPolicy().config_from_hf(dict(
        PUBLISHED, num_hidden_layers=6, vocab_size=25008, layer_types=KINDS,
        layer_offset=14, published={"num_hidden_layers": 32}))
    shapes = jax.eval_shape(lambda: LlamaForCausalLM(cut).init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"])
    assert sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(shapes)) \
        == 697_094_272 == cut.layer_index_offset * 0 + phi4flash_cost.param_count(dict(
            PUBLISHED, vocab_size=25008, layer_types=KINDS))


def test_checkpoint_names_both_ways(seeded):
    """``Wqkv`` (rows q | k | v; a cross layer's is its queries'), the fused
    ``gate_up_proj``, Conv1d's ``[C, 1, L]`` and the lambdas under
    ``inner_cross_attn`` come apart and go back together."""
    cfg, params, _, _, _ = seeded
    hf = export_hf_checkpoint("phi4flash", cfg, params)
    shapes = {"model.layers.1.attn.Wqkv.weight": (128, 64),
              "model.layers.1.attn.Wqkv.bias": (128, ),
              "model.layers.5.attn.Wqkv.weight": (64, 64),
              "model.layers.3.attn.inner_cross_attn.lambda_q1": (16, ),
              "model.layers.3.attn.inner_cross_attn.subln.weight": (32, ),
              "model.layers.0.attn.conv1d.weight": (128, 1, 4),
              "model.layers.0.attn.dt_proj.weight": (128, 4),
              "model.layers.0.attn.A_log": (128, 4),
              "model.layers.4.attn.in_proj.weight": (128, 64),
              "model.layers.2.mlp.gate_up_proj.weight": (256, 64),
              "model.final_layernorm.bias": (64, ),
              "model.embed_tokens.weight": (256, 64)}
    for name, shape in shapes.items():
        assert hf[name].shape == shape, (name, hf[name].shape)
    assert "model.layers.5.attn.Wqkv.bias" in hf and "lm_head.weight" not in hf
    np.testing.assert_array_equal(
        hf["model.layers.1.attn.Wqkv.weight"][64:96],
        np.asarray(params["model"]["layers_1"]["self_attn"]["k_proj"]["kernel"]).T)
    cfg2, back = convert_hf_checkpoint("phi4flash", hf, dict(SMALL))
    assert cfg2.layer_specs == cfg.layer_specs
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(params)[0],
                            jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=str(path))


def test_a_window_a_layer_in_layer_spec_is_the_configs_window_there():
    """R2: ``LayerSpec.window`` on plain attention layers gives what
    ``sliding_window`` with ``sliding_window_layers`` gives, layer by layer."""
    base = dict(num_hidden_layers=3, dtype=jnp.float32)
    spec = lambda w: LayerSpec("attention", "dense", 128, window=w)     # noqa: E731
    by_spec = LlamaConfig.tiny(layer_specs=(spec(4), spec(0), spec(16)), **base)
    assert [llama._layer_window(by_spec, i) for i in range(3)] == [4, None, 16]
    alike = LlamaConfig.tiny(layer_specs=(spec(4), spec(0), spec(4)), **base)
    by_config = LlamaConfig.tiny(layer_specs=(spec(0), ) * 3, sliding_window=4,
                                 sliding_window_layers=(0, 2), **base)
    _, params = init_llama(alike, seed=0, seq_len=8)
    ids = jax.random.randint(jax.random.PRNGKey(3), (2, 32), 0, 256)
    a = LlamaForCausalLM(alike).apply({"params": params}, ids)
    b = LlamaForCausalLM(by_config).apply({"params": params}, ids)
    np.testing.assert_allclose(a, b, atol=1e-6)
    full = LlamaForCausalLM(LlamaConfig.tiny(layer_specs=(spec(0), ) * 3, **base)).apply(
        {"params": params}, ids)
    assert float(jnp.abs(a - full).max()) > 1e-4


# sha256 (16 hex) of every leaf's path, shape and dtype of the eight older
# configurations' parameter trees, read at the commit before this model came
TREES = {"train-granite4hm-1chip-longseq": "17dc40ba6dbe4c13",
         "train-keyevl2-1chip-dsa-seq32k": "5d7de40bb0abfdfd",
         "train-kimivl-1chip-seq8k": "3ddc90209275b4d2",
         "train-lfm2moe-1chip-seq8k": "daecd15ee488a74f",
         "train-ling3flash-1chip-kda-longseq": "836712581b657250",
         "train-olmoe-1chip-seq4k": "2c3d35de3e481b34",
         "train-sdar-1chip-bd4-seq8k": "51d75b7717cbc4cf",
         "train-zero3-seq4k": "e849f8129a758a02"}


@pytest.mark.parametrize("cell", sorted(TREES))
def test_the_older_configurations_parameter_trees_are_unchanged(cell):
    """``LayerSpec``'s new fields and the carry beside the residual stream
    leave every leaf of the eight older configurations where and what it was."""
    bench = os.path.join(ROOT, "benchmark")
    with open(os.path.join(bench, "workloads", cell + ".json")) as f:
        workload = json.load(f)
    with open(os.path.join(bench, "configs", workload["config"] + ".json")) as f:
        config = json.load(f)
    runner = importlib.import_module(f"benchmark.runners.{workload['runner']}")
    if hasattr(runner, "model_config"):
        cfg = runner.model_config(config)
    elif cell == "train-olmoe-1chip-seq4k":
        cfg = policy_for("olmoe").config_from_hf(config)
    else:
        cfg = policy_for("mistral").config_from_hf({k: config[k] for k in runner.HF_KEYS})
    assert all(s.window == 0 and not s.differential and s.kv_from == s.memory_from == -1
               for s in cfg.layer_specs or ())
    assert cfg.shared_sources() == (None, ) * len(cfg.layer_specs or ())
    model = LlamaForCausalLM(cfg)
    shapes = jax.eval_shape(lambda: llama.unbox_params(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"])
    text = "\n".join(sorted(
        f"{jax.tree_util.keystr(p)}:{a.shape}:{a.dtype}"
        for p, a in jax.tree_util.tree_flatten_with_path(shapes)[0]))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == TREES[cell]
