"""``OuroPolicy``: the catalog's published keys as a ``LlamaConfig``, what is
refused by name, and the name map on a seeded HF-shaped tree, both ways."""

import json
import os
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).parents[3]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from deepspeed_tpu.models import llama  # noqa: E402
from deepspeed_tpu.module_inject.replace_policy import OuroPolicy, policy_for  # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CONFIG = ROOT / "benchmark" / "configs" / "ouro-2.6b-train1.json"
# the catalog's row, as the file's ``published`` and own keys give it back (the
# test runs where the guide is not installed too)
HF = {"head_dim": 16, "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
      "layer_types": ["full_attention"] * 2, "max_position_embeddings": 128,
      "model_type": "ouro", "num_attention_heads": 4, "num_hidden_layers": 2,
      "num_key_value_heads": 4, "rms_norm_eps": 1e-6, "rope_scaling": None,
      "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
      "total_ut_steps": 4, "early_exit_threshold": 1, "use_sliding_window": False,
      "vocab_size": 96}


def _row() -> dict:
    body = json.loads(CONFIG.read_text())
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            for line in f:
                row = json.loads(line)
                if row["name"] == "Ouro-2.6B":
                    return row
        raise AssertionError("no Ouro-2.6B row in the catalog")
    config = {k: v for k, v in body.items() if k in HF or k == "max_window_layers"}
    return {"source_url": body["source"], "config": {**config, **body["published"]}}


def test_the_policy_reads_the_catalog_row_and_refuses_what_is_not_built():
    row = _row()["config"]
    assert policy_for("ouro").__class__ is OuroPolicy is policy_for("OuroForCausalLM").__class__
    cfg = OuroPolicy().config_from_hf(row)
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.intermediate_size,
            cfg.vocab_size) == (48, 2048, 5632, 49152)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim) == (16, 16, 128)
    assert (cfg.rope_theta, cfg.rms_norm_eps, cfg.max_position_embeddings) == (1e6, 1e-6, 65536)
    assert cfg.sandwich_norm and cfg.total_ut_steps == 4 and cfg.exit_gate and cfg.looped_
    # the entropy weight is the recipe's: the config has no key of it
    assert cfg.exit_entropy_weight is None and not cfg.tie_word_embeddings
    assert cfg.layer_specs is None and not cfg.scan_layers and cfg.sliding_window is None
    for key, bad in (("use_sliding_window", True), ("rope_scaling", {"type": "yarn"}),
                     ("attention_bias", True), ("hidden_act", "gelu"),
                     ("layer_types", ["sliding_attention"] * 48),
                     ("early_exit_threshold", 0.5)):
        with pytest.raises(ValueError, match=key):
            OuroPolicy().config_from_hf({**row, key: bad})
    with pytest.raises(ValueError, match="layer_types"):
        OuroPolicy().config_from_hf({**row, "layer_types": ["full_attention"] * 47})


def test_the_cells_file_is_the_published_config_cut_in_depth_and_counts_its_parameters():
    """No key of the catalog's row differs in the file but the two in
    ``reduced``; the count by ``jax.eval_shape`` of the model the runner builds
    is the file's and the issue's: a layer 51,388,416, embedding and head
    201,326,592, the final norm, the gate and its bias."""
    from benchmark import ouro_cost
    from benchmark.runners.train_steps_ouro import model_config
    body, row = json.loads(CONFIG.read_text()), _row()
    assert body["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if body.get(k, "absent") != v}
    assert differs == set(body["reduced"]) == {"num_hidden_layers", "layer_types"}
    assert body["published"] == {k: row["config"][k] for k in body["reduced"]}
    assert body["num_hidden_layers"] == len(body["layer_types"]) == 8
    assert "shared over" not in body["deployment"] and "49,152 rows" in body["deployment"]
    for key in ("exit_entropy_weight", "exit_gate_init", "embedding_std", "learning_rate",
                "ce_chunk_size", "tokens_per_step", "scan_layers", "loss"):
        assert key in body["assumed"], key
    cfg = model_config(body)
    assert cfg.scan_layers and cfg.remat and cfg.remat_policy is None
    assert (cfg.exit_entropy_weight, cfg.ce_chunk_size, cfg.total_ut_steps) == (0.05, 3072, 4)
    shapes = jax.eval_shape(lambda: llama.init_llama(cfg, seed=0, dtype=jnp.float32)[1])

    def count(tree):
        return sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))

    model = shapes["model"]
    assert count(model["layers"]) == 8 * 51_388_416
    assert count(model["embed_tokens"]) + count(model["lm_head"]) == 201_326_592
    assert count(model["norm"]) == 2048 and count(model["early_exit_gate"]) == 2049
    assert count(shapes) == body["parameters"] == ouro_cost.param_count(body) == 612_438_017
    assert ouro_cost.param_count({**body, "num_hidden_layers": 48}) == 2_667_974_657
    assert ouro_cost.param_count({**body, "num_hidden_layers": 6}) == 509_661_185
    assert round(ouro_cost.bytes_at_rest(body) / 1e9, 2) == 7.35
    assert cfg.per_layer_elements() == 51_388_416 - 2 * 2048    # the budget's unit: two norms


def test_the_name_map_round_trips_a_seeded_hf_shaped_tree_both_ways():
    """An HF-named seeded tree -> ours -> HF again, bit for bit; the three
    norms that change their name land where the layer reads them (HF's
    ``input_layernorm_2`` is the norm on the attention's OUTPUT), the gate's
    ``[1, hidden]`` weight as a ``[hidden, 1]`` kernel."""
    from deepspeed_tpu.module_inject.replace_module import (convert_hf_checkpoint,
                                                            export_hf_checkpoint)
    cfg = OuroPolicy().config_from_hf(HF)
    _, params = llama.init_llama(cfg, seed=11)
    rng = np.random.default_rng(4)
    params = jax.tree_util.tree_map(
        lambda a: np.asarray(a) + rng.standard_normal(a.shape).astype(np.float32), params)
    exported = export_hf_checkpoint("ouro", cfg, params)
    p = "model.layers.1."
    ours = params["model"]["layers_1"]
    for hf_name, name in (("input_layernorm", "input_layernorm"),
                          ("input_layernorm_2", "post_attention_layernorm"),
                          ("post_attention_layernorm", "pre_feedforward_layernorm"),
                          ("post_attention_layernorm_2", "post_feedforward_layernorm")):
        np.testing.assert_array_equal(exported[p + hf_name + ".weight"], ours[name]["weight"])
    assert exported["model.early_exit_gate.weight"].shape == (1, 64)
    assert exported["model.early_exit_gate.bias"].shape == (1, )
    assert exported[p + "mlp.down_proj.weight"].shape == (64, 128)
    assert exported["lm_head.weight"].shape == (96, 64)
    assert len(exported) == 2 * (4 + 3 + 4) + 5     # layers; embedding, norm, head, the gate's two
    back_cfg, back = convert_hf_checkpoint("ouro", exported, HF)
    assert back_cfg == cfg
    want = jax.tree_util.tree_flatten_with_path(params)[0]
    got = jax.tree_util.tree_leaves(back)
    assert len(want) == len(got)
    for (path, a), b in zip(want, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=jax.tree_util.keystr(path))
    # and the converted tree runs: a loss, finite, through the looped model
    ids = jnp.asarray(rng.integers(0, 96, (1, 12)), jnp.int32)
    loss = llama.LlamaForCausalLM(back_cfg).apply({"params": back}, ids, labels=ids)
    assert np.isfinite(float(loss))
