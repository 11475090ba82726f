"""The Kimi-VL cell's own tests: its configuration against the published
values, its parameter and FLOP counts by hand, the latent kernels' cost
function on made-up events (the backward counted by the kernel's NAME), its
readers, its manifest entries by membership and relative order (never
"last": the next cell appends after these), the chip's calibration readings
through the limits as they are, and a rehearsal of the runner end to end.
All on the CPU; no number here is a measurement."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for path in (ROOT, HERE):
    if path not in sys.path:
        sys.path.insert(0, path)

import manifest_checks  # noqa: E402  (beside this file)
from benchmark import kimi_cost, mla_cost  # noqa: E402
from benchmark.run import cell_metrics, load_json, load_manifest, load_module  # noqa: E402

CELL, CONFIG = "train-kimivl-1chip-seq8k", "kimi-vl-a3b-instruct-ep8-train1"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = "https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct/blob/main/config.json"
# config.json's text_config at SOURCE, key by key as the catalog has it
PUBLISHED = {"vocab_size": 163840, "max_position_embeddings": 131072, "hidden_size": 2048,
             "intermediate_size": 11264, "moe_intermediate_size": 1408,
             "num_hidden_layers": 27, "num_attention_heads": 16, "n_shared_experts": 2,
             "n_routed_experts": 64, "ep_size": 1, "routed_scaling_factor": 2.446,
             "kv_lora_rank": 512, "q_lora_rank": None, "qk_rope_head_dim": 64,
             "v_head_dim": 128, "qk_nope_head_dim": 128, "topk_method": "noaux_tc",
             "n_group": 1, "topk_group": 1, "num_experts_per_tok": 6, "moe_layer_freq": 1,
             "first_k_dense_replace": 1, "norm_topk_prob": True, "scoring_func": "sigmoid",
             "seq_aux": True, "num_key_value_heads": 16, "hidden_act": "silu",
             "rms_norm_eps": 1e-05, "rope_theta": 800000, "rope_scaling": None,
             "attention_bias": False, "tie_word_embeddings": False}
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
NEW = {"kernel.mla_fwd_roofline": ("kernel", "%"), "kernel.mla_bwd_roofline": ("kernel", "%"),
       "mla.kernel_ms_per_step": ("kernel", "ms"),
       "mla.proj_ms_per_step": ("latent attention operator", "ms"),
       "moe.shared_ms_per_step": ("MoE block", "ms")}
SHARED = ["step.mfu_pct", "device.idle_pct.train", "host.work_ms_per_step",
          "host.idle_unnamed_pct.train", "setup.engine_init_s", "setup.place_params_s",
          "moe.gmm_ms_per_step", "moe.load_max_over_mean", "kernel.moe_gmm_held_roofline",
          "moe.rows_held_pct"]
# device events as a v5e's compiled step names them (4 rows x 16 heads)
FWD = ("%mla_fwd.3 = (bf16[64,1,8192,128]{3,2,1,0:T(8,128)(2,1)}, "
       "f32[64,1,8192,1]{3,2,1,0:T(8,128)}) custom-call(bf16[64,1,8192,192]")
BWD = ("%mla_bwd.1 = (bf16[64,8192,192]{2,1,0:T(8,128)(2,1)}, "
       "bf16[64,8192,128]{2,1,0:T(8,128)(2,1)}, bf16[64,1,8192,192]{3,2,1,0:T(8,128)(2,1)}) cust")
BWD_DQ = "%mla_bwd_dq.1 = bf16[64,1,8192,192]{3,2,1,0:T(8,128)(2,1)} custom-call(bf16[64,1,81"
BWD_DKDV = ("%mla_bwd_dkdv.1 = (bf16[64,8192,192]{2,1,0:T(8,128)(2,1)}, "
            "bf16[64,8192,128]{2,1,0:T(8,128)(2,1)}) custom-call(")
FLASH = "%flash_fwd.2 = (bf16[64,1,8192,128]{3,2,1,0:T(8,128)(2,1)}, f32[64,1,8192,1]"
PAIRS = 8192 * 8193 // 2        # live (query, key) pairs a head and sequence


def config() -> dict:
    return load_json("configs", CONFIG + ".json")


def read(name, run):
    return load_module("layers", name).read(run)


def test_the_configuration_is_the_published_one_but_for_the_stated_cuts():
    cfg = config()
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert cfg[key] == value, key
    assert cfg["reduced"] == REDUCED and cfg["source"] == SOURCE
    assert cfg["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) \
        == (6, 8, 20480)
    # the floors: four layers after the leading dense one, 8 experts, an eighth
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8 == PUBLISHED["n_routed_experts"] // 8
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert "each layer shared over 8 chips" in cfg["deployment"]
    assert "no vision tower" in cfg["deployment"]
    assert cfg["num_dense_layers"] == cfg["first_k_dense_replace"] == 1
    for key in ("rope_layout", "softmax_scale", "no_vision_tower", "renorm_eps",
                "num_dense_layers", "no_balance_loss", "expert_bias", "embedding_std",
                "tokens_per_step", "learning_rate"):
        assert key in cfg["assumed"], key
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-VL-A3B-Instruct")
        assert row["source_url"] == SOURCE and row["config"] == PUBLISHED
    # the program's config of the file: the widths as published, the share set
    from benchmark.runners import train_steps_kimi_vl as runner
    model = runner.model_config(cfg)
    assert (model.hidden_size, model.num_attention_heads, model.kv_lora_rank,
            model.head_dim_ - model.rotary_dim, model.rotary_dim, model.v_head_dim) \
        == (2048, 16, 512, 128, 64, 128)
    assert [(s.operator, s.ffn, s.ffn_width) for s in model.layer_specs] \
        == [("latent", "dense", 11264)] + [("latent", "moe", 1408)] * 5
    assert (model.num_local_experts, model.experts_held_, model.moe_share_index,
            model.num_experts_per_tok) == (64, 8, 0, 6)
    assert (model.shared_expert_intermediate_size, model.shared_expert_gated) == (2816, False)
    assert model.routed_scaling_factor == 2.446 and model.moe_scoring == "sigmoid"
    assert model.remat and model.remat_policy is None
    assert model.ce_chunk_size == cfg["ce_chunk_size"] and model.vocab_size == 20480


def test_manifest_entries_of_the_cell_and_the_checks_every_manifest_passes():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        admitted = json.load(f)
    manifest_checks.check_admitted(admitted)
    manifest_checks.check_entries(load_manifest())
    cells = {w["name"]: w for w in admitted["workloads"]}
    # the cells admitted before it keep their order; this one comes after them
    assert list(cells).index(CELL) > list(cells).index("train-sdar-1chip-bd4-seq8k")
    assert cells[CELL]["chips"] == 1 and cells[CELL]["config"] == CONFIG
    assert cells[CELL]["traffic"] == "kimivl-1chip-seq8k"
    assert [w["name"] for w in admitted["workloads"] if w["chips"] == 4] \
        == ["train-zero3-seq4k"]                               # still the one on four
    entry = next(c for c in admitted["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED and entry["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    cell = load_json("workloads", CELL + ".json")
    assert cell["traffic"] == {"global_batch": 4, "seq_len": 8192,
                               "warmup_steps": 2, "trace_steps": 4}
    assert cell["runner"] == "train_steps_kimi_vl" == config()["runner"]
    assert cell["why"] == cells[CELL]["why"] and "4 x 8,192 tokens" in cell["why"]
    m = load_manifest()
    assert [x["name"] for x in cell_metrics(m, CELL, "end_to_end")] \
        == ["setup_s", "train_tok_s"] == cell["end_to_end"]
    layers = {x["name"]: x for x in cell_metrics(m, CELL, "per_layer")}
    assert set(layers) == set(SHARED) | set(NEW) | {
        "setup.compile_s", "setup.programs", "setup.cache_misses"}
    # not under the readers that would misread this cell: they read ONE width
    for absent in ("kernel.flash_fwd_roofline", "kernel.flash_bwd_roofline",
                   "flash.kernel_ms_per_step", "kernel.moe_gmm_roofline",
                   "coll.exposed_ms_per_step"):
        assert absent not in layers
    assert not any(name.startswith("scope.") for name in layers)
    for name, (layer, unit) in NEW.items():
        assert layers[name]["layer"] == layer and layers[name]["moves"] == "train_tok_s"
        assert layers[name]["workloads"] == [CELL] and layers[name]["unit"] == unit
        assert layers[name]["source"] == "device_trace"
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layers", name + ".py"))
    # membership and relative order: the five stand together in their order,
    # after every metric the accepted benchmark had; in each shared list this
    # cell comes after the cells that were there
    names = [x["name"] for x in admitted["per_layer"]]
    first = names.index("kernel.mla_fwd_roofline")
    assert names[first:first + 5] == list(NEW)
    assert first > names.index("diffusion.masked_pct")
    for name in SHARED + ["train_tok_s"]:
        metric = next(x for x in admitted["per_layer"] + admitted["end_to_end"]
                      if x["name"] == name)
        cells_of = metric["workloads"]
        assert cells_of.count(CELL) == 1
        assert cells_of.index(CELL) > cells_of.index("train-lfm2moe-1chip-seq8k")


def test_parameters_bytes_and_flops_by_hand():
    cfg = config()
    attention = 2048 * 3072 + 2048 * 576 + 512 + 512 * 4096 + 2048 * 2048
    assert attention == 13_763_072
    dense_layer = attention + 3 * 2048 * 11264 + 2 * 2048
    expert_layer = (attention + 2 * 2048 + 2048 * 64 + 64 + 3 * 2048 * 2816
                    + 8 * 3 * 2048 * 1408)
    assert (dense_layer, expert_layer) == (82_973_184, 100_405_824)
    total = dense_layer + 5 * expert_layer + 2 * 20480 * 2048 + 2048
    assert kimi_cost.param_count(cfg) == total == 668_890_432
    assert kimi_cost.bytes_at_rest(cfg) == 12 * total       # 8.03 GB
    assert abs(kimi_cost.bytes_at_rest(cfg) / 1e9 - 8.03) < 0.005
    assert kimi_cost.router_width(cfg) == 64
    assert kimi_cost.experts_held_per_token(cfg) == 0.75
    projections = 2 * (attention - 512)
    pairs = 2 * (192 + 128) * 16 * 4096.5
    dense = projections + pairs + 2 * 3 * 2048 * 11264
    expert = (projections + pairs + 2 * 2048 * 64 + 2 * 3 * 2048 * 2816
              + 2 * 0.75 * 3 * 2048 * 1408)
    forward = dense + 5 * expert + 2 * 2048 * 20480
    assert kimi_cost.forward_flops_per_token(cfg, 8192) == forward
    assert kimi_cost.train_flops_per_token(cfg, 8192) == 3 * forward
    assert abs(dense - 207.9e6) < 1e5 and abs(expert - 117.3e6) < 1e5
    assert abs(forward - 878.4e6) < 1e5 and abs(3 * forward - 2.635e9) < 1e6
    assert abs(3 * forward * 32768 - 86.3e12) < 1e11         # a step
    # the uncut model from the same arithmetic: 27 layers, 64 experts, 163,840 rows
    uncut = {**cfg, **cfg["published"], "reduced": []}
    assert 15.5e9 < kimi_cost.param_count(uncut) < 16.5e9    # "16B-A2.8B"


def test_kernel_cost_by_hand_counts_the_backward_by_its_name():
    cfg = config()
    pair = 192 + 128
    assert mla_cost.call_flops("%mla_fwd.3", FWD, cfg) == 2.0 * pair * 64 * PAIRS
    assert mla_cost.call_flops("%mla_bwd.1", BWD, cfg) == 4.0 * pair * 64 * PAIRS
    both = (mla_cost.call_flops("%mla_bwd_dq.1", BWD_DQ, cfg)
            + mla_cost.call_flops("%mla_bwd_dkdv.1", BWD_DKDV, cfg))
    assert both == mla_cost.call_flops("%mla_bwd.1", BWD, cfg)      # a pair is one backward
    # one width off the first result would be wrong either way
    assert mla_cost.call_flops("%mla_fwd.3", FWD, cfg) != 4.0 * 128 * 64 * PAIRS
    assert mla_cost.call_flops("%mla_fwd.3", FWD, {"hidden_size": 4096}) is None
    assert mla_cost.call_flops("%mla_fwd.3", "%mla_fwd.3 = bf16[64,8192]{1,0} cust", cfg) is None
    # 3.44e11 FLOP a sequence forward, as the reader's docstring reckons
    assert abs(2.0 * pair * 16 * PAIRS - 3.44e11) < 1e9


def made_up_run(kernels) -> dict:
    return {"trace": {"kernels": kernels}, "config": config(), "trace_steps": 4,
            "device": {"kind": "TPU v5 lite", "count": 1}, "tokens_per_step": 32768}


def test_readers_on_a_made_up_trace():
    peak = 197e12
    fwd_flops = 2.0 * 320 * 64 * PAIRS
    run = made_up_run({
        "%mla_fwd.3": {"hlo": FWD, "count": 24, "seconds": 24 * 0.020},
        "%mla_bwd.1": {"hlo": BWD, "count": 24, "seconds": 24 * 0.045},
        "%flash_fwd.2": {"hlo": FLASH, "count": 4, "seconds": 1.0},       # not ours
        "%fusion.7": {"hlo": "%fusion.7 = bf16[4,8192,2048]", "count": 9, "seconds": 0.3}})
    np.testing.assert_allclose(read("kernel.mla_fwd_roofline", run),
                               100 * fwd_flops / peak / 0.020)
    np.testing.assert_allclose(read("kernel.mla_bwd_roofline", run),
                               100 * 2 * fwd_flops / peak / 0.045)
    np.testing.assert_allclose(read("mla.kernel_ms_per_step", run),
                               1e3 * 24 * (0.020 + 0.045) / 4)
    assert read("kernel.mla_fwd_roofline", run) < 100 > read("kernel.mla_bwd_roofline", run)
    # the pair: the same work over both kernels' time, each counted by name
    pair = made_up_run({
        "%mla_bwd_dq.1": {"hlo": BWD_DQ, "count": 24, "seconds": 24 * 0.025},
        "%mla_bwd_dkdv.1": {"hlo": BWD_DKDV, "count": 24, "seconds": 24 * 0.030}})
    np.testing.assert_allclose(read("kernel.mla_bwd_roofline", pair),
                               100 * 2 * fwd_flops / peak / 0.055)
    assert read("kernel.mla_fwd_roofline", pair) is None


def test_readers_report_nothing_when_nothing_matched(monkeypatch):
    """A program without the kernels or the scope (the parent commit, a CPU
    rehearsal, another cell): every new reader returns None and raises
    nothing."""
    from benchmark import host_spans
    monkeypatch.setattr(host_spans, "_xplane_path", lambda: None)
    for run in ({}, {"trace": None}, made_up_run({}),
                made_up_run({"%flash_fwd.2": {"hlo": FLASH, "count": 4, "seconds": 1.0}})):
        for name in NEW:
            assert read(name, dict(run)) is None, name
    # an event of the name in another configuration: no widths, so no share
    other = made_up_run({"%mla_fwd.3": {"hlo": FWD, "count": 1, "seconds": 1.0}})
    other["config"] = {"hidden_size": 2048}
    assert read("kernel.mla_fwd_roofline", other) is None
    assert read("mla.proj_ms_per_step", other) is None


def test_the_shared_experts_reader_sums_its_scope_over_the_phases(monkeypatch):
    from benchmark import scope_time
    table = {"ds_ms": {("ds.moe.shared", "forward"): 3.0, ("ds.moe.shared", "backward"): 6.5,
                       ("ds.moe.shared", "recompute"): 3.1, ("ds.moe.route", "forward"): 9.0,
                       ("ds.rope", "forward"): 1.0}}
    monkeypatch.setattr(scope_time, "load", lambda run: table)
    assert read("moe.shared_ms_per_step", {}) == 12.6
    monkeypatch.setattr(scope_time, "load", lambda run: {"ds_ms": {("ds.rope", "forward"): 1.0}})
    assert read("moe.shared_ms_per_step", {}) is None


def chip_readings() -> list:
    with open(os.path.join(ROOT, "benchmark", "readings", "kimi_vl_calibration.jsonl")) as f:
        return [json.loads(ln) for ln in f if ln.startswith("{")]


def chip_verdicts(row) -> dict:
    """The runner's limits as they are now on one of the chip's rows (the
    counts themselves are not kept: two numbers with the row's sums stand in)."""
    from benchmark.runners import train_steps_kimi_vl as runner
    counts = np.zeros(64, np.int64)
    counts[0], counts[8] = row["rows_held"][0], row["assigned"][0] - row["rows_held"][0]
    # the rows read before the parameters' change was held leaf by leaf keep
    # its pooled norm (``update_err``): the later rows of the same program
    # (``--routing``) have the worst leaf, which no reference moves
    row.setdefault("update_worst", ["pooled over all leaves", row.get("update_err")])
    return runner.verdicts({**row, "counts": [counts.tolist()]}, 4 * 8192 * 6 * 5, 64, 8)


@pytest.mark.parametrize("against", ["sound", "bf16", "fp8", "no_latent_norm",
                                     "rope_on_nope", "scale_128", "no_shared",
                                     "gated_shared", "no_scaling", "unbiased_topk"])
def test_the_limits_stand_between_what_the_chip_read(against):
    """The chip's readings of the timed step at 4 x 8,192 tokens against the
    reference sound and made wrong (``calibrate_kimi_vl.py``, kept in
    ``benchmark/readings/``), through the runner's limits as they are now:
    each wrong way gives ``correct`` false, fp8 (the precision below the
    configuration's) by the logits, the gradients, the assignments moved and
    the latent's statistics and NOT by the losses or the rows held; the sound
    program passes with room, and so does a reference at the configuration's
    own bf16. A limit moved past either reading fails here."""
    from benchmark.runners import train_steps_kimi_vl as runner
    rows = [r for r in chip_readings() if r["against"] == against]
    # two seeds for the sound program, the precisions and the subtle ways;
    # the ways that are wrong by a factor were read on one
    assert len({r["seed"] for r in rows}) == (
        1 if against in ("no_latent_norm", "rope_on_nope", "no_shared") else 2), against
    for row in rows:
        ok = chip_verdicts(row)
        assert all(ok.values()) == (against in ("sound", "bf16")), (row["seed"], ok)
        assert ok == row["verdicts"] and row["lr"] == runner.LR
        assert row["update_worst"][1] < runner.UPDATE_RTOL / 3 and row["descends"]
    for row in rows if against == "sound" else []:      # room under each limit
        assert row["logit_median"] < runner.LOGIT_MEDIAN_RTOL / 1.8
        assert row["logit_p90"] < runner.LOGIT_P90_RTOL / 1.8
        assert row["grad_worst"][1] < runner.GRAD_RTOL / 2
        assert row["grad_routed_worst"][1] < runner.GRAD_ROUTED_RTOL / 1.7
        assert row["grad_router_median"] < runner.GRAD_ROUTER_RTOL / 1.7
        assert row["moved"] / row["assigned"][0] < runner.COUNT_MOVED_SHARE / 2.5
        assert row["loss_err"] < runner.LOSS_RTOL / 50
        assert row["loss_after_err"] < runner.LOSS_AFTER_RTOL / 45
        for got, want in (row["latent_rms"], row["k_rope_rms"]):
            assert abs(got - want) < runner.LATENT_RTOL * want / 3
        for leaf in ("kv_a_proj_with_mqa", "kv_b_proj", "shared_expert"):
            assert row["grad_named"][leaf] < 6e-2, leaf
    for row in rows if against == "fp8" else []:
        ok = chip_verdicts(row)
        assert not (ok["logits"] or ok["grads"] or ok["routing"] or ok["latent"])
        assert ok["loss"]                               # not by each
        assert row["logit_median"] > runner.LOGIT_MEDIAN_RTOL * 4.9
        assert row["grad_worst"][1] > runner.GRAD_RTOL * 2.8
        assert row["grad_routed_worst"][1] > runner.GRAD_ROUTED_RTOL * 1.45
        assert row["grad_router_median"] > runner.GRAD_ROUTER_RTOL * 1.5
        assert row["moved"] / row["assigned"][0] > runner.COUNT_MOVED_SHARE * 1.5
        held = row["rows_held"]
        assert abs(held[0] - held[1]) < runner.ROWS_HELD_RTOL * held[1] / 5
    for row in rows:
        ok = chip_verdicts(row)
        if against == "no_latent_norm":     # the 90th percentile and the leaf itself
            assert row["logit_median"] < runner.LOGIT_MEDIAN_RTOL < 3e-2 > row["logit_p90"]
            assert not ok["logits"] and row["grad_worst"][0].endswith("['kv_a_layernorm']['weight']")
        if against in ("rope_on_nope", "scale_128"):
            assert not ok["logits"] and not ok["grads"]
            assert row["logit_median"] > runner.LOGIT_MEDIAN_RTOL * 2
            assert row["grad_named"]["kv_b_proj"] > runner.GRAD_RTOL * 1.8
        if against in ("no_shared", "gated_shared", "no_scaling"):
            assert not (ok["loss"] or ok["logits"] or ok["grads"])
            assert row["logit_median"] > runner.LOGIT_MEDIAN_RTOL * 10
        if against == "unbiased_topk":      # the median cannot tell it; the routing does
            assert row["logit_median"] < runner.LOGIT_MEDIAN_RTOL and not ok["routing"]
            assert row["moved"] / row["assigned"][0] > runner.COUNT_MOVED_SHARE * 8
            held = row["rows_held"]
            assert abs(held[0] - held[1]) > runner.ROWS_HELD_RTOL * held[1] * 2.5


def test_the_held_experts_distance_is_the_flipped_tokens():
    """What the routed leaves' 0.17 is made of, on the chip's own readings
    (``calibrate_kimi_vl.py --routing``): layer by layer the held experts read
    ``sqrt(2 f)`` of the share of assignments on which the two routers differ
    token by token, of which the net of the counts is a small part; against
    the reference routed alike they read what the shared expert of their
    block reads, and no leaf more than 2e-2."""
    row, = [r for r in chip_readings() if r["against"] == "routed_as_program"]
    columns = row["gap_by_leaf_columns"]
    step, alone, alike = (columns.index(c) for c in (
        "step|reference", "alone|reference", "alone|reference_routed_alike"))
    gap, routers = row["gap_by_leaf"], row["routers"]
    assert 5 * routers["net_moved_share"] < routers["flipped_share"] < 1.5e-2
    for layer, f in zip(range(1, 6), routers["flipped_share_by_layer"]):
        moe = f"['model']['layers_{layer}']['block_sparse_moe']"
        shared = gap[moe + "['shared_expert']['down_proj']['kernel']"]
        for leaf in ("['w1']", "['w3']", "['w2']"):
            held = gap[moe + leaf]
            assert abs(held[step] - np.sqrt(2 * f)) < 0.1 * held[step], (layer, leaf)
            assert abs(held[alone] - held[step]) < 0.03 * held[step]
            assert held[alike] < 1.1 * shared[alike] < 2e-2 < shared[step] / 2.5
    assert max(v[alike] for v in gap.values()) < 2e-2 < min(
        v[step] for leaf, v in gap.items() if "layers_" in leaf) / 2


def made_up_readings(**over) -> dict:
    counts = np.zeros(64, np.int64)
    counts[0], counts[8] = 123_000, 983_040 - 123_000
    return dict({"loss_err": 1e-5, "loss_after_err": 2e-5, "descends": True,
                 "logit_median": 1.05e-2, "logit_p90": 1.15e-2,
                 "grad_worst": ("['a']", 7.4e-2), "grad_routed_worst": ("['w1']", 0.175),
                 "grad_router_median": 0.19, "update_worst": ("['embedding']", 1.2e-4),
                 "counts": [counts.tolist()], "assigned": [983_040, 983_040], "moved": 450,
                 "rows_held": [123_000, 123_050], "share_fallback": 0,
                 "latent_rms": [1.00017, 1.00018], "k_rope_rms": [1.00060, 1.00061]}, **over)


@pytest.mark.parametrize("fails,over", [
    (set(), {}),
    ({"loss"}, {"descends": False}), ({"loss"}, {"loss_after_err": 8e-3}),
    ({"loss"}, {"loss_err": 1.8e-3}),
    ({"logits"}, {"logit_median": 4.0e-2}),             # the chip's scores over sqrt(128)
    ({"logits"}, {"logit_p90": 2.99e-2}),               # the chip's dropped norm
    ({"logits"}, {"logit_p90": float("nan")}),
    ({"grads"}, {"grad_worst": ("['kv_a_layernorm']", float("inf"))}),
    ({"grads"}, {"grad_routed_worst": ("['w3']", 1.2)}),
    ({"grads"}, {"grad_router_median": 1.0}),           # no gradient at all reads 1
    ({"grads"}, {"update_worst": ("['kv_a_layernorm']['weight']", 1.0)}),
    ({"routing"}, {"moved": 1_850}),                    # fp8's 1.88e-3 of all
    ({"routing"}, {"rows_held": [123_000, 123_800]}),   # the unbiased top-6's least
    ({"routing"}, {"share_fallback": 1}), ({"routing"}, {"assigned": [983_040, 983_000]}),
    ({"latent"}, {"latent_rms": [1.0, 1.00017]}),       # read after its norm
    ({"latent"}, {"k_rope_rms": [float("nan"), 1.0]})])
def test_verdicts_by_hand(fails, over):
    from benchmark.runners import train_steps_kimi_vl as runner
    ok = runner.verdicts(made_up_readings(**over), 983_040, 64, 8)
    assert {k for k, good in ok.items() if not good} == fails
    wide = runner.verdicts(made_up_readings(logit_p90=2.99e-2, latent_rms=[1.0, 1.00017]),
                           983_040, 64, 8, slack=runner.REHEARSAL_SLACK)
    assert all(wide.values())       # a rehearsal's slack widens the distances


def test_a_small_leaf_left_unwritten_is_the_worst_leaf():
    """The parameters' change is held leaf by leaf: a ``kv_a_layernorm`` of
    512 values that the step did not write reads 1 at its own name, where one
    norm pooled over all the values read ``sqrt(512 / all)`` and passed."""
    import jax
    from benchmark.runners import train_steps_kimi_vl as runner
    rng = np.random.default_rng(0)
    NORM = "['model']['layers_0']['self_attn']['kv_a_layernorm']['weight']"
    BIAS = "['model']['layers_1']['block_sparse_moe']['expert_bias']"

    def tree(draw):
        kernel = lambda *shape: {"kernel": draw(*shape)}        # noqa: E731
        return {"model": {
            "layers_0": {"self_attn": {
                "q_proj": kernel(1024, 1024), "kv_a_layernorm": {"weight": draw(512)},
                "kv_a_proj_with_mqa": kernel(64, 8), "kv_b_proj": kernel(8, 64)}},
            "layers_1": {"block_sparse_moe": {
                "gate": kernel(64, 8), "w1": draw(2, 64, 8),
                "expert_bias": np.zeros(8, np.float32),
                "shared_expert": {"down_proj": kernel(8, 64)}}}}}

    def leaf(t, name):
        for key in name.strip("[]'").split("']['")[:-1]:
            t = t[key]
        return t, name.strip("[]'").split("']['")[-1]

    g = tree(lambda *shape: rng.standard_normal(shape).astype(np.float32))
    before = tree(lambda *shape: rng.standard_normal(shape).astype(np.float32))
    after = jax.tree_util.tree_map(lambda p, gr: p + runner.adamw_first_step(gr), before, g)
    counts = np.zeros(64, np.int64)
    got = {"logits": np.ones((1, 2, 4), np.float32), "loss": 1.0, "loss_after": 0.9,
           "grads": g, "before": before, "after": after,
           "stats": {"expert_counts": counts, "rows_held": 0, "share_fallback": 0},
           "latent": {"latent_rms": 1.0, "k_rope_rms": 1.0}}
    want = {"logits": np.ones((1, 2, 4), np.float32), "ce": 1.0, "ce_after": 0.9,
            "grads": g, "counts": counts, "rows_held": 0, "latent_rms": 1.0,
            "k_rope_rms": 1.0}
    r = runner.readings(got, want)
    assert r["update_worst"][1] <= runner.UPDATE_RTOL and runner.verdicts(r, 0, 64, 8)["grads"]
    at, key = leaf(after, NORM)
    at[key] = leaf(before, NORM)[0][key]            # the step did not write it
    r = runner.readings(got, want)
    assert r["update_worst"][0] == NORM and abs(r["update_worst"][1] - 1.0) < 1e-2
    assert not runner.verdicts(r, 0, 64, 8)["grads"]
    assert np.sqrt(512 / (1024 * 1024)) < 25 * runner.UPDATE_RTOL      # pooled: 2.2e-2
    at[key] = leaf(before, NORM)[0][key] + runner.adamw_first_step(leaf(g, NORM)[0][key])
    # a leaf with no gradient has to stand as it was
    at, key = leaf(after, BIAS)
    at[key] = np.full(8, 1e-5, np.float32)
    assert runner.readings(got, want)["update_worst"] == (BIAS, float("inf"))


def test_the_runners_positions_and_first_step_rule():
    from benchmark.runners import train_steps_kimi_vl as runner
    at = runner.logit_positions(4, 8192)
    assert at.shape == (4, runner.LOGIT_POSITIONS // 4)
    assert at[0][0] == 0 and at[0][-1] == 8190 and (np.diff(at[0]) > 0).all()
    assert runner.logit_positions(4, 64).max() == 62        # a position with a next token
    update = runner.adamw_first_step(np.array([0.5, -2.0, 0.0], np.float32))
    np.testing.assert_allclose(update, [-runner.LR, runner.LR, 0.0], rtol=1e-6)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_prints_the_contracts_last_line(trace):
    from deepspeed_tpu.utils.hostdev import force_host_devices_env
    env = force_host_devices_env(4 if trace else 1, extra={"PYTHONPATH": ROOT})
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 41), "--seconds", "3", "--trace", str(trace), "--rehearse"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    said = next(ln for ln in lines if ln.startswith("training:"))
    # the cell's one chip, however many the host has
    assert "'data': 1," in said and "2 of 16 experts held" in said
    assert "latent+dense/latent+moe" in said and "batch 4 x 64" in said
    check = next(ln for ln in lines if ln.startswith("correctness:"))
    assert "FAILED" not in check and "kv_a_proj_with_mqa" in check and "latent rms" in check
    notes = json.loads(next(ln for ln in lines if ln.startswith("notes: "))[7:])
    assert notes["model_layers"] == {"latent+dense": 1.0, "latent+moe": 1.0}
    assert all(notes["verdicts"].values()) and notes["share_fallback_layers"] == 0.0
    assert notes["step_programs"] == 1 and notes["n_params"] == kimi_cost.param_count(
        {**config(), **config()["rehearse"]})
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in cell_metrics(load_manifest(), CELL, group)}
    assert set(line["metrics"]) <= set(declared)
    for name, got in line["metrics"].items():
        assert got["unit"] == declared[name]["unit"] and np.isfinite(got["value"])
    if trace:
        # no kernel events and no utilization on a CPU; the scopes are read
        for absent in ("kernel.mla_fwd_roofline", "kernel.mla_bwd_roofline",
                       "mla.kernel_ms_per_step", "step.mfu_pct"):
            assert absent not in line["metrics"]
        # (the two scope readers only where the step was compiled in this
        # process: one loaded from the persistent cache brings no op paths)
        assert {"setup.compile_s", "device.idle_pct.train", "moe.rows_held_pct",
                "moe.load_max_over_mean"} <= set(line["metrics"])
        assert 5 < line["metrics"]["moe.rows_held_pct"]["value"] < 25
    else:
        assert set(line["metrics"]) == {"setup_s", "train_tok_s"}
        assert all(v["value"] > 0 for v in line["metrics"].values())
