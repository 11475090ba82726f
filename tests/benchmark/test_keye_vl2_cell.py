"""The Keye-VL cell's own tests: its configuration against the published
values, its parameter and FLOP counts by hand, the sparse-attention kernels'
cost function on made-up events (the CHOSEN pairs, the backward counted by
the kernel's name), its readers, its manifest entries by membership (never
"last" and no ordered list: the next cell appends after these), the chip's
calibration readings through the limits as they are, and a rehearsal of the
runner end to end. All on the CPU; no number here is a measurement."""

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
from benchmark import dsa_cost, keye_cost  # noqa: E402
from benchmark.run import cell_metrics, load_json, load_manifest, load_module  # noqa: E402

CELL, CONFIG = "train-keyevl2-1chip-dsa-seq32k", "keye-vl-2.0-30b-a3b-ep8-train1"
SOURCE = "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json"
# the language model's keys of config.json at SOURCE, as the catalog has them
PUBLISHED = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
             "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
             "max_position_embeddings": 262144, "max_window_layers": 48,
             "mlp_only_layers": [], "model_type": "KeyeVL2", "moe_intermediate_size": 768,
             "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 128,
             "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
             "num_local_experts": 128, "rms_norm_eps": 1e-06,
             "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                              "type": "default"},
             "rope_theta": 10000000,
             "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                           "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                           "q_chunk_size": 512, "topk": 2048},
             "sliding_window": None, "tie_word_embeddings": False,
             "use_sliding_window": False, "vocab_size": 151936}
REDUCED = ["num_hidden_layers", "num_experts", "num_local_experts", "vocab_size"]
NEW = {"kernel.dsa_index_roofline": ("kernel", "%", "device_trace"),
       "kernel.dsa_fwd_roofline": ("kernel", "%", "device_trace"),
       "kernel.dsa_bwd_roofline": ("kernel", "%", "device_trace"),
       "dsa.attn_ms_per_step": ("kernel", "ms", "device_trace"),
       "dsa.choose_ms_per_step": ("sparse attention operator", "ms", "device_trace"),
       "dsa.chosen_pct": ("sparse attention operator", "%", "program_counter")}
SHARED = ["step.mfu_pct", "device.idle_pct.train", "host.work_ms_per_step",
          "host.idle_unnamed_pct.train", "setup.engine_init_s", "setup.place_params_s",
          "moe.gmm_ms_per_step", "moe.load_max_over_mean", "kernel.moe_gmm_held_roofline",
          "moe.rows_held_pct"]
# device events as a v5e's compiled step names them (1 row, 4 x 8 heads of 128)
T = 32768
INDEX = ("%dsa_index.3 = (s32[1,32768,1]{2,1,0:T(8,128)}, s32[1,32768,1]{2,1,0:T(8,128)}) "
         "custom-call(bf16[1,16,32768,64]{3,2,1,0:T(8,128)(2,1)}, bf16[1,32768,64]")
FWD = ("%dsa_fwd.3 = (bf16[1,4,8,32768,128]{4,3,2,1,0:T(8,128)(2,1)}, "
       "f32[1,4,256,1,1024]{4,3,2,1,0:T(1,128)}, s32[1,32768,1]{2,1,0:T(8,128)}, "
       "f32[1,32768,1]{2,1,0:T(8,128)}) custom-call(bf16[1,4,8,32768,128]")
BWD_DQ = "%dsa_bwd_dq.1 = bf16[1,4,8,32768,128]{4,3,2,1,0:T(8,128)(2,1)} custom-call(bf16[1,4"
BWD_DKDV = ("%dsa_bwd_dkdv.1 = (bf16[1,4,32768,128]{3,2,1,0:T(8,128)(2,1)}, "
            "bf16[1,4,32768,128]{3,2,1,0:T(8,128)(2,1)}) custom-call(")
FLASH = "%flash_fwd.2 = (bf16[4,8,32768,128]{3,2,1,0:T(8,128)(2,1)}, f32[4,8,32768,1]"
CAUSAL = T * (T + 1) // 2                               # 536,887,296
CHOSEN = 2048 * 2049 // 2 + (T - 2048) * 2048           # 65,012,736


def config() -> dict:
    return load_json("configs", CONFIG + ".json")


def read(name, run):
    return load_module("layers", name).read(run)


def test_the_configuration_is_the_published_one_but_for_the_stated_cuts():
    cfg = config()
    for key, value in PUBLISHED.items():
        assert cfg[key] == value or key in REDUCED, key
    assert cfg["reduced"] == REDUCED and cfg["source"] == SOURCE
    assert cfg["published"] == {k: PUBLISHED[k] for k in REDUCED}
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["num_local_experts"],
            cfg["vocab_size"]) == (6, 16, 16, 18992)
    assert 151936 == 8 * 18992 and 128 == 8 * 16
    assert "each layer shared over 8 chips" in cfg["deployment"]
    assert cfg["num_dense_layers"] == 0 and cfg["remat"] and cfg["remat_policy"] is None
    from benchmark.runners import train_steps_keye_vl2 as runner
    model = runner.model_config(cfg)
    assert (model.num_local_experts, model.experts_held_, model.moe_share_index) == (128, 16, 0)
    assert (model.dsa_topk, model.dsa_index_heads, model.dsa_index_head_dim) == (2048, 16, 64)
    assert model.ce_chunk_size == cfg["ce_chunk_size"] and model.vocab_size == 18992


def test_manifest_entries_of_the_cell_and_the_checks_every_manifest_passes():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        admitted = json.load(f)
    manifest_checks.check_admitted(admitted)
    manifest_checks.check_entries(load_manifest())
    cells = {w["name"]: w for w in admitted["workloads"]}
    # the cells admitted before it keep their order; this one comes after them
    assert list(cells).index(CELL) > list(cells).index("train-kimivl-1chip-seq8k")
    assert cells[CELL]["chips"] == 1 and cells[CELL]["config"] == CONFIG
    assert cells[CELL]["traffic"] == "keyevl2-1chip-dsa-seq32k"
    entry = next(c for c in admitted["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED and entry["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    cell = load_json("workloads", CELL + ".json")
    assert cell["traffic"] == {"global_batch": 1, "seq_len": T,
                               "warmup_steps": 2, "trace_steps": 4}
    assert cell["runner"] == "train_steps_keye_vl2" == config()["runner"]
    assert cell["why"] == cells[CELL]["why"] and "1 x 32,768 tokens" in cell["why"]
    assert len(cell["why"]) <= 200 and "8x" in cell["why"]
    m = load_manifest()
    assert [x["name"] for x in cell_metrics(m, CELL, "end_to_end")] \
        == ["setup_s", "train_tok_s"] == cell["end_to_end"]
    layers = {x["name"]: x for x in cell_metrics(m, CELL, "per_layer")}
    assert set(layers) == set(SHARED) | set(NEW) | {
        "setup.compile_s", "setup.programs", "setup.cache_misses"}
    # not under the readers that would credit these calls with causal work
    for absent in ("kernel.flash_fwd_roofline", "kernel.flash_bwd_roofline",
                   "flash.kernel_ms_per_step", "kernel.moe_gmm_roofline",
                   "coll.exposed_ms_per_step", "setup.cost_analysis_s"):
        assert absent not in layers
    assert not any(name.startswith("scope.") for name in layers)
    names = [x["name"] for x in admitted["per_layer"]]
    for name, (layer, unit, source) in NEW.items():
        assert layers[name]["layer"] == layer and layers[name]["moves"] == "train_tok_s"
        assert layers[name]["workloads"] == [CELL] and layers[name]["unit"] == unit
        assert layers[name]["source"] == source
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layers", name + ".py"))
        # after every metric the accepted benchmark had
        assert names.index(name) > names.index("setup.cost_analysis_s")
    for name in SHARED + ["train_tok_s"]:
        metric = next(x for x in admitted["per_layer"] + admitted["end_to_end"]
                      if x["name"] == name)
        cells_of = metric["workloads"]
        assert cells_of.count(CELL) == 1
        assert cells_of.index(CELL) > cells_of.index("train-kimivl-1chip-seq8k")


def test_parameters_bytes_and_flops_by_hand():
    cfg = config()
    attention = 2 * 2048 * 4096 + 2 * 2048 * 512
    indexer = 2048 * 1024 + 2048 * 64 + 2048 * 16
    layer = attention + 2 * 128 + indexer + 2 * 64 + 2 * 2048 + 2048 * 128 \
        + 16 * 3 * 2048 * 768
    assert (attention, indexer + 128, layer) == (18_874_368, 2_261_120, 96_899_456)
    n = 6 * layer + 2 * 18992 * 2048 + 2048
    assert keye_cost.param_count(cfg) == n == 659_190_016
    assert keye_cost.bytes_at_rest(cfg) == 12 * n
    assert 0.25 * 16.9e9 < 12 * n < 0.5 * 16.9e9        # 7.91 GB: 47% of the chip
    assert keye_cost.experts_held_per_token(cfg) == 1.0
    f = keye_cost.forward_flops_per_token(cfg, T)
    scores, attended = 2 * 16 * 64 * CAUSAL / T, 4 * 32 * 128 * CHOSEN / T
    assert abs(CAUSAL / T - 16384.5) < 1e-9 and abs(CHOSEN / T - 1984.03) < 0.01
    np.testing.assert_allclose(f["indexer"], 6 * (2 * indexer + scores))
    np.testing.assert_allclose(
        f["rest"], 6 * (2 * attention + 2 * 2048 * 128 + 2 * 3 * 2048 * 768 + attended)
        + 2 * 2048 * 18992)
    np.testing.assert_allclose(keye_cost.train_flops_per_token(cfg, T),
                               3 * f["rest"] + f["indexer"])
    assert abs(keye_cost.train_flops_per_token(cfg, T) - 1.906e9) < 1e6
    # the cell's tokens a step at the peak: a third of a second
    assert abs(keye_cost.train_flops_per_token(cfg, T) * T / 197e12 - 0.317) < 1e-3


def test_kernel_cost_by_hand_counts_chosen_pairs_and_the_backward_by_its_name():
    cfg = config()
    assert dsa_cost.causal_pairs(T) == CAUSAL and dsa_cost.chosen_pairs(T, 2048) == CHOSEN
    assert dsa_cost.chosen_pairs(1000, 2048) == dsa_cost.causal_pairs(1000)
    assert abs(100.0 * CHOSEN / CAUSAL - 12.11) < 5e-3
    assert abs(100.0 * dsa_cost.chosen_pairs(16384, 2048)
               / dsa_cost.causal_pairs(16384) - 23.44) < 5e-3
    assert dsa_cost.call_cost("%dsa_index.3", INDEX, cfg)["flops"] == 2.0 * 16 * 64 * CAUSAL
    fwd = dsa_cost.call_cost("%dsa_fwd.3", FWD, cfg)
    assert fwd["flops"] == 4.0 * 32 * 128 * CHOSEN      # the chosen pairs, not the causal
    assert fwd["bytes"] == 2.0 * T * 128 * (2 * 32 + 2 * 4)
    dq = dsa_cost.call_cost("%dsa_bwd_dq.1", BWD_DQ, cfg)
    dkdv = dsa_cost.call_cost("%dsa_bwd_dkdv.1", BWD_DKDV, cfg)
    assert dq["flops"] == dkdv["flops"] == fwd["flops"]  # a pair is twice the forward
    assert dsa_cost.call_cost("%dsa_fwd.3", FWD, {"hidden_size": 4096}) is None
    assert dsa_cost.call_cost("%dsa_fwd.3", "%dsa_fwd.3 = bf16[64,8192]{1,0} cust", cfg) is None
    assert dsa_cost.call_cost("%dsa_index.3", FWD, cfg) is None
    # compute-bound: 1.07e12 FLOP forward against 0.6 GB of q, k, v and o
    assert fwd["flops"] / 197e12 > 7 * fwd["bytes"] / 819e9


def made_up_run(kernels) -> dict:
    return {"trace": {"kernels": kernels}, "config": config(), "trace_steps": 4,
            "device": {"kind": "TPU v5 lite", "count": 1}, "tokens_per_step": T}


def test_readers_on_a_made_up_trace(monkeypatch):
    from benchmark import host_spans, scope_time
    monkeypatch.setattr(host_spans, "_xplane_path", lambda: None)
    peak = 197e12
    fwd_flops = 4.0 * 32 * 128 * CHOSEN
    run = made_up_run({
        "%dsa_index.3": {"hlo": INDEX, "count": 24, "seconds": 24 * 0.030},
        "%dsa_fwd.3": {"hlo": FWD, "count": 24, "seconds": 24 * 0.080},
        "%dsa_bwd_dq.1": {"hlo": BWD_DQ, "count": 24, "seconds": 24 * 0.090},
        "%dsa_bwd_dkdv.1": {"hlo": BWD_DKDV, "count": 24, "seconds": 24 * 0.110},
        "%flash_fwd.2": {"hlo": FLASH, "count": 4, "seconds": 1.0},       # not ours
        "%fusion.7": {"hlo": "%fusion.7 = bf16[1,32768,2048]", "count": 9, "seconds": 0.3}})
    np.testing.assert_allclose(read("kernel.dsa_index_roofline", run),
                               100 * 2.0 * 16 * 64 * CAUSAL / peak / 0.030)
    np.testing.assert_allclose(read("kernel.dsa_fwd_roofline", run),
                               100 * fwd_flops / peak / 0.080)
    np.testing.assert_allclose(read("kernel.dsa_bwd_roofline", run),
                               100 * 2 * fwd_flops / peak / 0.200)
    np.testing.assert_allclose(read("dsa.attn_ms_per_step", run), 1e3 * 6 * 0.280)
    for name in ("kernel.dsa_index_roofline", "kernel.dsa_fwd_roofline",
                 "kernel.dsa_bwd_roofline"):
        assert 0 < read(name, run) < 100
    # the choice: the index kernels and both scopes, every phase
    np.testing.assert_allclose(read("dsa.choose_ms_per_step", run), 1e3 * 6 * 0.030)
    table = {"ds_ms": {("ds.dsa.index", "fwd"): 3.0, ("ds.dsa.index", "recompute"): 2.5,
                       ("ds.dsa.select", "fwd"): 0.5, ("ds.rope", "fwd"): 1.0}}
    monkeypatch.setattr(scope_time, "load", lambda run: table)
    np.testing.assert_allclose(read("dsa.choose_ms_per_step", run), 180.0 + 6.0)
    np.testing.assert_allclose(read("dsa.choose_ms_per_step", made_up_run({})), 6.0)
    run["dsa_chosen_share_samples"] = [CHOSEN / CAUSAL] * 4
    assert abs(read("dsa.chosen_pct", run) - 12.11) < 5e-3


def test_readers_report_nothing_when_nothing_matched(monkeypatch):
    """A program without the kernels or the scopes (the parent commit, a CPU
    rehearsal of another cell): every new reader returns None and raises
    nothing."""
    from benchmark import host_spans
    monkeypatch.setattr(host_spans, "_xplane_path", lambda: None)
    for run in ({}, {"trace": None}, made_up_run({}),
                made_up_run({"%flash_fwd.2": {"hlo": FLASH, "count": 4, "seconds": 1.0}})):
        for name in NEW:
            assert read(name, dict(run)) is None, name
    other = made_up_run({"%dsa_fwd.3": {"hlo": FWD, "count": 1, "seconds": 1.0}})
    other["config"] = {"hidden_size": 2048}
    assert read("kernel.dsa_fwd_roofline", other) is None


def made_up_readings(**over) -> dict:
    pairs = CHOSEN
    return dict({"loss_err": 2e-5, "loss_after_err": 2e-5, "descends": True,
                 "logit_median": 1.5e-2, "logit_p90": 4.4e-2,
                 "grad_worst": ("['k_norm']", 0.19), "grad_routed_worst": ("['w1']", 0.12),
                 "grad_router_median": 0.114, "update_worst": ("['norm']", 3e-4),
                 "indexer_untouched": True,
                 "counts": [[12288] * 128], "assigned": [1_572_864] * 2, "moved": 700,
                 "rows_held": [196_608, 196_700], "share_fallback": 0,
                 "chosen_pairs": [[pairs] * 6, [pairs] * 6],
                 "kth_score_mean": [0.40007, 0.40003],
                 "overlap_by_layer": [0.996, 0.993, 0.990, 0.984, 0.979, 0.976]}, **over)


@pytest.mark.parametrize("fails,over", [
    (set(), {}),
    ({"loss"}, {"descends": False}), ({"loss"}, {"loss_after_err": 8e-3}),
    ({"logits"}, {"logit_median": 5.57e-2}),            # the chip's fp8
    ({"logits"}, {"logit_p90": float("nan")}),
    ({"grads"}, {"grad_worst": ("['q_proj']", float("inf"))}),
    ({"grads"}, {"grad_worst": ("['norm']", 0.4065)}),  # the chip's fp8, its least
    ({"grads"}, {"grad_routed_worst": ("['w3']", 0.2196)}),
    ({"grads"}, {"grad_router_median": 0.2017}),
    ({"grads"}, {"update_worst": ("['q_norm']['weight']", 1.0)}),
    ({"grads"}, {"indexer_untouched": False}),          # a gradient reached the indexer
    ({"routing"}, {"moved": 1_970}),                    # no ReLU's 1.25e-3 of all
    ({"routing"}, {"rows_held": [196_068, 197_691]}),   # the choice without the causal limit
    ({"routing"}, {"share_fallback": 1}),
    ({"routing"}, {"assigned": [1_572_864, 1_572_000]}),
    ({"choice"}, {"chosen_pairs": [[CHOSEN] * 5 + [CHOSEN + 1], [CHOSEN] * 6]}),  # a tie taken twice
    ({"choice"}, {"chosen_pairs": [[CAUSAL] * 6, [CHOSEN] * 6]}),   # the choice ignored
    ({"choice"}, {"kth_score_mean": [0.41606, 0.41645]}),           # the chip's fp8, its least
    ({"choice"}, {"kth_score_mean": [float("nan"), 0.40003]}),
    ({"choice"}, {"overlap_by_layer": [0.996, 0.99, 0.99, 0.98, 0.98, 0.927]})])   # fp8's
def test_verdicts_by_hand(fails, over):
    from benchmark.runners import train_steps_keye_vl2 as runner
    ok = runner.verdicts(made_up_readings(**over), 1_572_864, 128, 16, CHOSEN)
    assert {k for k, good in ok.items() if not good} == fails
    wide = runner.verdicts(made_up_readings(logit_median=5.57e-2, moved=1_970), 1_572_864,
                           128, 16, CHOSEN, slack=runner.REHEARSAL_SLACK)
    assert all(wide.values())       # a rehearsal's slack widens the distances
    near_zero = made_up_readings(kth_score_mean=[-0.0831, -0.0839])
    assert not runner.verdicts(near_zero, 1_572_864, 128, 16, CHOSEN)["choice"]
    assert runner.verdicts(near_zero, 1_572_864, 128, 16, CHOSEN,
                           slack=runner.REHEARSAL_SLACK)["choice"]    # absolute there


def chip_readings() -> list:
    path = os.path.join(ROOT, "benchmark", "readings", "keye_vl2_calibration.jsonl")
    with open(path) as f:
        return [json.loads(line) for line in f]


def chip_verdicts(row: dict) -> dict:
    """A stored line through the limits as they are now (the line keeps the
    counts' sums, not the counts: one is made up that has them)."""
    from benchmark.runners import train_steps_keye_vl2 as runner
    counts = np.zeros(128, np.int64)
    counts[0], counts[16] = row["rows_held"][0], row["assigned"][0] - row["rows_held"][0]
    return runner.verdicts({**row, "counts": [counts.tolist()]}, T * 8 * 6, 128, 16, CHOSEN)


@pytest.mark.parametrize("against", ["sound", "bf16", "fp8", "dense", "top1024", "no_relu",
                                     "no_w", "acausal_choice"])
def test_the_limits_stand_between_what_the_chip_read(against):
    """The chip's readings of the timed step at 1 x 32,768 tokens against the
    reference sound and made wrong (``calibrate_keye_vl2.py``, kept in
    ``benchmark/readings/``), through the runner's limits as they are now:
    each wrong way gives ``correct`` false, fp8 (the precision below the
    configuration's) by the logits, the gradients, the assignments moved and
    the choice's statistics and NOT by the losses or the rows held; the sound
    program passes with room, and so does a reference at the configuration's
    own bf16. A limit moved past either reading fails here."""
    from benchmark.runners import train_steps_keye_vl2 as runner
    rows = [r for r in chip_readings() if r["against"] == against]
    # two seeds for the sound program and the precisions; the ways that are
    # wrong by a factor were read on one
    assert len({r["seed"] for r in rows}) == (2 if against in ("sound", "bf16", "fp8") else 1)
    for row in rows:
        ok = chip_verdicts(row)
        assert all(ok.values()) == (against in ("sound", "bf16")), (row["seed"], ok)
        assert row["lr"] == runner.LR and row["descends"] and row["indexer_untouched"]
        assert row["update_worst"][1] < runner.UPDATE_RTOL / 3
        assert row["loss_err"] < runner.LOSS_RTOL / 8       # no loss tells a wrong model
    for row in rows if against == "sound" else []:          # room under each limit
        assert row["logit_median"] < runner.LOGIT_MEDIAN_RTOL / 1.9
        assert row["logit_p90"] < runner.LOGIT_P90_RTOL / 1.5
        assert row["grad_worst"][1] < runner.GRAD_RTOL / 1.5
        assert row["grad_routed_worst"][1] < runner.GRAD_ROUTED_RTOL / 1.35
        assert row["grad_router_median"] < runner.GRAD_ROUTER_RTOL / 1.35
        assert row["moved"] / row["assigned"][0] < runner.COUNT_MOVED_SHARE / 1.45
        assert row["loss_err"] < runner.LOSS_RTOL / 40
        kth = row["kth_score_mean"]
        assert abs(kth[0] - kth[1]) < runner.KTH_RTOL * abs(kth[1]) / 2.2
        assert 1 - min(row["overlap_by_layer"]) < (1 - runner.OVERLAP_MIN) / 1.8
        assert row["chosen_pairs"] == [[CHOSEN] * 6, [CHOSEN] * 6]
    for row in rows if against == "fp8" else []:
        ok = chip_verdicts(row)
        assert not (ok["logits"] or ok["grads"] or ok["routing"] or ok["choice"])
        assert ok["loss"]                                   # not by each
        assert row["logit_median"] > runner.LOGIT_MEDIAN_RTOL * 1.85
        assert row["grad_worst"][1] > runner.GRAD_RTOL * 1.35
        assert row["grad_routed_worst"][1] > runner.GRAD_ROUTED_RTOL * 1.29
        assert row["grad_router_median"] > runner.GRAD_ROUTER_RTOL * 1.3
        assert row["moved"] / row["assigned"][0] > runner.COUNT_MOVED_SHARE * 3
        kth, held = row["kth_score_mean"], row["rows_held"]
        assert abs(kth[0] - kth[1]) > runner.KTH_RTOL * abs(kth[1]) * 1.5
        assert 1 - min(row["overlap_by_layer"]) > (1 - runner.OVERLAP_MIN) * 1.6
        assert abs(held[0] - held[1]) < runner.ROWS_HELD_RTOL * held[1] / 2
        assert row["chosen_pairs"][1] == [CHOSEN] * 6       # a precision keeps the count
    for row in rows:
        ok = chip_verdicts(row)
        if against in ("dense", "top1024", "acausal_choice"):   # the count itself tells
            assert row["chosen_pairs"][1][0] != CHOSEN and not ok["choice"]
        if against == "dense":
            assert row["chosen_pairs"][1] == [CAUSAL] * 6
        if against in ("no_relu", "no_w"):          # the count does not: the scores do
            assert row["chosen_pairs"][1] == [CHOSEN] * 6 and not ok["choice"]
            assert min(row["overlap_by_layer"]) < 0.71
        if against not in ("sound", "bf16"):
            assert not ok["logits"] and not ok["grads"]
            assert row["logit_median"] > runner.LOGIT_MEDIAN_RTOL * 1.85
        if against == "acausal_choice":
            held = row["rows_held"]
            assert abs(held[0] - held[1]) > runner.ROWS_HELD_RTOL * held[1] * 3


def test_the_runners_positions_and_queries():
    from benchmark.runners import train_steps_keye_vl2 as runner
    at = runner.logit_positions(1, T)
    assert at.shape == (1, runner.LOGIT_POSITIONS) and at.max() == T - 2
    assert (at >= 2048).sum() >= runner.LOGIT_POSITIONS // 2    # where the choice is one
    sample = runner.choice_queries(1, T)
    assert sample.shape == (1, runner.CHOICE_QUERIES)
    assert sample[0][0] == 0 and sample[0][-1] == T - 1 and (np.diff(sample[0]) > 0).all()


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_prints_the_contracts_last_line(trace):
    from deepspeed_tpu.utils.hostdev import force_host_devices_env
    env = force_host_devices_env(4 if trace else 1, extra={"PYTHONPATH": ROOT})
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 45), "--seconds", "3", "--trace", str(trace), "--rehearse"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    said = next(ln for ln in lines if ln.startswith("training:"))
    # the cell's one chip, however many the host has
    assert "'data': 1," in said and "2 of 16 experts held" in said
    assert "sparse attention top-32 by a 2 x 16 indexer" in said and "batch 1 x 128" in said
    check = next(ln for ln in lines if ln.startswith("correctness:"))
    assert "FAILED" not in check and "pairs chosen a layer" in check
    assert "unwritten on both sides: True" in check
    notes = json.loads(next(ln for ln in lines if ln.startswith("notes: "))[7:])
    pairs = dsa_cost.chosen_pairs(128, 32)
    assert notes["chosen_pairs_first_batch"] == [[pairs, pairs], [pairs, pairs]]
    assert all(notes["verdicts"].values()) and notes["share_fallback_layers"] == 0.0
    assert notes["step_programs"] == 1 and notes["n_params"] == keye_cost.param_count(
        {**config(), **config()["rehearse"]})
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in cell_metrics(load_manifest(), CELL, group)}
    assert set(line["metrics"]) <= set(declared)
    for name, got in line["metrics"].items():
        assert got["unit"] == declared[name]["unit"] and np.isfinite(got["value"])
    if trace:
        # no kernel events and no utilization on a CPU; the counter is read
        for absent in ("kernel.dsa_index_roofline", "kernel.dsa_fwd_roofline",
                       "kernel.dsa_bwd_roofline", "dsa.attn_ms_per_step", "step.mfu_pct"):
            assert absent not in line["metrics"]
        assert {"setup.compile_s", "device.idle_pct.train", "moe.rows_held_pct",
                "dsa.chosen_pct"} <= set(line["metrics"])
        np.testing.assert_allclose(line["metrics"]["dsa.chosen_pct"]["value"],
                                   100.0 * pairs / dsa_cost.causal_pairs(128))
    else:
        assert set(line["metrics"]) == {"setup_s", "train_tok_s"}
        assert all(v["value"] > 0 for v in line["metrics"].values())
