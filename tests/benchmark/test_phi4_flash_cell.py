"""The Phi-4-mini-flash cell's own tests: its parameter and FLOP counts by hand,
the two cost files on made-up events against a count by hand, its readers, its
manifest entries by membership and relative order (never "last", never an
ordered list of all metrics: the next cell appends after these), the runner's
verdicts by hand, the chip's calibration readings through the limits as they
are, and a rehearsal of the runner end to end. All on the CPU; no number here
is a measurement."""

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
from benchmark import diffattn_cost, phi4flash_cost, selscan_cost  # noqa: E402
from benchmark.run import cell_metrics, load_json, load_manifest, load_module  # noqa: E402

CELL = "train-phi4flash-1chip-sambay-seq16k"
CONFIG = "phi-4-mini-flash-reasoning-vp8-train1"
SOURCE = "https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json"
REDUCED = ["num_hidden_layers", "layer_types", "vocab_size"]
NEW = {"kernel.selscan_fwd_roofline": ("kernel", "%", "higher"),
       "kernel.selscan_bwd_roofline": ("kernel", "%", "higher"),
       "selscan.kernel_ms_per_step": ("state-space mixer", "ms", "lower"),
       "selscan.dt_ms_per_step": ("state-space mixer", "ms", "lower"),
       "kernel.diffattn_fwd_roofline": ("kernel", "%", "higher"),
       "kernel.diffattn_bwd_roofline": ("kernel", "%", "higher"),
       "diffattn.kernel_ms_per_step": ("kernel", "ms", "lower"),
       "diffattn.combine_ms_per_step": ("differential attention operator", "ms", "lower")}
SHARED = ["step.mfu_pct", "device.idle_pct.train", "host.work_ms_per_step",
          "host.idle_unnamed_pct.train", "setup.engine_init_s", "setup.place_params_s",
          "setup.cost_analysis_s"]
SEQ = 16384
# device events as a v5e's compiled step names them (1 row, 5,120 channels of
# 16 states; 40 stacked query heads in groups of 2 over 20 key heads)
SCAN_FWD = ("%selscan_fwd.1 = (bf16[1,16384,40,128]{3,2,1,0:T(8,128)(2,1)}, "
            "f32[1,128,16,40,128]{4,3,2,1,0:T(8,128)}, f32[1,5,8,128]{3,2,1,0:T(8,128)}) cu")
SCAN_BWD = ("%selscan_bwd.1 = (bf16[1,16384,40,128]{3,2,1,0:T(8,128)(2,1)}, "
            "f32[1,16384,40,128]{3,2,1,0:T(8,128)}, f32[1,16,40,128]{3,2,1,0:T(8,128)}, f32[")
ATTN_FWD = ("%mla_fwd.5 = (bf16[20,2,16384,128]{3,2,1,0:T(8,128)(2,1)}, "
            "f32[20,2,16384,1]{3,2,1,0:T(8,128)}) custom-call(")
ATTN_BWD = ("%mla_bwd.4 = (bf16[20,16384,64]{2,1,0:T(8,128)(2,1)}, "
            "bf16[20,16384,128]{2,1,0:T(8,128)(2,1)}, bf16[20,2,16384,64]) custom-call(")
OTHER = "%ssd_chunk_fwd.3 = (bf16[1,16384,4096]{2,1,0:T(8,128)(2,1)}, f32[1,64,128,4096]"


def config() -> dict:
    return load_json("configs", CONFIG + ".json")


def read(name, run):
    return load_module("layers", name).read(run)


def test_manifest_entries_of_the_cell_and_the_checks_every_manifest_passes():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        admitted = json.load(f)
    manifest_checks.check_admitted(admitted)
    manifest_checks.check_entries(load_manifest())
    cells = {w["name"]: w for w in admitted["workloads"]}
    # the cells admitted before it keep their order; this one comes after them
    assert list(cells).index(CELL) > list(cells).index("train-ling3flash-1chip-kda-longseq")
    assert cells[CELL]["chips"] == 1 and cells[CELL]["config"] == CONFIG
    assert cells[CELL]["traffic"] == "phi4flash-1chip-sambay-seq16k"
    assert [w["name"] for w in admitted["workloads"] if w["chips"] == 4] \
        == ["train-zero3-seq4k"]                               # still the one on four
    entry = next(c for c in admitted["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED == config()["reduced"] and entry["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    cell = load_json("workloads", CELL + ".json")
    assert cell["traffic"] == {"global_batch": 1, "seq_len": SEQ,
                               "warmup_steps": 2, "trace_steps": 4}
    assert cell["runner"] == "train_steps_phi4_flash" == config()["runner"]
    assert cell["why"] == cells[CELL]["why"] and "1 x 16,384 tokens" in cell["why"]
    assert "512 window" in cell["why"] and len(cell["why"]) <= 200
    m = load_manifest()
    assert [x["name"] for x in cell_metrics(m, CELL, "end_to_end")] \
        == ["setup_s", "train_tok_s"] == cell["end_to_end"]
    layers = {x["name"]: x for x in cell_metrics(m, CELL, "per_layer")}
    assert set(SHARED) | set(NEW) | {"setup.compile_s", "setup.programs",
                                     "setup.cache_misses"} <= set(layers)
    # not under the readers that would credit its calls with another kernel's work
    for absent in ("kernel.flash_fwd_roofline", "kernel.ssd_fwd_roofline",
                   "ssm.kernel_ms_per_step", "kda.kernel_ms_per_step",
                   "moe.gmm_ms_per_step", "coll.exposed_ms_per_step"):
        assert absent not in layers
    names = [x["name"] for x in admitted["per_layer"]]
    for name, (layer, unit, better) in NEW.items():
        assert layers[name]["layer"] == layer and layers[name]["moves"] == "train_tok_s"
        assert layers[name]["workloads"][0] == CELL and layers[name]["unit"] == unit
        assert layers[name]["source"] == "device_trace" and layers[name]["better"] == better
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layers", name + ".py"))
        # membership and relative order: after every metric the benchmark had
        assert names.index(name) > names.index("setup.first_call_unnamed_pct")
    at = [names.index(name) for name in NEW]
    assert at == sorted(at)
    for name in SHARED + ["train_tok_s"]:
        metric = next(x for x in admitted["per_layer"] + admitted["end_to_end"]
                      if x["name"] == name)
        cells_of = metric["workloads"]
        assert cells_of.count(CELL) == 1
        # appended: after every cell of the list that was admitted before this one
        assert all(cells_of.index(c) < cells_of.index(CELL) for c in cells_of
                   if list(cells).index(c) < list(cells).index(CELL))


def test_the_configurations_file_says_what_was_published_reduced_and_assumed():
    cfg, row = config(), {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2, "model_type": "phi4flash",
        "num_attention_heads": 40, "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512, "tie_word_embeddings": True,
        "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064}
    for key, value in row.items():      # every key of the catalog row, as published
        assert cfg[key] == value or key in REDUCED, key
        assert (key in REDUCED) == (cfg[key] != value)
    assert cfg["source"] == SOURCE and cfg["reduced"] == REDUCED
    assert (cfg["num_hidden_layers"], cfg["vocab_size"], cfg["layer_offset"]) == (6, 25008, 14)
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] == 200064
    assert cfg["published"]["num_hidden_layers"] == 32
    kinds = cfg["published"]["layer_types"]
    assert kinds[14:20] == cfg["layer_types"] and len(kinds) == 32
    assert [kinds.count(k) for k in ("mamba", "sliding_attention", "full_attention", "gmu",
                                     "cross_attention")] == [9, 8, 1, 7, 7]
    assert "each layer shared over 8 chips" in cfg["deployment"]
    assert "over-represented" in cfg["deployment"]
    said = " ".join(cfg["assumed"].values())
    for stated in ("d_inner 5,120", "d_state 16", "d_conv 4", "dt_rank 160", "ADJACENT pairs",
                   "N(0, 0.1)", "lambda_init = 0.8 - 0.6 exp(-0.3 i)", "A_log = log(1..16)",
                   "log-uniform in [1e-3, 1e-1]", "160^-1/2", "biases on Wqkv and out_proj",
                   "64^-1/2", "explicit positional encoding", "3,852,562,944"):
        assert stated in said, stated
    assert set(cfg["assumed"]) >= {"layer_kinds", "mamba_sizes", "mamba_mixer", "mamba_init",
                                   "differential_attention", "gmu", "no_position_embedding"}
    small = {**cfg, **cfg["rehearse"]}
    assert (small["hidden_size"], small["sliding_window"], small["vocab_size"],
            small["mamba_d_state"], small["mamba_dt_rank"]) == (64, 8, 256, 4, 4)
    assert small["layer_types"] == cfg["layer_types"]       # the six kinds


def test_parameters_bytes_and_flops_by_hand():
    cfg = config()
    ffn, norms = 3 * 2560 * 10240, 2 * 2 * 2560
    mamba = (2560 * 10240 + (4 * 5120 + 5120) + 5120 * 192 + (160 * 5120 + 5120)
             + 5120 * 16 + 5120 + 5120 * 2560)
    attention = (2560 * 5120 + 5120) + (2560 * 2560 + 2560) + 4 * 64 + 128
    cross = 2 * (2560 * 2560 + 2560) + 4 * 64 + 128
    gmu = 2 * 2560 * 5120
    assert (ffn, mamba, attention, cross, gmu) == (
        78_643_200, 41_241_600, 19_668_864, 13_112_704, 26_214_400)
    layers = [mamba, attention, mamba, attention, gmu, cross]
    total = sum(layer + ffn + norms for layer in layers) + 25008 * 2560 + 2 * 2560
    assert phi4flash_cost.param_count(cfg) == total == 697_094_272 == cfg["n_params"]
    assert phi4flash_cost.bytes_at_rest(cfg) == 12 * total
    assert abs(phi4flash_cost.bytes_at_rest(cfg) / 1e9 - 8.37) < 0.005
    whole = dict(cfg, layer_types=cfg["published"]["layer_types"], vocab_size=200064)
    assert phi4flash_cost.param_count(whole) == 3_852_562_944
    assert phi4flash_cost.sizes(cfg) == {"inner": 5120, "state": 16, "conv": 4, "rank": 160}
    matrices = (6 * ffn + 2 * (2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560)
                + 2 * (2560 * 5120 + 2560 * 2560) + 2 * 2560 * 2560 + gmu + 25008 * 2560)
    assert abs(matrices - 696.8e6) < 0.05e6
    windowed = 4 * 192 * 20 * (512 * 513 / 2 + (SEQ - 512) * 512) / SEQ
    causal = 4 * 192 * 20 * (SEQ + 1) / 2
    assert abs(windowed - 7.74e6) < 0.01e6 and abs(causal - 125.8e6) < 0.05e6
    forward = 2 * matrices + windowed + 2 * causal
    np.testing.assert_allclose(phi4flash_cost.forward_flops_per_token(cfg, SEQ), forward)
    np.testing.assert_allclose(phi4flash_cost.train_flops_per_token(cfg, SEQ), 3 * forward)
    assert abs(3 * forward - 4.96e9) < 0.005e9


def test_the_scans_least_bytes_by_hand():
    cfg = config()
    values, states = SEQ * 5120, 4 * (SEQ // 128) * 5120 * 16
    fwd = values * (2 + 4 + 2) + 4 * SEQ * 32 + states
    bwd = values * (2 + 4 + 2 + 2 + 4) + 4 * SEQ * 64 + states
    assert selscan_cost.call_bytes(SCAN_FWD, cfg) == fwd
    assert selscan_cost.call_bytes(SCAN_BWD, cfg) == bwd
    assert abs(fwd - 0.715e9) < 1e6 and abs(bwd - 1.2205e9) < 1e6 and states == 41_943_040
    # the rehearsal's four states, and what is no scan of ours
    assert selscan_cost.call_bytes(SCAN_FWD, {"mamba_d_state": 4}) < fwd
    assert selscan_cost.call_bytes(OTHER, cfg) is None
    assert selscan_cost.call_bytes("%selscan_fwd.1 = bf16[16384,5120]{1,0} cust", cfg) is None


def test_differential_attentions_least_work_by_hand():
    cfg = config()
    pairs = 20
    live_window = 512 * 513 / 2 + (SEQ - 512) * 512
    live_causal = SEQ * (SEQ + 1) / 2
    assert diffattn_cost.layer_flops(cfg, "sliding_attention", 1, SEQ) == 768 * pairs * live_window
    for kind in ("full_attention", "cross_attention"):
        assert diffattn_cost.layer_flops(cfg, kind, 1, SEQ) == 768 * pairs * live_causal
    assert abs(live_causal / live_window - 16.26) < 0.01     # 32 to 1 in whole key blocks
    assert diffattn_cost.layer_bytes(cfg, 1, SEQ) == 2 * SEQ * 64 * (80 + 40)
    # compute-bound either way on a v5e
    assert 768 * pairs * live_window / 197e12 > diffattn_cost.layer_bytes(cfg, 1, SEQ) / 819e9
    run = made_up_run({"%mla_fwd.5": {"hlo": ATTN_FWD, "count": 4, "seconds": 0.1}})
    least = (768 * pairs * (live_window + 2 * live_causal)) / 197e12
    np.testing.assert_allclose(diffattn_cost.step_least_seconds(run, False), least)
    np.testing.assert_allclose(diffattn_cost.step_least_seconds(run, True), 2 * least)
    assert abs(least * 1e3 - 21.57) < 0.01


def made_up_run(kernels) -> dict:
    return {"trace": {"kernels": kernels}, "config": config(), "trace_steps": 4,
            "device": {"kind": "TPU v5 lite", "count": 1}, "tokens_per_step": SEQ}


def test_readers_on_a_made_up_trace(capsys):
    cfg = config()
    fwd, bwd = selscan_cost.call_bytes(SCAN_FWD, cfg), selscan_cost.call_bytes(SCAN_BWD, cfg)
    kernels = {
        # two layers' scans and one forward run again, four traced steps
        "%selscan_fwd.1": {"hlo": SCAN_FWD, "count": 12, "seconds": 12 * 0.004},
        "%selscan_bwd.1": {"hlo": SCAN_BWD, "count": 8, "seconds": 8 * 0.011},
        # the windowed layer's call and the two causal ones, forward and backward
        "%mla_fwd.3": {"hlo": ATTN_FWD.replace(".5", ".3"), "count": 4, "seconds": 4 * 0.002},
        "%mla_fwd.5": {"hlo": ATTN_FWD, "count": 4, "seconds": 4 * 0.025},
        "%mla_fwd.7": {"hlo": ATTN_FWD.replace(".5", ".7"), "count": 4, "seconds": 4 * 0.026},
        "%mla_bwd.4": {"hlo": ATTN_BWD, "count": 12, "seconds": 12 * 0.040},
        "%ssd_chunk_fwd.3": {"hlo": OTHER, "count": 4, "seconds": 1.0},       # not ours
        "%fusion.7": {"hlo": "%fusion.7 = bf16[1,16384,5120]", "count": 9, "seconds": 0.3}}
    run = made_up_run(kernels)
    # of the 12 forward scans the 8 that a backward call used are credited
    np.testing.assert_allclose(read("kernel.selscan_fwd_roofline", run),
                               100 * 8 * fwd / 819e9 / (12 * 0.004))
    np.testing.assert_allclose(read("kernel.selscan_bwd_roofline", run),
                               100 * bwd / 819e9 / 0.011)
    np.testing.assert_allclose(read("selscan.kernel_ms_per_step", run),
                               1e3 * (12 * 0.004 + 8 * 0.011) / 4)
    least = diffattn_cost.step_least_seconds(run, False)
    np.testing.assert_allclose(read("kernel.diffattn_fwd_roofline", run),
                               100 * least * 4 / (4 * (0.002 + 0.025 + 0.026)))
    np.testing.assert_allclose(read("kernel.diffattn_bwd_roofline", run),
                               100 * 2 * least * 4 / (12 * 0.040))
    np.testing.assert_allclose(read("diffattn.kernel_ms_per_step", run),
                               1e3 * (4 * 0.053 + 12 * 0.040) / 4)
    said = capsys.readouterr().out
    assert "%mla_fwd.3 2.000, %mla_fwd.5 25.000, %mla_fwd.7 26.000" in said   # windowed first
    for name in NEW:
        if "roofline" in name:
            assert 0 < read(name, run) < 100, name
    assert diffattn_cost.by_call(run)[0] == ("%mla_fwd.3", 2.0)


def test_readers_report_nothing_when_nothing_matched(monkeypatch):
    """A program without the kernels or the scopes (the parent commit, a CPU
    rehearsal, another cell): every new reader returns None and raises
    nothing."""
    from benchmark import host_spans, scope_time
    monkeypatch.setattr(host_spans, "_xplane_path", lambda: None)
    for run in ({}, {"trace": None}, made_up_run({}),
                made_up_run({"%ssd_chunk_fwd.3": {"hlo": OTHER, "count": 4, "seconds": 1.0}})):
        for name in NEW:
            assert read(name, dict(run)) is None, name
    # the two-width kernels in a configuration without differential layers
    # (the Kimi-VL cell's): no share is read off them here
    other = made_up_run({"%mla_fwd.5": {"hlo": ATTN_FWD, "count": 4, "seconds": 1.0}})
    other["config"] = {"hidden_size": 2048, "num_attention_heads": 16,
                       "num_key_value_heads": 16}
    assert read("kernel.diffattn_fwd_roofline", other) is None
    table = {"ds_ms": {("ds.selscan.dt", "forward"): 3.0, ("ds.selscan.dt", "backward"): 6.5,
                       ("ds.diffattn.combine", "recompute"): 3.1,
                       ("ds.gmu.gate", "forward"): 9.0}}
    monkeypatch.setattr(scope_time, "load", lambda run: table)
    assert read("selscan.dt_ms_per_step", {}) == 9.5
    assert read("diffattn.combine_ms_per_step", {}) == 3.1
    monkeypatch.setattr(scope_time, "load", lambda run: {"ds_ms": {("ds.rope", "forward"): 1.0}})
    assert read("selscan.dt_ms_per_step", {}) is None
    assert read("diffattn.combine_ms_per_step", {}) is None


def made_up_readings(**over) -> dict:
    return dict({"loss_err": 1e-5, "loss_after_err": 2e-5, "descends": True,
                 "logit_median": 1.0e-2, "logit_p90": 1.3e-2,
                 "grad_worst": ("['a']", 3e-2), "grad_small_worst": ("['A_log']", 6e-2),
                 "update_worst": ("['embedding']", 1.2e-4),
                 "state_absmax": [2.0, 2.05], "dt_mean": [0.01700, 0.01701],
                 "lambda_err": 2e-4,
                 "grad_lambda": {"layers_1": {"distance": 4e-2, "sum_over_terms": 0.2},
                                 "layers_3": {"distance": 2.9, "sum_over_terms": 4.4e-5}},
                 "grad_value": {"['v_proj']['bias']": {"distance": 7e-2,
                                                       "sum_over_terms": 2e-2}}},
                **over)


@pytest.mark.parametrize("fails,over", [
    (set(), {}),
    ({"loss"}, {"descends": False}), ({"loss"}, {"loss_after_err": 8e-3}),
    ({"logits"}, {"logit_median": 6e-2}), ({"logits"}, {"logit_p90": float("nan")}),
    ({"grads"}, {"grad_worst": ("['x_proj']", float("inf"))}),
    ({"grads"}, {"grad_small_worst": ("['D']", 1.2)}),
    ({"grads"}, {"update_worst": ("['subln']", 1.0)}),
    # a sum that does not cancel is held as a matrix is; one that cancels to
    # 4.4e-5 of its terms (the chip's worst) may move by 11 times its own size,
    # and to a thousandth of them by half its size, and no further
    ({"lambda_grads"}, {"grad_lambda": {"layers_1": {"distance": 9e-2, "sum_over_terms": 0.2}}}),
    ({"lambda_grads"}, {"grad_lambda": {"layers_3": {"distance": 12.0, "sum_over_terms": 4.4e-5}}}),
    ({"lambda_grads"}, {"grad_lambda": {"layers_3": {"distance": 0.6, "sum_over_terms": 1e-3}}}),
    ({"lambda_grads"}, {"grad_lambda": {"layers_5": {"distance": float("inf"),
                                                     "sum_over_terms": 0.0}}}),
    ({"lambda_grads"}, {"grad_lambda": {"layers_5": {"distance": float("nan"),
                                                     "sum_over_terms": 0.5}}}),
    # a value bias that is ONE term is held as a matrix is; at lambda 0.98 (the
    # sum a hundredth of its terms) it may move by 0.46 of its size, no further
    ({"value_grads"}, {"grad_value": {"b": {"distance": 8e-2, "sum_over_terms": 1.0}}}),
    ({"value_grads"}, {"grad_value": {"b": {"distance": 0.5, "sum_over_terms": 1e-2}}}),
    ({"value_grads"}, {"grad_value": {"b": {"distance": float("nan"), "sum_over_terms": 0.1}}}),
    ({"selscan"}, {"state_absmax": [4.0, 2.0]}), ({"selscan"}, {"dt_mean": [0.018, 0.017]}),
    ({"selscan"}, {"state_absmax": [float("nan"), 2.0]}),
    ({"diffattn"}, {"lambda_err": 2.3e-3}), ({"diffattn"}, {"lambda_err": float("inf")})])
def test_verdicts_by_hand(fails, over):
    from benchmark.runners import train_steps_phi4_flash as runner
    ok = runner.verdicts(made_up_readings(**over))
    assert {k for k, good in ok.items() if not good} == fails
    wide = runner.verdicts(made_up_readings(logit_median=6e-2, grad_worst=("['a']", 0.2)),
                           slack=runner.REHEARSAL_SLACK)
    assert all(wide.values())       # a rehearsal's slack widens the distances
    # an off-by-one in the published index moves lambda_init by more than the
    # limit at the layers kept: 15 | 17 against 14 | 16
    for i in (15, 17):
        step = 0.6 * (np.exp(-0.3 * (i - 1)) - np.exp(-0.3 * i))
        assert step > runner.LAMBDA_ATOL


def test_a_lambda_near_one_is_drawn_again_from_the_seed():
    """``draw_lambdas_again``: a differential layer whose seeded lambda lies
    within ``LAMBDA_MARGIN`` of 1 gets four new N(0, 0.1) vectors from the seed
    until it does not; the other layers keep theirs; the same seed draws the
    same vectors."""
    import types
    from benchmark.runners import train_steps_phi4_flash as runner
    cfg = types.SimpleNamespace(layer_index_offset=14)

    def layers(seed):
        rng = np.random.default_rng(seed)
        vectors = lambda: {n[2:-2]: rng.normal(0, 0.1, 64).astype(np.float32)  # noqa: E731
                           for n in runner.LAMBDA_LEAVES}
        near = vectors()
        # exp(q1 . k1) - exp(q2 . k2) = 0.2 + 0.6 exp(-0.3 * 17): lambda is 1
        near["lambda_q1"] = near["lambda_k1"] * np.float32(
            np.log(1.2 + 0.6 * np.exp(-5.1)) / np.vdot(near["lambda_k1"], near["lambda_k1"]))
        near["lambda_q2"] = np.zeros(64, np.float32)
        far = vectors()
        far["lambda_q1"], far["lambda_q2"] = np.zeros(64, np.float32), np.zeros(64, np.float32)
        return {"model": {"embed_tokens": {"embedding": np.zeros(2)},
                          "layers_2": {"mamba": {"D": np.ones(2)}},
                          "layers_3": {"self_attn": near}, "layers_5": {"self_attn": far}}}

    first, second = layers(3), layers(3)
    kept = {k: v.copy() for k, v in first["model"]["layers_5"]["self_attn"].items()}
    assert abs(1 - runner.lambda_of(first["model"]["layers_3"]["self_attn"], 17)) < 1e-5
    again = runner.draw_lambdas_again(first, cfg, seed=2**31 + 5)
    assert list(again) == ["layers_3"] and again["layers_3"] >= 1
    lam = runner.lambda_of(first["model"]["layers_3"]["self_attn"], 17)
    assert abs(1 - lam) >= runner.LAMBDA_MARGIN
    assert all(v.dtype == np.float32 and v.shape == (64, )
               for v in first["model"]["layers_3"]["self_attn"].values())
    for name, v in first["model"]["layers_5"]["self_attn"].items():
        np.testing.assert_array_equal(v, kept[name])
    runner.draw_lambdas_again(second, cfg, seed=2**31 + 5)
    for name, v in first["model"]["layers_3"]["self_attn"].items():
        np.testing.assert_array_equal(v, second["model"]["layers_3"]["self_attn"][name])
    assert runner.draw_lambdas_again(first, cfg, seed=2**31 + 5) == {}


def test_the_runners_positions_lie_past_the_middle_of_the_sequence():
    from benchmark.runners import train_steps_phi4_flash as runner
    at = runner.logit_positions(1, SEQ)
    assert at.shape[0] == 1 and runner.LOGIT_POSITIONS - 2 <= at.shape[1] <= runner.LOGIT_POSITIONS
    assert at[0][0] == 0 and at[0][-1] == SEQ - 2 and (np.diff(at[0]) > 0).all()
    assert (at[0] >= SEQ // 2 - 1).mean() >= 0.74       # three quarters past 8,192
    assert runner.logit_positions(1, 96).max() == 94    # a position with a next token


def chip_readings() -> list:
    with open(os.path.join(ROOT, "benchmark", "readings",
                           "phi4_flash_calibration.jsonl")) as f:
        return [json.loads(ln) for ln in f if ln.startswith("{")]


WRONG = ["one_decay_a_channel", "no_softplus", "no_d_term", "bf16_state", "memory_after_gate",
         "gmu_reads_first_scan", "cross_own_kv", "no_window", "window_everywhere",
         "no_subtraction", "no_one_minus_lambda_init", "no_subln", "fp8"]


@pytest.mark.parametrize("against", ["sound", "bf16"] + WRONG)
def test_the_limits_stand_between_what_the_chip_read(against):
    """The chip's readings of the timed step at 1 x 16,384 tokens against the
    reference sound and made wrong (``calibrate_phi4_flash.py``, kept in
    ``benchmark/readings/``), through the runner's limits as they are now:
    each wrong way gives ``correct`` false, fp8 (the precision below the
    configuration's) by one limit at least and not by each; the sound program
    passes, and so does a reference at the configuration's own bf16. A limit
    moved past either reading fails here."""
    from benchmark.reference import phi4_flash as reference
    from benchmark.runners import train_steps_phi4_flash as runner
    assert list(reference.WRONG) == WRONG and reference.OWN_PRECISION == "bf16"
    rows = [r for r in chip_readings() if r["against"] == against]
    assert len({r["seed"] for r in rows}) >= (2 if against in ("sound", "bf16", "fp8") else 1)
    for row in rows:
        # the lines read before the lambda vectors had a limit carry no reading
        # of theirs: the other limits are held to those lines as they are
        ok = runner.verdicts({"grad_lambda": {}, "grad_value": {}, **row})
        for readings, verdict in (("grad_lambda", "lambda_grads"), ("grad_value", "value_grads")):
            if readings not in row:
                del ok[verdict]
        assert all(ok.values()) == (against in ("sound", "bf16")), (row["seed"], ok)
        assert ok == row["verdicts"] and row["lr"] == runner.LR
    for row in rows if against == "fp8" else []:
        assert not all(row["verdicts"].values()) and any(       # by one limit, not by each
            good for name, good in row["verdicts"].items()
            if name not in ("lambda_grads", "value_grads"))


def near_one_readings() -> list:
    with open(os.path.join(ROOT, "benchmark", "readings",
                           "phi4_flash_lambda_near_one.jsonl")) as f:
        return [json.loads(ln) for ln in f if ln.startswith("{")]


@pytest.mark.parametrize("row", near_one_readings(),
                         ids=lambda r: f"{r['call']}-{r['seed']}")
def test_what_the_chip_read_with_a_lambda_near_one(row):
    """The cell's runs before the runner drew a lambda near 1 again, seeds
    picked for one among them: rule (iii) holds every ``v_proj`` bias and norm's
    bias read (up to 1.83 times its own size, a 1,057th of its terms), the
    lambda vectors pass theirs, and the leaves outside the rules pass the
    matrices' limit on every seed but the one the margin is there for."""
    from benchmark.runners import train_steps_phi4_flash as runner
    held = {"grad_lambda": row["grad_lambda"], "grad_value": row["grad_value"]}
    ok = runner.verdicts(made_up_readings(**held))
    assert ok["value_grads"] and ok["lambda_grads"]
    for v in row["grad_value"].values():
        if v["sum_over_terms"] < 1 / 14:    # the terms' rounding is what shows
            assert v["distance"] * v["sum_over_terms"] < 0.75 * runner.VALUE_TERMS_RTOL
    near = min(abs(1 - lam) for lam in row["lambdas"])
    assert (row["grad_worst"][1] <= runner.GRAD_RTOL) == (row["seed"] != 2028953222)
    assert row["seed"] != 2028953222 or near < runner.LAMBDA_MARGIN
    assert row["logit_p90"] <= runner.LOGIT_P90_RTOL


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_prints_the_contracts_last_line(trace):
    from deepspeed_tpu.utils.hostdev import force_host_devices_env
    env = force_host_devices_env(4 if trace else 1, extra={"PYTHONPATH": ROOT})
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 52), "--seconds", "3", "--trace", str(trace), "--rehearse"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    said = next(ln for ln in lines if ln.startswith("training:"))
    # the cell's one chip, however many the host has
    assert "'data': 1," in said and "batch 1 x 96" in said
    assert "mamba/sliding_attention/mamba/full_attention/gmu/cross_attention" in said
    assert "published layers 14-19" in said and "vocabulary 256" in said
    check = next(ln for ln in lines if ln.startswith("correctness:"))
    assert "FAILED" not in check and "A_log" in check and "largest |h|" in check
    assert "lambdas" in check
    notes = json.loads(next(ln for ln in lines if ln.startswith("notes: "))[7:])
    assert notes["model_layers"] == {"mamba1+dense": 2.0, "attention+dense": 3.0,
                                     "gmu+dense": 1.0}
    assert notes["gauges"]["ds_model_shared_kv_readers"] == 1.0
    assert notes["gauges"]["ds_model_shared_memory_readers"] == 1.0
    assert all(notes["verdicts"].values()) and len(notes["lambdas_first_batch"][0]) == 3
    assert notes["step_programs"] == 1 and notes["n_params"] == phi4flash_cost.param_count(
        {**config(), **config()["rehearse"]})
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in cell_metrics(load_manifest(), CELL, group)}
    assert set(line["metrics"]) <= set(declared)
    for name, got in line["metrics"].items():
        assert got["unit"] == declared[name]["unit"] and np.isfinite(got["value"])
    if trace:
        # no kernel events and no utilization on a CPU (the two scope readers
        # want ONE trace under .bench_out/: another cell's rehearsal running
        # beside this one leaves two, and they then report nothing)
        for absent in ("kernel.selscan_fwd_roofline", "kernel.diffattn_bwd_roofline",
                       "selscan.kernel_ms_per_step", "diffattn.kernel_ms_per_step",
                       "step.mfu_pct"):
            assert absent not in line["metrics"]
        assert {"setup.compile_s", "device.idle_pct.train"} <= set(line["metrics"])
        # the gauges the step publishes one dispatch late
        assert notes["gauges"]["ds_selscan_state_absmax"] > 0
        assert 0 < notes["gauges"]["ds_diffattn_lambda_mean"] < 1
    else:
        assert set(line["metrics"]) == {"setup_s", "train_tok_s"}
        assert all(v["value"] > 0 for v in line["metrics"].values())
