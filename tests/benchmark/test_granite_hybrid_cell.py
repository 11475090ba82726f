"""The Granite cell's own tests: its configuration against the published
values, its parameter and FLOP count by hand, the kernels' cost functions at
one small shape by hand, its four readers on made-up traces, its manifest
entries, and a rehearsal of the runner and of the limits' calibration end to
end. All on the CPU; no number here is a measurement."""

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
from benchmark import granite_cost, ssd_cost  # noqa: E402
from benchmark.run import cell_metrics, load_json, load_manifest, load_module  # noqa: E402

CELL, CONFIG = "train-granite4hm-1chip-longseq", "granite-4.0-h-micro-vp8-train1"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = "https://huggingface.co/ibm-granite/granite-4.0-h-micro/blob/main/config.json"
# config.json at SOURCE, key by key as published (40 layers, a vocabulary of
# 100,352; this cell runs layers 0-9 and holds an eighth of the rows)
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": PERIOD * 4, "logits_scaling": 8,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 64, "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192, "tie_word_embeddings": True,
    "vocab_size": 100352}
REDUCED = ["num_hidden_layers", "layer_types", "vocab_size"]
NEW = {"kernel.ssd_fwd_roofline": "kernel", "kernel.ssd_bwd_roofline": "kernel",
       "kernel.causal_conv_roofline": "kernel",
       "ssm.kernel_ms_per_step": "state-space mixer"}
SHARED = ["step.mfu_pct", "device.idle_pct.train", "kernel.flash_fwd_roofline",
          "kernel.flash_bwd_roofline", "host.work_ms_per_step",
          "host.idle_unnamed_pct.train", "setup.engine_init_s", "setup.place_params_s"]
# device events as a v5e's trace would name them
SSD_FWD = ("%ssd_chunk_fwd.3 = (bf16[1,16384,4096]{2,1,0:T(8,128)(2,1)}, "
           "f32[1,64,128,4096]{3,2,1,0:T(8,128)}) custom-call(bf16[1,16384,4096]")
SSD_BWD = ("%ssd_chunk_bwd.2 = (bf16[1,16384,4096]{2,1,0:T(8,128)(2,1)}, "
           "f32[1,4,16384,16]{3,2,1,0:T(8,128)}, f32[1,4,16384,16]{3,2,1,0:T(8,128)}, f32")
CONV_FWD = ("%causal_conv_fwd.1 = bf16[1,16384,4352]{2,1,0:T(8,128)(2,1)} custom-call("
            "bf16[1,16384,4352]{2,1,0:T(8,128)(2,1)} %fusion.12")
CONV_BWD = ("%causal_conv_bwd.1 = (bf16[1,16384,4352]{2,1,0:T(8,128)(2,1)}, "
            "f32[64,8,4352]{2,1,0:T(8,128)}) custom-call(bf16[1,16384,4352]")
GATED = "%short_conv_fwd.3 = bf16[4,8192,2048]{2,1,0:T(8,128)(2,1)} custom-call("


def config() -> dict:
    return load_json("configs", CONFIG + ".json")


def read(name, run):
    return load_module("layers", name).read(run)


def test_the_configuration_is_the_published_one_but_for_the_stated_cuts():
    cfg = config()
    assert cfg["source"] == SOURCE and cfg["reduced"] == REDUCED
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    assert set(cfg["published"]) == set(REDUCED)
    # and with the catalog's row of that source, on a machine whose catalog has one
    rows = []
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [r for r in map(json.loads, f) if r.get("source_url") == SOURCE]
    for row in rows:
        assert row["config"] == PUBLISHED
        assert (row["layers"], row["dense_width"], row["vocab_size"]) == (40, 8192, 100352)
    # the cut: the first whole period of layer_types, an eighth of the vocabulary
    assert cfg["num_hidden_layers"] == 10 and cfg["layer_types"] == PERIOD \
        == PUBLISHED["layer_types"][:10]
    assert (cfg["layer_types"].count("mamba"), cfg["layer_types"].count("attention")) == (9, 1)
    assert cfg["vocab_size"] == 12544 == 100352 // 8
    assert "each layer shared over 8 chips" in cfg["deployment"]
    for said in ("rows 0-12543", "layers 0-9", "layers 10-39", "pipeline stages",
                 "without its exchange", "whole-layer recomputation"):
        assert said in cfg["deployment"], said
    assert set(cfg["assumed"]) >= {
        "head_dim", "mamba_in_proj_split", "mamba_conv", "dt", "gated_norm", "shared_mlp",
        "multipliers", "no_position_embedding", "initial_A_log", "initial_dt_bias",
        "initial_D", "tokens_per_step"}
    assert cfg["ds_config"] == {"zero_optimization": {"stage": 0}}
    assert cfg["remat"] is True and cfg["remat_policy"] is None and cfg["dtype"] == "bfloat16"
    assert cfg["n_params"] == granite_cost.param_count(cfg)
    # no width is among the cuts
    for key in PUBLISHED:
        if key not in REDUCED:
            assert cfg[key] == PUBLISHED[key]


def test_manifest_entries_of_the_cell_and_the_checks_every_manifest_passes():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        admitted = json.load(f)
    manifest_checks.check_admitted(admitted)
    manifest_checks.check_entries(load_manifest())
    cells = {w["name"]: w for w in admitted["workloads"]}
    # the admitted cells keep their order; this one comes after them
    assert list(cells)[:4] == ["train-zero3-seq4k", "train-olmoe-1chip-seq4k",
                               "train-lfm2moe-1chip-seq8k", CELL]
    assert cells[CELL]["chips"] == 1 and cells[CELL]["config"] == CONFIG
    assert cells[CELL]["traffic"] == "granite4hm-1chip-longseq"
    assert [w["name"] for w in admitted["workloads"] if w["chips"] == 4] \
        == ["train-zero3-seq4k"]                               # still the one on four
    entry = next(c for c in admitted["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED and entry["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    cell = load_json("workloads", CELL + ".json")
    assert cell["traffic"] == {"global_batch": 1, "seq_len": 16384,
                               "warmup_steps": 2, "trace_steps": 4}
    assert cell["runner"] == "train_steps_granite_hybrid"
    assert cell["why"] == cells[CELL]["why"] and "1 x 16,384" in cell["why"]
    m = load_manifest()
    assert [x["name"] for x in cell_metrics(m, CELL, "end_to_end")] \
        == ["setup_s", "train_tok_s"] == cell["end_to_end"]
    layers = {x["name"]: x for x in cell_metrics(m, CELL, "per_layer")}
    assert list(layers) == [
        "step.mfu_pct", "device.idle_pct.train", "setup.compile_s", "setup.programs",
        "setup.cache_misses", "kernel.flash_fwd_roofline", "kernel.flash_bwd_roofline",
        "host.work_ms_per_step", "host.idle_unnamed_pct.train", "setup.engine_init_s",
        "setup.place_params_s"] + list(NEW)                    # the order of its metrics
    for absent in ("coll.exposed_ms_per_step", "kernel.moe_gmm_roofline",
                   "moe.gmm_ms_per_step", "kernel.short_conv_roofline",
                   "conv.kernel_ms_per_step", "moe.rows_held_pct"):
        assert absent not in layers
    for name, layer in NEW.items():
        assert layers[name]["layer"] == layer and layers[name]["moves"] == "train_tok_s"
        assert layers[name]["workloads"] == [CELL] and layers[name]["source"] == "device_trace"
    # membership and relative order, not "last": the next cell appends after
    # these. This cell's four stand together in their order, after the LFM2
    # cell's; in each shared list this cell comes after the LFM2 cell
    names = [x["name"] for x in admitted["per_layer"]]
    first = names.index("kernel.ssd_fwd_roofline")
    assert names[first:first + 4] == list(NEW)
    assert first > names.index("moe.rows_held_pct")
    for name in SHARED + ["train_tok_s"]:
        metric = next(x for x in admitted["per_layer"] + admitted["end_to_end"]
                      if x["name"] == name)
        cells_of = metric["workloads"]
        assert cells_of.count(CELL) == 1
        assert cells_of.index(CELL) > cells_of.index("train-lfm2moe-1chip-seq8k")


def test_parameters_and_flops_by_hand():
    cfg = config()
    h, inner, xbc, ffn = 2048, 4096, 4352, 8192
    mixer = h * (inner + xbc + 64) + 5 * xbc + 3 * 64 + inner + inner * h
    assert mixer == 25_847_232
    swiglu, attention = 3 * h * ffn, 2 * h * h + 2 * h * 512
    mamba_layer, attention_layer = mixer + swiglu + 2 * h, attention + swiglu + 2 * h
    assert (mamba_layer, attention_layer) == (76_182_976, 60_821_504)
    total = 9 * mamba_layer + attention_layer + 12544 * h + h
    assert granite_cost.param_count(cfg) == total == 772_160_448
    # and of the uncut model: 40 layers, the whole vocabulary
    whole = dict(cfg, **cfg["published"])
    assert granite_cost.param_count(whole) == 36 * mamba_layer + 4 * attention_layer \
        + 100352 * h + h
    scan = 2 * 256 * 128 + 64 * (2 * 256 * 64 + 4 * 64 * 128)
    assert granite_cost.scan_flops_per_token(cfg) == scan == 4_259_840
    mean_keys = (16384 + 1) / 2
    forward = (9 * (2 * (mixer - 5 * xbc - 3 * 64 - inner) + scan) + 10 * 2 * swiglu
               + 2 * attention + 4 * 32 * 64 * mean_keys + 2 * 12544 * h)
    assert granite_cost.forward_flops_per_token(cfg, 16384) == pytest.approx(forward)
    assert granite_cost.train_flops_per_token(cfg, 16384) == pytest.approx(3 * forward)
    assert 4.9e9 < 3 * forward < 5.0e9


def test_kernel_costs_by_hand_at_one_small_shape():
    small = {"mamba_chunk_size": 32, "mamba_d_state": 16}
    fwd = "%ssd_chunk_fwd.1 = (f32[2,96,128]{2,1,0}, f32[2,3,16,128]{3,2,1,0}) custom-call("
    # 2 sequences of 3 chunks of 32 tokens, heads * P = 128, N = 16
    chunk = 2 * 32 * 32 * 16 + 2 * 32 * 32 * 128 + 4 * 32 * 128 * 16
    cost = ssd_cost.ssd_call_cost(fwd, small)
    assert cost["flops"] == 2 * 3 * chunk == 3_342_336
    assert cost["bytes"] == 4 * 2 * 96 * (2 * 128 + 2 * 16) + 4 * 2 * 3 * 16 * 128
    bwd = fwd.replace("fwd", "bwd")
    cost = ssd_cost.ssd_call_cost(bwd, small)
    assert cost["flops"] == 2 * 2 * 3 * chunk
    assert cost["bytes"] == 4 * 2 * 96 * (3 * 128 + 4 * 16) + 4 * 2 * 3 * 16 * 128
    # a sequence that the chunk does not divide is padded: whole chunks
    odd = fwd.replace("f32[2,96,128]", "f32[2,80,128]")
    assert ssd_cost.ssd_call_cost(odd, small)["flops"] == 2 * 3 * chunk
    assert ssd_cost.ssd_call_cost(GATED, small) is None
    assert ssd_cost.ssd_call_cost("%ssd_chunk_fwd.1 = s32[4]{0} custom-call(", small) is None
    # the cell's shapes: the forward bound by memory, the backward by compute
    cfg, peaks = config(), {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    f, b = ssd_cost.ssd_call_cost(SSD_FWD, cfg), ssd_cost.ssd_call_cost(SSD_BWD, cfg)
    assert f["flops"] == 64 * (2 * 256 * 256 * 128 + 2 * 256 * 256 * 4096
                               + 4 * 256 * 4096 * 128) == b["flops"] / 2
    assert f["bytes"] / 819e9 > f["flops"] / 197e12
    assert b["flops"] / 197e12 > b["bytes"] / 819e9
    assert ssd_cost.least_seconds(f, peaks) == f["bytes"] / 819e9
    # the convolution: 2 and 3 values a channel and token and the halo rows
    values = 16384 * 4352
    assert ssd_cost.conv_call_bytes(CONV_FWD) == 2 * values * (2 + 16 / 256)
    assert ssd_cost.conv_call_bytes(CONV_BWD) == 2 * values * (3 + 48 / 256)
    assert ssd_cost.conv_call_bytes(GATED) is None            # the gated kernels' names
    short = "%causal_conv_fwd.1 = f32[2,40,256]{2,1,0} custom-call("
    assert ssd_cost.conv_call_bytes(short) == 4 * 2 * 40 * 256 * (2 + 16 / 48)
    from deepspeed_tpu.ops import short_conv
    assert (ssd_cost.CONV_BLOCK_ROWS, ssd_cost.CONV_HALO) \
        == (short_conv.BLOCK_ROWS, short_conv.HALO)


def run_with(kernels: dict, steps: int = 4) -> dict:
    return {"trace": {"kernels": kernels}, "trace_steps": steps, "config": config(),
            "device": {"kind": "TPU v5 lite", "platform": "tpu"}}


def test_readers_report_nothing_when_nothing_matched():
    for run in ({}, {"trace": None}, run_with({}),
                run_with({"%short_conv_fwd.3": {"count": 8, "seconds": 0.01, "hlo": GATED}})):
        for name in NEW:
            assert read(name, run) is None, name
    # a configuration without the scan's keys (the parent's cells): nothing
    other = run_with({"%ssd_chunk_fwd.3": {"count": 1, "seconds": 1e-3, "hlo": SSD_FWD}})
    other["config"] = {"hidden_size": 2048}
    assert read("kernel.ssd_fwd_roofline", other) is None


def test_readers_on_a_made_up_trace():
    fwd_least = ssd_cost.ssd_call_cost(SSD_FWD, config())["bytes"] / 819e9
    bwd_least = ssd_cost.ssd_call_cost(SSD_BWD, config())["flops"] / 197e12
    conv_least = (2 * ssd_cost.conv_call_bytes(CONV_FWD)
                  + ssd_cost.conv_call_bytes(CONV_BWD)) / 819e9
    run = run_with({
        # a step: nine layers, forward, recomputed forward, backward
        "%ssd_chunk_fwd.3": {"count": 72, "seconds": 72 * 4 * fwd_least, "hlo": SSD_FWD},
        "%ssd_chunk_bwd.2": {"count": 36, "seconds": 36 * 5 * bwd_least, "hlo": SSD_BWD},
        "%causal_conv_fwd.1": {"count": 72, "seconds": 72 * 2 * ssd_cost.conv_call_bytes(
            CONV_FWD) / 819e9, "hlo": CONV_FWD},
        "%causal_conv_bwd.1": {"count": 36, "seconds": 36 * 2 * ssd_cost.conv_call_bytes(
            CONV_BWD) / 819e9, "hlo": CONV_BWD},
        "%short_conv_fwd.3": {"count": 8, "seconds": 1.0, "hlo": GATED},
        "%flash_fwd.1": {"count": 8, "seconds": 1.0, "hlo": "%flash_fwd.1 = bf16[8,4,16384,64]"}})
    assert read("kernel.ssd_fwd_roofline", run) == pytest.approx(25.0)
    assert read("kernel.ssd_bwd_roofline", run) == pytest.approx(20.0)
    assert read("kernel.causal_conv_roofline", run) == pytest.approx(50.0)
    per_step = 9 * (2 * 4 * fwd_least + 5 * bwd_least) + 9 * 2 * conv_least
    assert read("ssm.kernel_ms_per_step", run) == pytest.approx(1e3 * per_step)
    assert 0.4e-3 < fwd_least < 0.6e-3 and 0.6e-3 < bwd_least < 0.8e-3


def test_the_runners_readings_by_hand_on_a_made_up_step():
    from benchmark.runners import train_steps_granite_hybrid as runner
    from benchmark.runners.train_steps_lfm2_moe import LR
    rng = np.random.default_rng(0)
    grads = {"a": rng.standard_normal((4, 3)).astype(np.float32),
             "b": rng.standard_normal((5, )).astype(np.float32),
             "A_log": rng.standard_normal((8, )).astype(np.float32)}
    before = {"a": np.ones((4, 3), np.float32), "b": np.zeros((5, ), np.float32),
              "A_log": np.ones((8, ), np.float32)}
    after = {k: before[k] + runner.adamw_first_step(grads[k]) for k in grads}
    np.testing.assert_allclose(runner.adamw_first_step(grads["a"]),
                               -LR * np.sign(grads["a"]), rtol=1e-6)
    assert after["a"].dtype == np.float32
    logits = rng.standard_normal((1, 6, 8)).astype(np.float32)
    moved = {"a": grads["a"] * 1.1, "b": grads["b"], "A_log": grads["A_log"] * 1.3}
    got = {"logits": logits * 1.01, "loss": 2.02, "loss_after": 1.98, "grads": moved,
           "before": before, "after": {k: before[k] + runner.adamw_first_step(moved[k])
                                       for k in grads},
           "stats": {"state_absmax": 3.3, "dt_mean": 0.0202}}
    want = {"logits": logits, "ce": 2.0, "ce_after": 2.0, "grads": grads,
            "state_absmax_chunks": 3.0,
            "state_absmax": 4.0, "dt_mean": 0.02}
    r = runner.readings(got, want)
    assert r["logit_median"] == pytest.approx(0.01, rel=1e-4) == pytest.approx(r["logit_p90"], rel=1e-4)
    assert r["grad_worst"][0] == "['a']" and r["grad_worst"][1] == pytest.approx(0.1, rel=1e-5)
    # the scan's per-head leaves are judged apart, by their own limit
    assert r["grad_scan_worst"][0] == "['A_log']"
    assert r["grad_scan_worst"][1] == pytest.approx(0.3, rel=1e-5)
    assert r["grad_err"]["['b']"] == 0.0 and r["update_err"] < 1e-6
    assert r["loss_err"] == pytest.approx(0.01) and r["dt_mean_err"] == pytest.approx(0.01)
    assert r["loss_after_err"] == pytest.approx(0.01) and r["descends"] is True
    assert r["state_absmax_ratio"] == pytest.approx(1.1)
    # an update without the bias correction (a tenth of the step) is told
    got["after"] = {k: before[k] + 0.1 * (got["after"][k] - before[k]) for k in before}
    assert runner.readings(got, want)["update_err"] == pytest.approx(0.9, rel=1e-4)
    assert list(runner.logit_positions(16384)[:3]) == [0, 127, 254]
    positions = runner.logit_positions(16384)
    assert positions.size == 256 == np.unique(positions).size and positions[-1] == 16383
    assert (positions[127], positions[128]) == (127 * 127, 16384 - 128)
    assert list(runner.logit_positions(96)) == list(range(96))


def test_the_calibration_of_the_limits_rehearses():
    """``calibrate_granite_hybrid.py`` is where the limits' readings come
    from: on the CPU at tiny sizes it has to run and to tell the wrong
    references a 96-token sequence can tell."""
    from deepspeed_tpu.utils.hostdev import force_host_devices_env
    env = force_host_devices_env(1, extra={"PYTHONPATH": ROOT})
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "calibrate_granite_hybrid.py"),
         "--seeds", "3", "--rehearse", "--only", "no_softplus,no_residual_multiplier,rope,no_carry"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = {r["against"]: r for r in map(json.loads, (
        ln for ln in proc.stdout.splitlines() if ln.startswith("{")))}
    assert list(rows) == ["sound", "no_softplus", "no_residual_multiplier", "rope", "no_carry"]
    sound = rows["sound"]
    assert sound["update_err"] < 1e-3 and sound["loss_err"] < 1e-4
    assert 0.95 < sound["state_absmax_ratio"] < 1.05
    # through the runner's own limits (three times as wide at this size): the
    # sound reference passes each, every wrong one fails `correct`
    assert sound["correct"] is True and all(sound["verdicts"].values())
    assert not np.isfinite(rows["no_softplus"]["logit_median"])     # NaN: fails any limit
    assert {k for k, v in rows["no_softplus"]["verdicts"].items() if not v} \
        == {"loss", "logits", "grads", "state"}
    for wrong in ("no_residual_multiplier", "no_carry"):
        assert rows[wrong]["correct"] is False
        assert not rows[wrong]["verdicts"]["logits"] and not rows[wrong]["verdicts"]["grads"]
    assert rows["rope"]["correct"] is False and not rows["rope"]["verdicts"]["grads"]
    said = [ln for ln in proc.stdout.splitlines() if " against " in ln]
    assert len(said) == 5 and all(ln.endswith(": ok") for ln in said)
    assert "3 against rope: correct false (fails grads): ok" in said


def chip_readings() -> list:
    with open(os.path.join(ROOT, "benchmark", "readings",
                           "granite_hybrid_calibration.jsonl")) as f:
        return [json.loads(ln) for ln in f if ln.startswith("{")]


@pytest.mark.parametrize("against", ["sound", "bf16_state", "bf16_decay", "no_softplus",
                                     "no_residual_multiplier", "rope", "no_carry"])
def test_the_limits_stand_between_what_the_chip_read(against):
    """The chip's readings of the timed step at 1 x 16,384 against the
    reference sound and made wrong (``calibrate_granite_hybrid.py``, kept in
    ``benchmark/readings/``), through the runner's limits as they are now: a
    planted bf16 state, and each other wrong way, gives ``correct`` false; the
    sound program passes. A limit moved past either reading fails here."""
    from benchmark.runners import train_steps_granite_hybrid as runner
    rows = [r for r in chip_readings() if r["against"] == against]
    assert rows, against
    for row in rows:
        ok = runner.verdicts(row, row["initialisation"])
        assert all(ok.values()) == (against == "sound"), (row["seed"], ok)
    if against in ("bf16_state", "bf16_decay"):
        # the precision below the configuration's: told by the summed
        # gradient leaves and by the scan's per-head leaves on every seed
        # read, with room; by the logits on one seed of the two (the other
        # reads 2.24e-2 under the 2.3e-2)
        assert len({r["seed"] for r in rows}) >= 2
        for row in rows:
            assert not runner.verdicts(row, row["initialisation"])["grads"]
            assert row["grad_worst"][1] > runner.GRAD_RTOL * 1.2
            assert row["grad_scan_worst"][1] > runner.GRAD_SCAN_RTOL * 1.5
        assert sum(not runner.verdicts(r, r["initialisation"])["logits"] for r in rows) >= 1
    if against == "sound":
        for row in rows:    # room under each limit
            assert row["logit_median"] < runner.LOGIT_MEDIAN_RTOL / 1.15
            assert row["grad_worst"][1] < runner.GRAD_RTOL / 1.2
            assert row["grad_scan_worst"][1] < runner.GRAD_SCAN_RTOL / 1.15


def made_up_readings(**over) -> dict:
    return dict({"loss_err": 1e-6, "loss_after_err": 3e-5, "descends": True,
                 "logit_median": 1.9e-2, "logit_p90": 2.0e-2,
                 "grad_worst": ("['a']", 3.1e-2), "grad_scan_worst": ("['D']", 6e-2),
                 "update_err": 1e-5, "state_absmax_ratio": 1.0, "dt_mean_err": 1e-5},
                **over)


@pytest.mark.parametrize("fails,over", [
    (set(), {}),
    ({"loss"}, {"descends": False}), ({"loss"}, {"loss_after_err": 2e-3}),
    ({"logits"}, {"logit_median": 3.17e-2}),            # the chip's bf16 state
    ({"logits"}, {"logit_p90": float("nan")}),
    ({"grads"}, {"grad_worst": ("['a']", 5.7e-2)}),
    ({"grads"}, {"grad_scan_worst": ("['D']", 1.48e-1)}),
    ({"grads"}, {"update_err": 0.9}),
    ({"state"}, {"state_absmax_ratio": 0.68}), ({"state"}, {"state_absmax_ratio": float("nan")}),
    ({"state"}, {"dt_mean_err": 0.05})])
def test_verdicts_by_hand(fails, over):
    from benchmark.runners import train_steps_granite_hybrid as runner
    ok = runner.verdicts(made_up_readings(**over), {"A_log": 0.0, "taps_mean": 2.0})
    assert {k for k, v in ok.items() if not v} == fails
    # a rehearsal's limits on logits and gradients are three times as wide
    wide = runner.verdicts(made_up_readings(**over), {"A_log": 0.0}, runner.REHEARSAL_SLACK)
    assert wide["logits"] or "logit_p90" in over
    assert not runner.verdicts(made_up_readings(), {})["init"]
    assert not runner.verdicts(made_up_readings(), {"D": float("inf")})["init"]


def seeded_mamba(seed: int, layers: int = 9) -> dict:
    """The Mamba-2 parameters of ``layers`` layers at the cell's widths as
    the program's own initialisers draw them."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.llama import init_llama
    from benchmark.runners import train_steps_granite_hybrid as runner
    # the cell's Mamba widths over a narrow stream: in_proj is not read
    cfg = runner.model_config(dict(
        config(), hidden_size=64, mamba_expand=64, shared_intermediate_size=64,
        intermediate_size=64, num_attention_heads=1, num_key_value_heads=1,
        vocab_size=64, ce_chunk_size=64, num_hidden_layers=layers,
        layer_types=["mamba"] * layers))
    _, params = init_llama(cfg, seed=seed, dtype=jnp.float32)
    return jax.tree_util.tree_map(np.asarray, params)


@pytest.mark.parametrize("planted", [
    "sound", "dt_bias_ones", "A_uniform_1_16", "D_zero", "taps_normal_0.02",
    "taps_not_truncated", "bias_zero"])
def test_the_assumed_initialisation_is_checked_on_the_references_side(planted):
    """The weights of both sides come from the program's initialisers, so the
    reference states the rule and measures the draw: a sound draw at the
    cell's widths reads a few standard errors, a planted other rule fails."""
    from benchmark.reference import granite_hybrid as reference
    from benchmark.runners import train_steps_granite_hybrid as runner
    cfg = config()
    params = seeded_mamba(seed=11)
    rng = np.random.default_rng(0)
    for name, lp in params["model"].items():
        if not name.startswith("layers_"):
            continue
        m = lp["mamba"]
        assert m["conv_weight"].shape == (4, 4352) and m["dt_bias"].shape == (64, )
        if planted == "dt_bias_ones":       # transformers' own Granite mixer
            m["dt_bias"] = np.ones_like(m["dt_bias"])
        elif planted == "A_uniform_1_16":   # Mamba-2's own A_init_range
            m["A_log"] = np.log(rng.uniform(1, 16, 64)).astype(np.float32)
        elif planted == "D_zero":
            m["D"] = np.zeros_like(m["D"])
        elif planted == "taps_normal_0.02":
            m["conv_weight"] = (0.02 * rng.standard_normal((4, 4352))).astype(np.float32)
        elif planted == "taps_not_truncated":
            m["conv_weight"] = (0.5 * rng.standard_normal((4, 4352))).astype(np.float32)
        elif planted == "bias_zero":
            m["conv_bias"] = np.zeros_like(m["conv_bias"])
    init = reference.initialisation_readings(params, cfg)
    assert set(init) == {"A_log", "D", "dt_bias_mean", "dt_bias_spread", "taps_mean",
                         "taps_spread", "conv_bias_mean", "conv_bias_spread"}
    ok = runner.verdicts(made_up_readings(), init)
    assert ok["init"] == (planted == "sound"), init
    if planted == "sound":
        assert max(init.values()) < 4.5


@pytest.mark.parametrize("trace,devices", [(0, 1), (1, 1), (0, 4)])
def test_rehearsal_of_the_cell_prints_the_contracts_last_line(trace, devices):
    from deepspeed_tpu.utils.hostdev import force_host_devices_env
    env = force_host_devices_env(devices, extra={"PYTHONPATH": ROOT})
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 33), "--seconds", "3", "--trace", str(trace), "--rehearse"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    said = next(ln for ln in lines if ln.startswith("training:"))
    # the cell's one chip, however many the host has
    assert "'data': 1," in said and "mamba/mamba/attention/mamba" in said
    check = next(ln for ln in lines if ln.startswith("correctness:"))
    assert "FAILED" not in check and "logits of 96 positions" in check
    notes = json.loads(next(ln for ln in lines if ln.startswith("notes: "))[7:])
    assert notes["model_layers"] == {"mamba+dense": 3.0, "attention+dense": 1.0}
    assert notes["gauges"]["ds_ssm_state_absmax"] > 0 < notes["gauges"]["ds_ssm_dt_mean"]
    assert set(notes["ssm_stats_last_step"]) == {"state_absmax", "dt_mean"}
    assert notes["step_programs"] == 1 and notes["n_params"] == granite_cost.param_count(
        {**config(), **config()["rehearse"]})
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in cell_metrics(load_manifest(), CELL, group)}
    assert set(line["metrics"]) <= set(declared)
    for name, got in line["metrics"].items():
        assert got["unit"] == declared[name]["unit"] and np.isfinite(got["value"])
    if trace:
        # no kernel events and no utilization on a CPU
        for absent in list(NEW) + ["step.mfu_pct", "kernel.flash_fwd_roofline"]:
            assert absent not in line["metrics"]
        assert {"setup.compile_s", "device.idle_pct.train"} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"setup_s", "train_tok_s"}
        assert all(v["value"] > 0 for v in line["metrics"].values())
