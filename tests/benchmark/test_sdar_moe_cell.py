"""The SDAR cell's own tests: its configuration against the published
values, its parameter, FLOP and live-pair counts by hand, the kernels' cost
function on made-up events, its readers, its manifest entries by membership
and relative order (never "last": the next cell appends after these), the
chip's calibration readings through the limits as they are, and a rehearsal
of the runner and of the calibration end to end. All on the CPU; no number
here is a measurement."""

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
from benchmark import bdattn_cost, sdar_cost  # noqa: E402
from benchmark.run import cell_metrics, load_json, load_manifest, load_module  # noqa: E402

CELL, CONFIG = "train-sdar-1chip-bd4-seq8k", "sdar-30b-a3b-chat-ep8-train1"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SOURCE = "https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/config.json"
# config.json at SOURCE, key by key as the catalog has it
PUBLISHED = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
             "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
             "max_position_embeddings": 32768, "max_window_layers": 48,
             "mlp_only_layers": [], "model_type": "sdar_moe",
             "moe_intermediate_size": 768, "norm_topk_prob": True,
             "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
             "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
             "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
             "tie_word_embeddings": False, "use_sliding_window": False,
             "vocab_size": 151936}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW = {"kernel.bdattn_fwd_roofline": ("kernel", "device_trace"),
       "kernel.bdattn_bwd_roofline": ("kernel", "device_trace"),
       "bdattn.kernel_ms_per_step": ("kernel", "device_trace"),
       "diffusion.masked_pct": ("data transform", "program_counter")}
SHARED = ["step.mfu_pct", "device.idle_pct.train", "host.work_ms_per_step",
          "host.idle_unnamed_pct.train", "setup.engine_init_s", "setup.place_params_s",
          "moe.gmm_ms_per_step", "moe.load_max_over_mean", "kernel.moe_gmm_held_roofline"]
# device events as a v5e's trace names them
FWD = ("%bdattn_fwd.3 = (bf16[8,2,8,8192,128]{4,3,2,1,0:T(8,128)(2,1)}, "
       "f32[8,2,8,8192,1]{4,3,2,1,0:T(8,128)}) custom-call(bf16[8,2,8,8192,128]")
BWD = ("%bdattn_bwd.1 = (bf16[8,2,8,8192,128]{4,3,2,1,0:T(8,128)(2,1)}, "
       "bf16[8,8192,128]{2,1,0:T(8,128)(2,1)}, bf16[8,8192,128]{2,1,0:T(8,128)(2,1)}, bf16")
FLASH = "%flash_fwd.2 = (bf16[8,8,16384,128]{3,2,1,0:T(8,128)(2,1)}, f32[8,8,16384,1]"


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
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) \
        == (6, 16, 18992)
    # the floors: four layers after none leading, 8 experts, an eighth of the rows
    assert 4 <= cfg["num_hidden_layers"] and cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert cfg["num_experts"] * 8 == PUBLISHED["num_experts"]
    assert "each layer shared over 8 chips" in cfg["deployment"]
    for key in ("block_length", "noise_schedule", "mask_id", "qk_norm", "tokens_per_step",
                "no_balance_loss", "training_form"):
        assert key in cfg["assumed"], key
    assert cfg["block_length"] == 4 and cfg["t_min"] == 1e-3
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "SDAR-30B-A3B-Chat")
        assert row["source_url"] == SOURCE and row["config"] == PUBLISHED
    # the program's config of the file: the widths as published, the share set
    from benchmark.runners import train_steps_sdar_moe as runner
    model = runner.model_config(cfg)
    assert (model.hidden_size, model.num_attention_heads, model.num_key_value_heads,
            model.head_dim_, model.intermediate_size) == (2048, 32, 4, 128, 768)
    assert (model.num_local_experts, model.experts_held_, model.moe_share_index,
            model.num_experts_per_tok) == (128, 16, 0, 8)
    assert model.moe_renormalize and model.moe_scoring == "softmax"
    assert model.rope_theta == 1e6 and model.qk_norm == "head"
    assert model.block_diffusion_ and model.diffusion_block_length == 4
    assert model.diffusion_mask_id_ == 18991 and model.diffusion_t_min == 1e-3
    assert model.remat and model.ce_chunk_size == cfg["ce_chunk_size"]


def test_manifest_entries_of_the_cell_and_the_checks_every_manifest_passes():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        admitted = json.load(f)
    manifest_checks.check_admitted(admitted)
    manifest_checks.check_entries(load_manifest())
    cells = {w["name"]: w for w in admitted["workloads"]}
    # the cells admitted before it keep their order; this one comes after them
    assert list(cells).index(CELL) > list(cells).index("train-granite4hm-1chip-longseq")
    assert cells[CELL]["chips"] == 1 and cells[CELL]["config"] == CONFIG
    assert cells[CELL]["traffic"] == "sdar-1chip-bd4-seq8k"
    assert [w["name"] for w in admitted["workloads"] if w["chips"] == 4] \
        == ["train-zero3-seq4k"]                               # still the one on four
    entry = next(c for c in admitted["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED and entry["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    cell = load_json("workloads", CELL + ".json")
    assert cell["traffic"] == {"global_batch": 2, "seq_len": 8192,
                               "warmup_steps": 2, "trace_steps": 4}
    assert cell["runner"] == "train_steps_sdar_moe" == config()["runner"]
    assert cell["why"] == cells[CELL]["why"] and "2 x 8,192 data tokens" in cell["why"]
    m = load_manifest()
    assert [x["name"] for x in cell_metrics(m, CELL, "end_to_end")] \
        == ["setup_s", "train_tok_s"] == cell["end_to_end"]
    layers = {x["name"]: x for x in cell_metrics(m, CELL, "per_layer")}
    assert set(layers) == set(SHARED) | set(NEW) | {
        "setup.compile_s", "setup.programs", "setup.cache_misses"}
    # not under the readers that would misread this cell (ISSUE 37, hazards)
    for absent in ("kernel.flash_fwd_roofline", "kernel.flash_bwd_roofline",
                   "flash.kernel_ms_per_step", "kernel.moe_gmm_roofline",
                   "moe.rows_held_pct", "coll.exposed_ms_per_step"):
        assert absent not in layers
    assert not any(name.startswith("scope.") for name in layers)
    for name, (layer, source) in NEW.items():
        assert layers[name]["layer"] == layer and layers[name]["moves"] == "train_tok_s"
        assert layers[name]["workloads"] == [CELL] and layers[name]["source"] == source
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layers", name + ".py"))
    # membership and relative order: the four stand together in their order,
    # after every metric the accepted benchmark had; in each shared list this
    # cell comes after the cells that were there
    names = [x["name"] for x in admitted["per_layer"]]
    first = names.index("kernel.bdattn_fwd_roofline")
    assert names[first:first + 4] == list(NEW)
    assert first > names.index("scope.named_pct.train")
    for name in SHARED + ["train_tok_s"]:
        metric = next(x for x in admitted["per_layer"] + admitted["end_to_end"]
                      if x["name"] == name)
        cells_of = metric["workloads"]
        assert cells_of.count(CELL) == 1
        assert cells_of.index(CELL) > cells_of.index("train-lfm2moe-1chip-seq8k")


def test_parameters_flops_and_live_pairs_by_hand():
    cfg = config()
    layer = (2 * 2048 * 4096 + 2 * 2048 * 512     # q, o; k, v
             + 2 * 128 + 2 * 2048                  # q/k norms; the layer's two norms
             + 2048 * 128                          # the 128-wide router
             + 16 * 3 * 2048 * 768)                # 16 experts held
    assert layer == 94_638_336
    assert sdar_cost.param_count(cfg) == 6 * layer + 2 * 18992 * 2048 + 2048 == 645_623_296
    assert sdar_cost.experts_held_per_position(cfg) == 1.0
    # the mask's live pairs: own block, noisy -> clean, clean -> clean
    for seq, block in ((8192, 4), (64, 8), (96, 32)):
        assert sdar_cost.live_pairs(seq, block) \
            == seq * block + seq * (seq - block) // 2 + seq * (seq + block) // 2
    from deepspeed_tpu.ops.attention import block_diffusion_mask
    assert int(block_diffusion_mask(64, 8).sum()) == sdar_cost.live_pairs(64, 8)
    position = 2 * (18_874_368 + 262_144) + 2 * 1.0 * 4_718_592
    scores = 4 * 32 * 128 * (8192 + 4)
    forward = 6 * (2 * position + scores) + 2 * 2048 * 18992
    assert sdar_cost.forward_flops_per_token(cfg, 8192) == forward
    assert sdar_cost.train_flops_per_token(cfg, 8192) == 3 * forward
    assert abs(forward - 1.456e9) < 1e6
    # a step of 16,384 data tokens: 71.6 TFLOP without recomputation
    assert abs(3 * forward * 16384 - 71.57e12) < 1e10


def test_kernel_cost_by_hand_from_the_events_own_shape():
    cfg = config()
    live = 8192 * 8192 + 8192 * 4
    assert bdattn_cost.call_flops(FWD, cfg, False) == 4.0 * 64 * 128 * live
    assert bdattn_cost.call_flops(BWD, cfg, True) == 8.0 * 64 * 128 * live
    assert bdattn_cost.call_flops(FLASH, cfg, False) is None      # not this layout
    assert bdattn_cost.call_flops(FWD, {"hidden_size": 4096}, False) is None
    # half of what a causal mask over the same 2L positions would count
    from benchmark import flash_cost
    causal = flash_cost.call_flops(FLASH, cfg)
    assert 0.499 < bdattn_cost.call_flops(FWD, cfg, False) / causal < 0.501


def made_up_run(kernels) -> dict:
    return {"trace": {"kernels": kernels}, "config": config(), "trace_steps": 4,
            "device": {"kind": "TPU v5 lite", "count": 1}, "tokens_per_step": 16384}


def test_readers_on_a_made_up_trace():
    peak = 197e12
    live = 8192 * 8192 + 8192 * 4
    fwd_flops = 4.0 * 64 * 128 * live
    run = made_up_run({
        "%bdattn_fwd.3": {"hlo": FWD, "count": 48, "seconds": 48 * 0.020},
        "%bdattn_bwd.1": {"hlo": BWD, "count": 24, "seconds": 24 * 0.036},
        "%flash_fwd.2": {"hlo": FLASH, "count": 4, "seconds": 1.0},       # not ours
        "%fusion.7": {"hlo": "%fusion.7 = bf16[2,16384,2048]", "count": 9, "seconds": 0.3}})
    np.testing.assert_allclose(read("kernel.bdattn_fwd_roofline", run),
                               100 * fwd_flops / peak / 0.020)
    np.testing.assert_allclose(read("kernel.bdattn_bwd_roofline", run),
                               100 * 2 * fwd_flops / peak / 0.036)
    np.testing.assert_allclose(read("bdattn.kernel_ms_per_step", run),
                               1e3 * (48 * 0.020 + 24 * 0.036) / 4)
    assert read("kernel.bdattn_fwd_roofline", run) < 100 > read("kernel.bdattn_bwd_roofline", run)
    run["diffusion_masked_samples"] = [8000, 8400, 8192, 8176]
    np.testing.assert_allclose(read("diffusion.masked_pct", run), 100 * 8192 / 16384)


def test_readers_report_nothing_when_nothing_matched():
    """A program without the kernels or the counter (the parent commit, a CPU
    rehearsal): every new reader returns None and raises nothing."""
    for run in ({}, {"trace": None}, made_up_run({}),
                made_up_run({"%flash_fwd.2": {"hlo": FLASH, "count": 4, "seconds": 1.0}})):
        for name in NEW:
            assert read(name, run) is None, name
    # an event of the name in another layout: no count, so no share
    odd = made_up_run({"%bdattn_fwd.1": {"hlo": FLASH.replace("flash", "bdattn"),
                                         "count": 1, "seconds": 1.0}})
    assert read("kernel.bdattn_fwd_roofline", odd) is None


def test_the_calibration_of_the_limits_rehearses():
    """``calibrate_sdar_moe.py`` is where the limits' readings come from: on
    the CPU at tiny sizes it has to run and to tell the wrong references a
    64-token sequence can tell (four times the limits wide: not the
    precision, nor the renormalisation of two held experts' weights)."""
    from deepspeed_tpu.utils.hostdev import force_host_devices_env
    env = force_host_devices_env(1, extra={"PYTHONPATH": ROOT})
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    only = "causal,leak,clean_sees_noisy,unit_weights,shifted_labels"
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "calibrate_sdar_moe.py"),
         "--seeds", "5", "--rehearse", "--only", only],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    rows = {r["against"]: r for r in map(json.loads, (
        ln for ln in proc.stdout.splitlines() if ln.startswith("{")))}
    assert list(rows) == ["sound"] + only.split(",")
    sound = rows["sound"]
    assert sound["update_err"] < 1e-3 and sound["loss_err"] < 1e-3
    assert sound["correct"] is True and all(sound["verdicts"].values())
    assert sound["masked_tokens"][0] == sound["masked_tokens"][1] > 0
    for wrong in only.split(","):
        assert rows[wrong]["correct"] is False, wrong
    for wrong in ("causal", "leak", "clean_sees_noisy"):
        assert not rows[wrong]["verdicts"]["logits"], wrong
    for wrong in ("unit_weights", "shifted_labels"):
        assert not rows[wrong]["verdicts"]["loss"], wrong
    said = [ln for ln in proc.stdout.splitlines() if " against " in ln and not ln.startswith("{")]
    assert len(said) == 6 and all(ln.endswith(": ok") for ln in said)


def chip_readings() -> list:
    with open(os.path.join(ROOT, "benchmark", "readings",
                           "sdar_moe_calibration.jsonl")) as f:
        return [json.loads(ln) for ln in f if ln.startswith("{")]


def chip_verdicts(row) -> dict:
    """The runner's limits as they are now on one of the chip's rows (the
    counts themselves are not kept: two numbers with the row's sums stand in)."""
    from benchmark.runners import train_steps_sdar_moe as runner
    counts = np.zeros(128, np.int64)
    counts[0], counts[16] = row["rows_held"][0], row["assigned"][0] - row["rows_held"][0]
    return runner.verdicts({**row, "counts": [counts.tolist()]}, 2 * 16384 * 8 * 6, 128, 16)


@pytest.mark.parametrize("against", ["sound", "bf16", "fp8", "leak", "causal",
                                     "clean_sees_noisy", "unit_weights",
                                     "shifted_labels", "no_renorm"])
def test_the_limits_stand_between_what_the_chip_read(against):
    """The chip's readings of the timed step at 2 x 8,192 data tokens against
    the reference sound and made wrong (``calibrate_sdar_moe.py``, kept in
    ``benchmark/readings/``), through the runner's limits as they are now:
    each wrong way gives ``correct`` false, fp8 (the precision below the
    configuration's) by the logits AND the routing with room, the leak by
    the logits, shifted labels and unit weights by the gradients; the sound program passes with room, and so does a
    reference at the configuration's own bf16. A limit moved past either
    reading fails here."""
    from benchmark.runners import train_steps_sdar_moe as runner
    rows = [r for r in chip_readings() if r["against"] == against]
    # two seeds for the sound program, the precisions and the leak; the ways
    # that are wrong by a factor were read on one
    assert len({r["seed"] for r in rows}) == (
        2 if against in ("sound", "bf16", "fp8", "leak") else 1), against
    for row in rows:
        ok = chip_verdicts(row)
        assert all(ok.values()) == (against in ("sound", "bf16")), (row["seed"], ok)
        assert ok["masked"] and row["update_err"] < runner.UPDATE_RTOL / 10
        assert row["lr"] == runner.LR
    if against == "sound":
        for row in rows:    # room under each limit
            assert row["lr"] == runner.LR and row["descends"]
            assert row["logit_median"] < runner.LOGIT_MEDIAN_RTOL / 1.8
            assert row["logit_p90"] < runner.LOGIT_P90_RTOL / 2.5
            assert row["grad_worst"][1] < runner.GRAD_RTOL / 3
            assert row["grad_routed_worst"][1] < runner.GRAD_ROUTED_RTOL / 2.5
            assert row["grad_router_median"] < runner.GRAD_ROUTER_RTOL / 3
            assert row["moved"] / row["assigned"][0] < runner.COUNT_MOVED_SHARE / 3
            assert row["loss_err"] < runner.LOSS_RTOL / 4
            assert row["loss_after_err"] < runner.LOSS_AFTER_RTOL / 5
    if against == "fp8":
        for row in rows:
            ok = chip_verdicts(row)
            assert not ok["logits"] and not ok["routing"]
            assert row["logit_median"] > runner.LOGIT_MEDIAN_RTOL * 9
            assert row["logit_p90"] > runner.LOGIT_P90_RTOL * 1.4
            assert row["moved"] / row["assigned"][0] > runner.COUNT_MOVED_SHARE * 2.5
    if against == "leak":
        for row in rows:
            assert not chip_verdicts(row)["logits"]
            assert row["logit_median"] > runner.LOGIT_MEDIAN_RTOL * 1.25
    if against in ("causal", "clean_sees_noisy", "no_renorm"):
        for row in rows:
            ok = chip_verdicts(row)
            assert not ok["logits"] and not ok["routing"]
            assert not ok["grads"] or against == "clean_sees_noisy"
    if against in ("unit_weights", "shifted_labels"):
        for row in rows:
            assert not chip_verdicts(row)["grads"] and row["grad_worst"][1] > 1.0
        if against == "unit_weights":
            assert all(r["loss_err"] > 0.5 for r in rows)


def made_up_readings(**over) -> dict:
    counts = np.zeros(128, np.int64)
    counts[0], counts[16] = 190_000, 1_572_864 - 190_000
    return dict({"loss_err": 2e-4, "loss_after_err": 5e-4, "descends": True,
                 "logit_median": 1.2e-2, "logit_p90": 1.07e-1,
                 "grad_worst": ("['a']", 2.5e-1), "grad_routed_worst": ("['w1']", 0.33),
                 "grad_router_median": 0.25,
                 "update_err": 5e-5, "counts": [counts.tolist()],
                 "assigned": [1_572_864, 1_572_864], "moved": 1900,
                 "rows_held": [190_000, 190_200], "share_fallback": 0,
                 "masked_tokens": [8200, 8200]}, **over)


@pytest.mark.parametrize("fails,over", [
    (set(), {}),
    ({"loss"}, {"descends": False}), ({"loss"}, {"loss_after_err": 8e-3}),
    ({"loss"}, {"loss_err": 1.8e-3}),                   # labels shifted by one
    ({"logits"}, {"logit_median": 2.8e-2}),             # the chip's leak
    ({"logits"}, {"logit_p90": float("nan")}),
    ({"grads"}, {"grad_worst": ("['q_norm']", 1.8)}),       # labels shifted by one
    ({"grads"}, {"grad_routed_worst": ("['w3']", 1.2)}),     # labels shifted by one
    ({"grads"}, {"grad_router_median": 1.0}),               # no gradient at all reads 1
    ({"grads"}, {"update_err": 1.0}),
    ({"routing"}, {"moved": 9900}),                     # fp8's 6.3e-3 of all
    ({"routing"}, {"rows_held": [190_000, 193_000]}),
    ({"routing"}, {"share_fallback": 1}),
    ({"routing"}, {"assigned": [1_572_864, 1_572_000]}),
    ({"masked"}, {"masked_tokens": [8200, 8201]})])
def test_verdicts_by_hand(fails, over):
    from benchmark.runners import train_steps_sdar_moe as runner
    ok = runner.verdicts(made_up_readings(**over), 1_572_864, 128, 16)
    assert {k for k, good in ok.items() if not good} == fails
    wide = runner.verdicts(made_up_readings(grad_worst=("['a']", 1.8)), 1_572_864,
                           128, 16, slack=runner.REHEARSAL_SLACK)
    assert all(wide.values())       # a rehearsal's slack widens the distances


def test_the_runners_batch_and_positions_are_the_programs_own():
    """The first batch is the program's noiser's step-0 draw under the run's
    seed (the engine is given the same seed), and the logits are read at
    masked positions spread over each sequence."""
    from benchmark.runners import train_steps_sdar_moe as runner
    from deepspeed_tpu.runtime.data_pipeline import noise_batch
    cfg = runner.model_config({**config(), **config()["rehearse"]})
    ids = np.random.default_rng(3).integers(0, cfg.diffusion_mask_id_, (2, 512),
                                            dtype=np.int32)
    batch = runner.noiser(cfg, 77)(ids, 0)
    want = noise_batch(ids, [77, 0], 4, cfg.diffusion_mask_id_, cfg.diffusion_t_min)
    for a, b in zip(batch, want):
        np.testing.assert_array_equal(a, b)
    at = runner.logit_positions(batch)
    assert at.shape == (2, runner.LOGIT_POSITIONS // 2)
    for row in range(2):
        assert (batch.weights[row, at[row]] > 0).all()
        assert at[row][0] < 16 and at[row][-1] > 512 - 16 and (np.diff(at[row]) > 0).all()
    update = runner.adamw_first_step(np.array([0.5, -2.0, 0.0], np.float32))
    np.testing.assert_allclose(update, [-runner.LR, runner.LR, 0.0], rtol=1e-6)


@pytest.mark.parametrize("trace,devices", [(0, 1), (1, 1), (0, 4)])
def test_rehearsal_of_the_cell_prints_the_contracts_last_line(trace, devices):
    from deepspeed_tpu.utils.hostdev import force_host_devices_env
    env = force_host_devices_env(devices, extra={"PYTHONPATH": ROOT})
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 37), "--seconds", "3", "--trace", str(trace), "--rehearse"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    said = next(ln for ln in lines if ln.startswith("training:"))
    # the cell's one chip, however many the host has
    assert "'data': 1," in said and "2 of 16 experts held" in said
    assert "batch 2 x 64 data tokens (2 x 128 positions)" in said
    check = next(ln for ln in lines if ln.startswith("correctness:"))
    assert "FAILED" not in check and "masked positions of 2 sequences" in check
    notes = json.loads(next(ln for ln in lines if ln.startswith("notes: "))[7:])
    assert notes["model_layers"] == {"attention+moe": 2.0}
    assert notes["masked_tokens_first_batch"][0] == notes["masked_tokens_first_batch"][1]
    assert 0.0 < notes["mask_rate_gauge"] < 1.0
    assert notes["step_programs"] == 1 and notes["n_params"] == sdar_cost.param_count(
        {**config(), **config()["rehearse"]})
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in cell_metrics(load_manifest(), CELL, group)}
    assert set(line["metrics"]) <= set(declared)
    for name, got in line["metrics"].items():
        assert got["unit"] == declared[name]["unit"] and np.isfinite(got["value"])
    if trace:
        # no kernel events and no utilization on a CPU; the counter is read
        for absent in ("kernel.bdattn_fwd_roofline", "kernel.bdattn_bwd_roofline",
                       "bdattn.kernel_ms_per_step", "step.mfu_pct"):
            assert absent not in line["metrics"]
        assert {"setup.compile_s", "device.idle_pct.train", "diffusion.masked_pct",
                "moe.load_max_over_mean"} <= set(line["metrics"])
        assert 20 < line["metrics"]["diffusion.masked_pct"]["value"] < 80
    else:
        assert set(line["metrics"]) == {"setup_s", "train_tok_s"}
        assert all(v["value"] > 0 for v in line["metrics"].values())
