"""The Ling-3.0-flash cell's own tests: its parameter and FLOP counts by hand,
the chunk kernels' cost function on made-up events against a count by hand,
its readers, its manifest entries by membership and relative order (never
"last", never an ordered list of all metrics: the next cell appends after
these), the runner's verdicts by hand, the chip's calibration readings
through the limits as they are, and a rehearsal of the runner end to end. All
on the CPU; no number here is a measurement."""

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
from benchmark import kda_cost, ling3_cost  # noqa: E402
from benchmark.run import cell_metrics, load_json, load_manifest, load_module  # noqa: E402

CELL, CONFIG = "train-ling3flash-1chip-kda-longseq", "ling-3.0-flash-ep64-train1"
SOURCE = "https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/config.json"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "layer_types", "num_experts",
           "vocab_size"]
NEW = {"kernel.kda_fwd_roofline": ("kernel", "%", "higher"),
       "kernel.kda_bwd_roofline": ("kernel", "%", "higher"),
       "kda.kernel_ms_per_step": ("linear-attention mixer", "ms", "lower"),
       "kda.gate_ms_per_step": ("linear-attention mixer", "ms", "lower")}
SHARED = ["step.mfu_pct", "device.idle_pct.train", "host.work_ms_per_step",
          "host.idle_unnamed_pct.train", "setup.engine_init_s", "setup.place_params_s",
          "moe.gmm_ms_per_step", "moe.load_max_over_mean", "kernel.moe_gmm_held_roofline",
          "moe.rows_held_pct"]
# readers that fit this cell with no edit (my chip run, PR 48, read all five), but
# whose lists the Kimi-VL and Granite cells' tests pin to their own cell: a
# `benchmark` PR appends this cell there when it loosens those pins (PERF.md 7)
PINNED_ELSEWHERE = ["kernel.mla_fwd_roofline", "kernel.mla_bwd_roofline",
                    "mla.kernel_ms_per_step", "moe.shared_ms_per_step",
                    "kernel.causal_conv_roofline"]
SEQ = 16384
# device events as a v5e's compiled step names them (1 row, 32 heads of 128)
FWD = ("%kda_chunk_fwd.3 = (bf16[1,16384,4096]{2,1,0:T(8,128)(2,1)}, "
       "f32[1,256,128,4096]{3,2,1,0:T(8,128)}, f32[1,32,8,128]{3,2,1,0:T(8,128)}) custom-call(")
BWD = ("%kda_chunk_bwd.2 = (bf16[1,16384,4096]{2,1,0:T(8,128)(2,1)}, "
       "bf16[1,16384,4096]{2,1,0:T(8,128)(2,1)}, bf16[1,16384,4096]{2,1,0:T(8,128)(2,1)}) cust")
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
    assert list(cells).index(CELL) > list(cells).index("train-keyevl2-1chip-dsa-seq32k")
    assert cells[CELL]["chips"] == 1 and cells[CELL]["config"] == CONFIG
    assert cells[CELL]["traffic"] == "ling3flash-1chip-kda-longseq"
    assert [w["name"] for w in admitted["workloads"] if w["chips"] == 4] \
        == ["train-zero3-seq4k"]                               # still the one on four
    entry = next(c for c in admitted["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED == config()["reduced"] and entry["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    cell = load_json("workloads", CELL + ".json")
    assert cell["traffic"] == {"global_batch": 1, "seq_len": SEQ,
                               "warmup_steps": 2, "trace_steps": 4}
    assert cell["runner"] == "train_steps_ling3_flash" == config()["runner"]
    assert cell["why"] == cells[CELL]["why"] and "1 x 16,384 tokens" in cell["why"]
    assert "1/8 of their load" in cell["why"] and len(cell["why"]) <= 200
    m = load_manifest()
    assert [x["name"] for x in cell_metrics(m, CELL, "end_to_end")] \
        == ["setup_s", "train_tok_s"] == cell["end_to_end"]
    layers = {x["name"]: x for x in cell_metrics(m, CELL, "per_layer")}
    assert set(SHARED) | set(NEW) | {"setup.compile_s", "setup.programs",
                                     "setup.cache_misses"} <= set(layers)
    # not under the readers that would misread this cell: the whole mixer
    # part here is five KDA layers and one MLA layer, not latent projections
    for absent in PINNED_ELSEWHERE + ["mla.proj_ms_per_step", "kernel.flash_fwd_roofline",
                   "kernel.ssd_fwd_roofline", "ssm.kernel_ms_per_step",
                                      "kernel.moe_gmm_roofline", "coll.exposed_ms_per_step"]:
        assert absent not in layers
    names = [x["name"] for x in admitted["per_layer"]]
    for name, (layer, unit, better) in NEW.items():
        assert layers[name]["layer"] == layer and layers[name]["moves"] == "train_tok_s"
        assert layers[name]["workloads"][0] == CELL and layers[name]["unit"] == unit
        assert layers[name]["source"] == "device_trace" and layers[name]["better"] == better
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layers", name + ".py"))
        # membership and relative order: after every metric the benchmark had
        assert names.index(name) > names.index("dsa.chosen_pct")
    first = names.index("kernel.kda_fwd_roofline")
    assert names[first:first + 4] == list(NEW)
    for name in SHARED + ["train_tok_s"]:
        metric = next(x for x in admitted["per_layer"] + admitted["end_to_end"]
                      if x["name"] == name)
        cells_of = metric["workloads"]
        assert cells_of.count(CELL) == 1
        assert cells_of.index(CELL) > 0        # after the cells that were there


def test_parameters_bytes_and_flops_by_hand():
    cfg = config()
    kda = (4 * 2560 * 4096 + 2 * 2560 * 4096 + 2560 * 32 + 3 * 4 * 4096 + 32 + 4096 + 128)
    mla = 2560 * 6144 + 2560 * 576 + 512 + 512 * 8192 + 2560 * 32 + 4096 * 2560
    assert (kda, mla) == (63_049_888, 31_965_696)
    expert = 3 * 2560 * 768
    moe = 2560 * 512 + 512 + expert + 8 * expert
    dense = 3 * 2560 * 6144
    layers = [kda + dense] + [kda + moe] * 3 + [mla + moe] + [kda + moe]
    assert layers[0] + 5120 == 110_240_928 and kda + moe + 5120 == 117_450_400
    assert mla + moe + 5120 == 86_366_208
    total = sum(layers) + 6 * 5120 + 2 * 19648 * 2560 + 2560
    assert ling3_cost.param_count(cfg) == total == 767_009_056
    assert ling3_cost.bytes_at_rest(cfg) == 12 * total
    assert abs(ling3_cost.bytes_at_rest(cfg) / 1e9 - 9.20) < 0.005
    assert ling3_cost.router_width(cfg) == 512
    assert ling3_cost.experts_held_per_token(cfg) == 0.125
    assert ling3_cost.layer_kinds(cfg) == [("kda", "dense")] + [("kda", "moe")] * 3 + [
        ("mla", "moe"), ("kda", "moe")]
    scan = 32 * (2 * 64 * (3 * 128 + 2 * 128) + 6 * 128 * 128)
    assert scan == 32 * kda_cost.token_flops(64, 128, 128) == 5_767_168
    kda_fwd = 2 * (6 * 2560 * 4096 + 2560 * 32) + scan
    mla_fwd = 2 * (mla - 512) + 2 * 320 * 32 * (SEQ + 1) / 2
    moe_fwd = 2 * (2560 * 512 + expert + 0.125 * expert)
    forward = (kda_fwd + 2 * dense + 4 * (kda_fwd + moe_fwd) + (mla_fwd + moe_fwd)
               + 2 * 2560 * 19648)
    assert ling3_cost.forward_flops_per_token(cfg, SEQ) == forward
    assert ling3_cost.train_flops_per_token(cfg, SEQ) == 3 * forward
    assert abs(3 * forward - 3.495e9) < 2e6             # a token; 57.3 TFLOP a step
    # the uncut model from the same arithmetic: 42 layers, 512 experts, 157,184 rows
    uncut = {**cfg, "num_hidden_layers": 42, "first_k_dense_replace": 2, "num_experts": 512,
             "vocab_size": 157184, "reduced": [],
             "layer_types": ["mla" if (i + 1) % 6 == 0 else "kda" for i in range(42)]}
    assert 120e9 < ling3_cost.param_count(uncut) < 130e9     # "~125B"


def test_kernel_cost_against_a_count_by_hand():
    """A call at the cell's shape: 256 chunks of 64 tokens and 32 heads of
    128. Forward ``2 Q^2 (3 d_k + 2 d_v) + 6 Q d_k d_v`` a chunk and head, the
    backward twice that; bytes of the mathematics: five (nine) bf16 values a
    channel and token (q, k, v, the decay's pre-activation, o; the gate and
    its running sum never reach HBM), beta (and its gradient) a head and
    token, and the float32 chunk states; memory-bound both."""
    cfg = config()
    chunks, heads = SEQ // 64, 32
    fwd_flops = chunks * heads * (2 * 64 * 64 * (3 * 128 + 2 * 128) + 6 * 64 * 128 * 128)
    states = 4 * chunks * heads * 128 * 128
    values, betas = SEQ * 4096, SEQ * heads
    fwd, bwd = kda_cost.call_cost(FWD, cfg), kda_cost.call_cost(BWD, cfg)
    assert fwd == {"flops": float(fwd_flops), "bytes": 2 * (5 * values + betas) + states}
    assert bwd == {"flops": 2.0 * fwd_flops, "bytes": 2 * (9 * values + 2 * betas) + states}
    assert abs(fwd["bytes"] - 1.209e9) < 1e6 and abs(bwd["bytes"] - 1.747e9) < 1e6
    assert abs(fwd_flops - 94.5e9) < 1e8 and abs(states - 0.537e9) < 1e6
    for cost in (fwd, bwd):                                  # memory-bound on a v5e
        assert cost["bytes"] / 819e9 > cost["flops"] / 197e12
    assert kda_cost.call_cost(OTHER, cfg) is None
    assert kda_cost.call_cost(FWD, {"hidden_size": 2560}) is None
    assert kda_cost.call_cost("%kda_chunk_fwd.3 = bf16[16384,4096]{1,0} cust", cfg) is None


def made_up_run(kernels) -> dict:
    return {"trace": {"kernels": kernels}, "config": config(), "trace_steps": 4,
            "device": {"kind": "TPU v5 lite", "count": 1}, "tokens_per_step": SEQ}


def test_readers_on_a_made_up_trace():
    cfg = config()
    fwd, bwd = kda_cost.call_cost(FWD, cfg), kda_cost.call_cost(BWD, cfg)
    run = made_up_run({
        # five layers' forwards and four run again, four traced steps
        "%kda_chunk_fwd.3": {"hlo": FWD, "count": 36, "seconds": 36 * 0.012},
        "%kda_chunk_bwd.2": {"hlo": BWD, "count": 20, "seconds": 20 * 0.030},
        "%ssd_chunk_fwd.3": {"hlo": OTHER, "count": 4, "seconds": 1.0},       # not ours
        "%fusion.7": {"hlo": "%fusion.7 = bf16[1,16384,4096]", "count": 9, "seconds": 0.3}})
    # of the 36 forward calls the 20 that a backward call used are credited:
    # a recomputed forward adds time and no work
    np.testing.assert_allclose(read("kernel.kda_fwd_roofline", run),
                               100 * 20 * fwd["bytes"] / 819e9 / (36 * 0.012))
    np.testing.assert_allclose(read("kernel.kda_bwd_roofline", run),
                               100 * bwd["bytes"] / 819e9 / 0.030)
    np.testing.assert_allclose(read("kda.kernel_ms_per_step", run),
                               1e3 * (36 * 0.012 + 20 * 0.030) / 4)
    assert 0 < read("kernel.kda_fwd_roofline", run) < 100
    assert 0 < read("kernel.kda_bwd_roofline", run) < 100
    # a step that scans once a layer, and a forward-only trace: every call
    once = {k: dict(v) for k, v in run["trace"]["kernels"].items()}
    once["%kda_chunk_fwd.3"].update(count=20, seconds=20 * 0.012)
    np.testing.assert_allclose(read("kernel.kda_fwd_roofline", made_up_run(once)),
                               100 * fwd["bytes"] / 819e9 / 0.012)
    del once["%kda_chunk_bwd.2"]
    np.testing.assert_allclose(read("kernel.kda_fwd_roofline", made_up_run(once)),
                               100 * fwd["bytes"] / 819e9 / 0.012)


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
    # an event of the name in another configuration: no chunk, so no share
    other = made_up_run({"%kda_chunk_fwd.3": {"hlo": FWD, "count": 1, "seconds": 1.0}})
    other["config"] = {"hidden_size": 2048}
    assert read("kernel.kda_fwd_roofline", other) is None
    table = {"ds_ms": {("ds.kda.gates", "forward"): 3.0, ("ds.kda.gates", "backward"): 6.5,
                       ("ds.kda.norm", "recompute"): 3.1, ("ds.moe.route", "forward"): 9.0}}
    monkeypatch.setattr(scope_time, "load", lambda run: table)
    assert read("kda.gate_ms_per_step", {}) == 12.6
    monkeypatch.setattr(scope_time, "load", lambda run: {"ds_ms": {("ds.rope", "forward"): 1.0}})
    assert read("kda.gate_ms_per_step", {}) is None


@pytest.mark.parametrize("what,low,high", [
    ("rounded_the_other_way", 0.0, 0.0), ("not_written", 0.9, 1.0),
    ("rate_one_percent_off", 8e-3, 1.2e-2)])
def test_the_parameters_distance_takes_float32s_rounding_out_and_nothing_else(what, low, high):
    """A ``dt_bias`` of -2 to -7 under an update of 1e-5: five elements one
    float32 step off read 1.4e-3 plainly and nothing here; a leaf not written
    and a learning rate 1% off (on a matrix of small values) read what they
    did."""
    from benchmark.runners import train_steps_ling3_flash as runner
    from benchmark.runners.train_steps_kimi_vl import adamw_first_step
    rng = np.random.default_rng(0)
    old = -rng.uniform(2.3, 6.9, 4096).astype(np.float32)
    if what == "rate_one_percent_off":
        old = (0.02 * rng.standard_normal(4096)).astype(np.float32)
    update = adamw_first_step((1e-3 * rng.standard_normal(4096)).astype(np.float32))
    new = old + update
    if what == "rounded_the_other_way":
        new[:5] = np.nextafter(new[:5], np.float32(0))
        assert np.linalg.norm(new - (old + update)) / np.linalg.norm(update) > 1e-3
    elif what == "not_written":
        new = old.copy()
    else:
        new = old + np.float32(1.01) * update
    got = np.linalg.norm(runner.beyond_rounding(new, old + update)) / np.linalg.norm(update)
    assert low <= got <= high and (got <= runner.UPDATE_RTOL) == (high == 0.0)


ASSIGNED, KEPT = SEQ * 8 * 5, SEQ * 4 * 5


def made_up_readings(**over) -> dict:
    counts = np.zeros(512, np.int64)
    counts[0], counts[8] = 10_000, ASSIGNED - 10_000
    return dict({"loss_err": 1e-5, "loss_after_err": 2e-5, "descends": True,
                 "logit_median": 1.0e-2, "logit_p90": 1.3e-2,
                 "grad_worst": ("['a']", 7e-2), "grad_routed_worst": ("['w1']", 0.17),
                 "grad_router_median": 0.18, "update_worst": ("['embedding']", 1.2e-4),
                 "counts": [counts.tolist()], "assigned": [ASSIGNED, ASSIGNED], "moved": 300,
                 "groups_kept": [KEPT, KEPT], "groups_moved": 100,
                 "rows_held": [10_000, 10_100], "share_fallback": 0,
                 "state_absmax": [2.0, 2.1], "decay_mean": [0.9000, 0.9001],
                 "beta_mean": [0.5000, 0.5001]}, **over)


@pytest.mark.parametrize("fails,over", [
    (set(), {}),
    ({"loss"}, {"descends": False}), ({"loss"}, {"loss_after_err": 8e-3}),
    ({"logits"}, {"logit_median": 6e-2}), ({"logits"}, {"logit_p90": float("nan")}),
    ({"grads"}, {"grad_worst": ("['A_log']", float("inf"))}),
    ({"grads"}, {"grad_routed_worst": ("['w3']", 1.2)}),
    ({"grads"}, {"grad_router_median": 1.0}),
    ({"grads"}, {"update_worst": ("['o_norm']", 1.0)}),
    ({"routing"}, {"moved": 6_000}), ({"routing"}, {"groups_moved": 2_000}),
    ({"routing"}, {"groups_moved": -1}), ({"routing"}, {"groups_kept": [KEPT, KEPT - 4]}),
    ({"routing"}, {"rows_held": [10_000, 11_000]}), ({"routing"}, {"share_fallback": 1}),
    ({"routing"}, {"assigned": [ASSIGNED, ASSIGNED - 8]}),
    ({"kda"}, {"state_absmax": [4.0, 2.0]}), ({"kda"}, {"decay_mean": [0.95, 0.90]}),
    ({"kda"}, {"beta_mean": [float("nan"), 0.5]})])
def test_verdicts_by_hand(fails, over):
    from benchmark.runners import train_steps_ling3_flash as runner
    ok = runner.verdicts(made_up_readings(**over), ASSIGNED, KEPT, 512, 8)
    assert {k for k, good in ok.items() if not good} == fails
    wide = runner.verdicts(made_up_readings(logit_median=6e-2, moved=6_000),
                           ASSIGNED, KEPT, 512, 8, slack=runner.REHEARSAL_SLACK)
    assert all(wide.values())       # a rehearsal's slack widens the distances


def test_the_runners_positions_lie_late_in_the_sequence():
    from benchmark.runners import train_steps_ling3_flash as runner
    at = runner.logit_positions(1, SEQ)
    assert at.shape[0] == 1 and runner.LOGIT_POSITIONS - 2 <= at.shape[1] <= runner.LOGIT_POSITIONS
    assert at[0][0] == 0 and at[0][-1] == SEQ - 2 and (np.diff(at[0]) > 0).all()
    assert (at[0] >= 3 * SEQ // 4 - 2).mean() >= 0.7        # most of them late
    assert runner.logit_positions(1, 96).max() == 94        # a position with a next token


def chip_readings() -> list:
    with open(os.path.join(ROOT, "benchmark", "readings",
                           "ling3_flash_calibration.jsonl")) as f:
        return [json.loads(ln) for ln in f if ln.startswith("{")]


def chip_verdicts(row) -> dict:
    """The runner's limits as they are now on one of the chip's rows (the
    counts themselves are not kept: two numbers with the row's sums stand in)."""
    from benchmark.runners import train_steps_ling3_flash as runner
    counts = np.zeros(512, np.int64)
    counts[0], counts[8] = row["rows_held"][0], row["assigned"][0] - row["rows_held"][0]
    return runner.verdicts({**row, "counts": [counts.tolist()]}, ASSIGNED, KEPT, 512, 8)


@pytest.mark.parametrize("against", ["sound", "bf16", "fp8", "scalar_decay", "no_delta",
                                     "softplus_gate", "no_k_norm", "bf16_state",
                                     "no_v_conv", "no_mla_gate", "no_groups"])
def test_the_limits_stand_between_what_the_chip_read(against):
    """The chip's readings of the timed step at 1 x 16,384 tokens against the
    reference sound and made wrong (``calibrate_ling3_flash.py``, kept in
    ``benchmark/readings/``), through the runner's limits as they are now:
    each wrong way gives ``correct`` false, fp8 (the precision below the
    configuration's) by one limit at least and not by each; the sound program
    passes, and so does a reference at the configuration's own bf16. A limit
    moved past either reading fails here."""
    from benchmark.runners import train_steps_ling3_flash as runner
    rows = [r for r in chip_readings() if r["against"] == against]
    assert len({r["seed"] for r in rows}) >= (2 if against in ("sound", "bf16", "fp8") else 1)
    for row in rows:
        ok = chip_verdicts(row)
        assert all(ok.values()) == (against in ("sound", "bf16")), (row["seed"], ok)
        assert ok == row["verdicts"] and row["lr"] == runner.LR
    for row in rows if against == "fp8" else []:
        assert not all(chip_verdicts(row).values()) and any(chip_verdicts(row).values())


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
    assert "kda+dense/latent+moe/kda+moe" in said and "batch 1 x 96" in said
    assert "top-2 in 2 of 4 groups" in said
    check = next(ln for ln in lines if ln.startswith("correctness:"))
    assert "FAILED" not in check and "f_proj" in check and "largest |S|" in check
    notes = json.loads(next(ln for ln in lines if ln.startswith("notes: "))[7:])
    assert notes["model_layers"] == {"kda+dense": 1.0, "latent+moe": 1.0, "kda+moe": 1.0}
    assert all(notes["verdicts"].values()) and notes["share_fallback_layers"] >= 0
    assert notes["step_programs"] == 1 and notes["n_params"] == ling3_cost.param_count(
        {**config(), **config()["rehearse"]})
    assert sum(notes["group_counts"][0]) == 96 * 2 * 2
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in cell_metrics(load_manifest(), CELL, group)}
    assert set(line["metrics"]) <= set(declared)
    for name, got in line["metrics"].items():
        assert got["unit"] == declared[name]["unit"] and np.isfinite(got["value"])
    if trace:
        # no kernel events and no utilization on a CPU; the scopes are read
        for absent in ("kernel.kda_fwd_roofline", "kernel.kda_bwd_roofline",
                       "kda.kernel_ms_per_step", "step.mfu_pct"):
            assert absent not in line["metrics"]
        assert {"setup.compile_s", "device.idle_pct.train", "moe.rows_held_pct",
                "moe.load_max_over_mean"} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"setup_s", "train_tok_s"}
        assert all(v["value"] > 0 for v in line["metrics"].values())
