"""The Qwen3-Next cell's own tests: its parameter and FLOP counts by hand, the
Gated DeltaNet kernels' cost function on made-up events against a count by
hand (an event whose share would pass 100 refused), its readers, its manifest
entries by membership and relative order (never "last": the next cell appends
after these), the runner's verdicts by hand, the chip's calibration readings
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
from benchmark import gdn_cost, qwen3next_cost  # noqa: E402
from benchmark.run import cell_metrics, load_json, load_manifest, load_module  # noqa: E402

CELL, CONFIG = "train-qwen3next-1chip-gdn-longseq", "qwen3-next-80b-a3b-ep16-train1"
SOURCE = "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
NEW = {"kernel.gdn_fwd_roofline": ("kernel", "%", "higher"),
       "kernel.gdn_bwd_roofline": ("kernel", "%", "higher"),
       "gdn.kernel_ms_per_step": ("linear-attention mixer", "ms", "lower"),
       "gdn.prep_ms_per_step": ("linear-attention mixer", "ms", "lower")}
SHARED = ["step.mfu_pct", "device.idle_pct.train", "host.work_ms_per_step",
          "host.idle_unnamed_pct.train", "setup.engine_init_s", "setup.place_params_s",
          "setup.cost_analysis_s", "moe.gmm_ms_per_step", "moe.load_max_over_mean",
          "kernel.moe_gmm_held_roofline", "moe.rows_held_pct", "kernel.flash_fwd_roofline",
          "kernel.flash_bwd_roofline"]
# readers that would fit this cell with no edit, but whose lists accepted tests
# pin to their own cells: a `benchmark` PR appends this cell there when it
# loosens those pins (PERF.md 7)
PINNED_ELSEWHERE = ["flash.kernel_ms_per_step", "kernel.causal_conv_roofline",
                    "moe.shared_ms_per_step", "setup.import_s", "setup.trace_s",
                    "setup.lower_s", "setup.cache_load_s", "setup.first_call_s",
                    "setup.first_call_unnamed_pct"]
SEQ = 32768
# device events as a v5e's compiled step names them (1 row, 16 key heads under
# 32 value heads of 128)
FWD = ("%gdn_chunk_fwd.5 = (bf16[1,32768,4096]{2,1,0:T(8,128)(2,1)}, "
       "f32[1,512,128,4096]{3,2,1,0:T(8,128)}, f32[1,32,8,128]{3,2,1,0:T(8,128)}) custom-call(")
BWD = ("%gdn_chunk_bwd.6 = (bf16[1,32768,2048]{2,1,0:T(8,128)(2,1)}, "
       "bf16[1,32768,2048]{2,1,0:T(8,128)(2,1)}, bf16[1,32768,4096]{2,1,0:T(8,128)(2,1)}) cust")
OTHER = "%kda_chunk_fwd.3 = (bf16[1,32768,4096]{2,1,0:T(8,128)(2,1)}, f32[1,512,128,4096]"


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
    assert len(cells) >= 10
    # the cells admitted before it keep their order; this one comes after them
    assert list(cells).index(CELL) > list(cells).index("train-phi4flash-1chip-sambay-seq16k")
    assert cells[CELL]["chips"] == 1 and cells[CELL]["config"] == CONFIG
    assert cells[CELL]["traffic"] == "qwen3next-1chip-gdn-longseq"
    assert [w["name"] for w in admitted["workloads"] if w["chips"] == 4] \
        == ["train-zero3-seq4k"]                               # still the one on four
    entry = next(c for c in admitted["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED == config()["reduced"] and entry["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    cell = load_json("workloads", CELL + ".json")
    assert cell["traffic"] == {"global_batch": 1, "seq_len": SEQ,
                               "warmup_steps": 2, "trace_steps": 4}
    assert cell["runner"] == "train_steps_qwen3_next" == config()["runner"]
    assert cell["why"] == cells[CELL]["why"] and "1 x 32,768 tokens" in cell["why"]
    assert "the rest 16x" in cell["why"] and len(cell["why"]) <= 200
    m = load_manifest()
    assert [x["name"] for x in cell_metrics(m, CELL, "end_to_end")] \
        == ["setup_s", "train_tok_s"] == cell["end_to_end"]
    layers = {x["name"]: x for x in cell_metrics(m, CELL, "per_layer")}
    assert set(SHARED) | set(NEW) | {"setup.compile_s", "setup.programs",
                                     "setup.cache_misses"} <= set(layers)
    for absent in PINNED_ELSEWHERE + ["kernel.kda_fwd_roofline", "kda.kernel_ms_per_step",
                                      "kernel.mla_fwd_roofline", "kernel.ssd_fwd_roofline",
                                      "kernel.moe_gmm_roofline", "coll.exposed_ms_per_step"]:
        assert absent not in layers
    names = [x["name"] for x in admitted["per_layer"]]
    for name, (layer, unit, better) in NEW.items():
        assert layers[name]["layer"] == layer and layers[name]["moves"] == "train_tok_s"
        assert layers[name]["workloads"][0] == CELL and layers[name]["unit"] == unit
        assert layers[name]["source"] == "device_trace" and layers[name]["better"] == better
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layers", name + ".py"))
        # membership and relative order: after every metric the benchmark had
        assert names.index(name) > names.index("diffattn.combine_ms_per_step")
    first = names.index("kernel.gdn_fwd_roofline")
    assert names[first:first + 4] == list(NEW)
    for name in SHARED + ["train_tok_s"]:
        metric = next(x for x in admitted["per_layer"] + admitted["end_to_end"]
                      if x["name"] == name)
        cells_of = metric["workloads"]
        assert cells_of.count(CELL) == 1
        assert cells_of.index(CELL) > 0        # after the cells that were there


def test_parameters_bytes_and_flops_by_hand():
    cfg = config()
    gdn = 2048 * 12288 + 2048 * 64 + 4 * 8192 + 32 + 32 + 128 + 4096 * 2048
    attn = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    assert (gdn, attn) == (33_718_464, 27_263_488)
    expert = 3 * 2048 * 512
    moe = 2048 * 512 + expert + 2048 + 32 * expert
    assert moe == 104_859_648
    total = 3 * gdn + attn + 4 * (moe + 4096) + 2 * 18992 * 2048 + 2048
    assert qwen3next_cost.param_count(cfg) == total == cfg["parameters"] == 625_667_136
    assert qwen3next_cost.bytes_at_rest(cfg) == 12 * total
    assert abs(qwen3next_cost.bytes_at_rest(cfg) / 1e9 - 7.51) < 0.005
    assert qwen3next_cost.router_width(cfg) == 512
    assert qwen3next_cost.experts_held_per_token(cfg) == 0.625
    assert qwen3next_cost.layer_kinds(cfg) == ["gdn", "gdn", "gdn", "attention"]
    scan = 16 * 4 * 64 * 128 + 32 * (2 * 64 * (128 + 2 * 128) + 6 * 128 * 128)
    assert scan == gdn_cost.token_flops(64, 16, 32, 128, 128) == 5_242_880
    gdn_fwd = 2 * (2048 * 12288 + 2048 * 64 + 4096 * 2048) + scan
    attn_fwd = 2 * (attn - 512) + 4 * 256 * 16 * (SEQ + 1) / 2
    moe_fwd = 2 * (2048 * 512 + expert + 2048 + 0.625 * expert)
    forward = 3 * (gdn_fwd + moe_fwd) + attn_fwd + moe_fwd + 2 * 2048 * 18992
    assert qwen3next_cost.forward_flops_per_token(cfg, SEQ) == forward
    assert qwen3next_cost.train_flops_per_token(cfg, SEQ) == 3 * forward
    assert abs(3 * forward - 2.004e9) < 1e6             # a token; 65.7 TFLOP a step
    # the experts by the rows they held in fact: half the expectation, less work
    fewer = qwen3next_cost.train_flops_per_token(cfg, SEQ, 0.3125)
    assert fewer == 3 * (forward - 4 * 2 * 0.3125 * expert)
    # the uncut model from the same arithmetic: 48 layers, 512 experts, 151,936 rows
    uncut = {**cfg, "num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936,
             "reduced": []}
    assert 79e9 < qwen3next_cost.param_count(uncut) < 82e9      # "80B"


def test_kernel_cost_against_a_count_by_hand():
    """A call at the cell's shape: 512 chunks of 64 tokens, 16 key heads under
    32 value heads of 128. Forward a key head's two triangular products and a
    value head's ``T`` applied, ``P U`` and the three state products, the
    backward twice that; bytes of the scan alone: q, k (16 heads), v, o (32) in
    bf16, ``g`` and ``beta`` a float32 a value head and token, the float32
    chunk states; the widths are ``linear_*``'s, never ``head_dim`` (256
    here); memory-bound both."""
    cfg = config()
    assert cfg["head_dim"] == 256
    chunks = SEQ // 64
    fwd_flops = chunks * (16 * 4 * 64 * 64 * 128
                          + 32 * (2 * 64 * 64 * (128 + 256) + 6 * 64 * 128 * 128))
    states = 4 * chunks * 32 * 128 * 128
    keys, values, gates = SEQ * 2048, SEQ * 4096, 4 * SEQ * 32
    fwd, bwd = gdn_cost.call_cost(FWD, cfg), gdn_cost.call_cost(BWD, cfg)
    assert fwd == {"flops": float(fwd_flops),
                   "bytes": 2 * (2 * keys + 2 * values) + 2 * gates + states}
    assert bwd == {"flops": 2.0 * fwd_flops,
                   "bytes": 2 * (4 * keys + 3 * values) + 4 * gates + states}
    assert abs(fwd["bytes"] - 1.887e9) < 1e6 and abs(bwd["bytes"] - 2.433e9) < 1e6
    assert abs(fwd_flops - 171.8e9) < 1e8 and abs(states - 1.074e9) < 1e6
    for cost in (fwd, bwd):                                  # memory-bound on a v5e
        assert cost["bytes"] / 819e9 > cost["flops"] / 197e12
    assert gdn_cost.call_cost(OTHER, cfg) is None
    assert gdn_cost.call_cost(FWD, {"hidden_size": 2048}) is None
    assert gdn_cost.call_cost("%gdn_chunk_fwd.3 = bf16[32768,4096]{1,0} cust", cfg) is None
    # a forward event as wide as the key heads, a backward one as the value
    # heads: not this configuration's kernels, not counted
    assert gdn_cost.call_cost(FWD.replace("32768,4096", "32768,2048"), cfg) is None
    assert gdn_cost.call_cost(BWD.replace("32768,2048", "32768,4096"), cfg) is None
    # nor would head_dim's 256 divide the event as the kernels laid it out
    assert gdn_cost.call_cost(FWD, {**cfg, "linear_value_head_dim": 256}) is None


def made_up_run(kernels) -> dict:
    return {"trace": {"kernels": kernels}, "config": config(), "trace_steps": 4,
            "device": {"kind": "TPU v5 lite", "count": 1}, "tokens_per_step": SEQ}


def test_readers_on_a_made_up_trace_and_a_share_over_100_is_refused():
    cfg = config()
    fwd, bwd = gdn_cost.call_cost(FWD, cfg), gdn_cost.call_cost(BWD, cfg)
    run = made_up_run({
        # three layers' forwards and two run again, four traced steps
        "%gdn_chunk_fwd.5": {"hlo": FWD, "count": 20, "seconds": 20 * 0.0175},
        "%gdn_chunk_bwd.6": {"hlo": BWD, "count": 12, "seconds": 12 * 0.0255},
        "%kda_chunk_fwd.3": {"hlo": OTHER, "count": 4, "seconds": 1.0},       # not ours
        "%fusion.7": {"hlo": "%fusion.7 = bf16[1,32768,4096]", "count": 9, "seconds": 0.3}})
    # of the 20 forward calls the 12 that a backward call used are credited:
    # a recomputed forward adds time and no work
    np.testing.assert_allclose(read("kernel.gdn_fwd_roofline", run),
                               100 * 12 * fwd["bytes"] / 819e9 / (20 * 0.0175))
    np.testing.assert_allclose(read("kernel.gdn_bwd_roofline", run),
                               100 * bwd["bytes"] / 819e9 / 0.0255)
    np.testing.assert_allclose(read("gdn.kernel_ms_per_step", run),
                               1e3 * (20 * 0.0175 + 12 * 0.0255) / 4)
    assert 0 < read("kernel.gdn_fwd_roofline", run) < 100
    assert 0 < read("kernel.gdn_bwd_roofline", run) < 100
    # an event faster than the memory system allows: the count is too high or
    # the time leaves work out. Refused, not capped
    fast = {k: dict(v) for k, v in run["trace"]["kernels"].items()}
    fast["%gdn_chunk_bwd.6"].update(seconds=12 * 0.002)
    assert 100 * bwd["bytes"] / 819e9 / 0.002 > 100
    assert read("kernel.gdn_bwd_roofline", made_up_run(fast)) is None
    fast["%gdn_chunk_fwd.5"].update(count=12, seconds=12 * 0.002)
    assert read("kernel.gdn_fwd_roofline", made_up_run(fast)) is None
    assert read("gdn.kernel_ms_per_step", made_up_run(fast)) == 1e3 * 24 * 0.002 / 4


def test_readers_report_nothing_when_nothing_matched(monkeypatch):
    """A program without the kernels or the scopes (the parent commit, a CPU
    rehearsal, another cell): every new reader returns None and raises
    nothing."""
    from benchmark import host_spans, scope_time
    monkeypatch.setattr(host_spans, "_xplane_path", lambda: None)
    for run in ({}, {"trace": None}, made_up_run({}),
                made_up_run({"%kda_chunk_fwd.3": {"hlo": OTHER, "count": 4, "seconds": 1.0}})):
        for name in NEW:
            assert read(name, dict(run)) is None, name
    # an event of the name in another configuration: no head counts, so no share
    other = made_up_run({"%gdn_chunk_fwd.5": {"hlo": FWD, "count": 1, "seconds": 1.0}})
    other["config"] = {"hidden_size": 2048, "head_dim": 128}
    assert read("kernel.gdn_fwd_roofline", other) is None
    table = {"ds_ms": {("ds.gdn.gates", "fwd"): 0.3, ("ds.gdn.gates", "bwd"): 1.2,
                       ("ds.gdn.split", "recompute"): 12.25, ("ds.moe.route", "fwd"): 9.0,
                       ("ds.kda.gates", "fwd"): 5.0}}
    monkeypatch.setattr(scope_time, "load", lambda run: table)
    assert read("gdn.prep_ms_per_step", {}) == 13.75
    monkeypatch.setattr(scope_time, "load", lambda run: {"ds_ms": {("ds.rope", "fwd"): 1.0}})
    assert read("gdn.prep_ms_per_step", {}) is None


ASSIGNED = SEQ * 10 * 4


def made_up_readings(**over) -> dict:
    counts = np.zeros(512, np.int64)
    counts[0], counts[32] = 82_000, ASSIGNED - 82_000
    return dict({"loss_err": 1e-5, "loss_after_err": 2e-5, "descends": True,
                 "logit_median": 1.0e-2, "logit_p90": 1.2e-2,
                 "grad_worst": ("['a']", 3e-2), "grad_routed_worst": ("['w1']", 0.12),
                 "grad_router_median": 0.11, "update_worst": ("['embedding']", 7e-6),
                 "counts": [counts.tolist()], "assigned": [ASSIGNED, ASSIGNED], "moved": 1500,
                 "rows_held": [82_000, 82_100], "share_fallback": 0,
                 "state_absmax": [3.5, 3.5], "decay_mean": [0.8264, 0.8264],
                 "beta_mean": [0.5015, 0.5015], "gate_mean": [0.5003, 0.5003]}, **over)


@pytest.mark.parametrize("fails,over", [
    (set(), {}),
    ({"loss"}, {"descends": False}), ({"loss"}, {"loss_after_err": 8e-3}),
    ({"logits"}, {"logit_median": 6e-2}), ({"logits"}, {"logit_p90": float("nan")}),
    ({"grads"}, {"grad_worst": ("['A_log']", float("inf"))}),
    ({"grads"}, {"grad_routed_worst": ("['w3']", 1.2)}),
    ({"grads"}, {"grad_router_median": 1.0}),
    ({"grads"}, {"update_worst": ("['norm_weight']", 1.0)}),
    ({"routing"}, {"moved": 20_000}), ({"routing"}, {"rows_held": [82_000, 90_000]}),
    ({"routing"}, {"share_fallback": 1}),
    ({"routing"}, {"assigned": [ASSIGNED, ASSIGNED - 10]}),
    ({"gdn"}, {"state_absmax": [7.0, 3.5]}), ({"gdn"}, {"decay_mean": [0.90, 0.83]}),
    ({"gdn"}, {"beta_mean": [float("nan"), 0.5]}), ({"gdn"}, {"gate_mean": [1.0, 0.5]})])
def test_verdicts_by_hand(fails, over):
    from benchmark.runners import train_steps_qwen3_next as runner
    ok = runner.verdicts(made_up_readings(**over), ASSIGNED, 512, 32)
    assert {k for k, good in ok.items() if not good} == fails
    wide = runner.verdicts(made_up_readings(logit_median=6e-2, moved=20_000),
                           ASSIGNED, 512, 32, slack=runner.REHEARSAL_SLACK)
    assert all(wide.values())       # a rehearsal's slack widens the distances


def chip_readings() -> list:
    with open(os.path.join(ROOT, "benchmark", "readings",
                           "qwen3_next_calibration.jsonl")) as f:
        return [json.loads(ln) for ln in f if ln.startswith("{")]


def chip_verdicts(row) -> dict:
    from benchmark import calibrate_qwen3_next
    return calibrate_qwen3_next.verdicts_of(row)


@pytest.mark.parametrize("against", ["sound", "bf16", "fp8", "no_softplus", "beta_doubled",
                                     "no_key_repeat", "sigmoid_out", "norm_plus_one",
                                     "rope_all", "no_attn_gate", "ungated_shared",
                                     "no_renorm", "bf16_state"])
def test_the_limits_stand_between_what_the_chip_read(against):
    """The chip's readings of the timed step at 1 x 32,768 tokens against the
    reference sound and made wrong (``calibrate_qwen3_next.py``, kept in
    ``benchmark/readings/``), through the runner's limits as they are now:
    each wrong way gives ``correct`` false, fp8 (the precision below the
    configuration's) by one limit at least and not by each; the sound program
    passes on two seeds. A limit moved past either reading fails here. Two
    variants are required of nothing and the file says what they read: a
    reference at bf16 operands (the configuration's own precision) and the
    state carried in bf16, which at this gate's memory of some six tokens
    reads within a tenth of the sound reference on the logits and a quarter
    on the worst gradient leaf (the runner's comment)."""
    from benchmark.runners import train_steps_qwen3_next as runner
    from benchmark.reference import qwen3_next as reference
    not_told = (reference.OWN_PRECISION, ) + reference.NOT_TOLD
    everything = chip_readings()
    assert set(reference.WRONG) | {"sound", reference.OWN_PRECISION} == {
        r["against"] for r in everything}
    rows = [r for r in everything if r["against"] == against]
    assert len({r["seed"] for r in rows}) >= (2 if against in ("sound", "fp8") + not_told
                                              else 1)
    for row in rows:
        ok = chip_verdicts(row)
        if against not in not_told:
            assert all(ok.values()) == (against == "sound"), (row["seed"], ok)
        else:
            sound = next(r for r in everything
                         if r["against"] == "sound" and r["seed"] == row["seed"])
            for key in ("logit_median", "logit_p90"):
                assert 0.9 <= row[key] / sound[key] <= 1.1, (key, row[key], sound[key])
            assert 0.9 <= row["grad_worst"][1] / sound["grad_worst"][1] <= 1.25
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
         "--seed", str(2**31 + 41), "--seconds", "2", "--trace", str(trace), "--rehearse"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    said = next(ln for ln in lines if ln.startswith("training:"))
    # the cell's one chip, however many the host has
    assert "'data': 1," in said and "2 of 16 experts held" in said
    assert "gdn+moe/gdn+moe/gdn+moe/attention+moe" in said and "batch 1 x 96" in said
    check = next(ln for ln in lines if ln.startswith("correctness:"))
    assert "FAILED" not in check and "in_proj_qkvz" in check and "largest |S|" in check
    assert "shared_expert_gate" in check and "the attention gate's mean" in check
    notes = json.loads(next(ln for ln in lines if ln.startswith("notes: "))[7:])
    assert notes["model_layers"] == {"gdn+moe": 3.0, "attention+moe": 1.0}
    assert all(notes["verdicts"].values()) and notes["share_fallback_layers"] >= 0
    assert notes["step_programs"] == 1 and notes["n_params"] == qwen3next_cost.param_count(
        {**config(), **config()["rehearse"]})
    assert sum(notes["expert_counts"]) == 96 * 2 * 4
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in cell_metrics(load_manifest(), CELL, group)}
    assert set(line["metrics"]) <= set(declared)
    for name, got in line["metrics"].items():
        assert got["unit"] == declared[name]["unit"] and np.isfinite(got["value"])
    if trace:
        # no kernel events and no utilization on a CPU; the scopes are read
        for absent in ("kernel.gdn_fwd_roofline", "kernel.gdn_bwd_roofline",
                       "gdn.kernel_ms_per_step", "step.mfu_pct", "kernel.flash_fwd_roofline"):
            assert absent not in line["metrics"]
        # (``gdn.prep_ms_per_step`` too where this rehearsal's trace is the
        # one file under ``.bench_out/``: another cell's beside it, as under
        # the suite's workers, and ``scope_time`` reads neither)
        assert {"setup.compile_s", "device.idle_pct.train", "moe.rows_held_pct",
                "moe.load_max_over_mean"} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"setup_s", "train_tok_s"}
        assert all(v["value"] > 0 for v in line["metrics"].values())
