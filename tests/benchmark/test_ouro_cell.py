"""The Ouro cell's own tests: its parameter and FLOP counts by hand, its
readers on a made-up trace, its manifest entries by membership and relative
order (never "last": the next cell appends after these), the runner's verdicts
by hand, the chip's calibration readings through the limits as they are, and a
rehearsal of the runner end to end. All on the CPU; no number here is a
measurement."""

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
from benchmark import flash_cost, ouro_cost  # noqa: E402
from benchmark.run import cell_metrics, load_json, load_manifest, load_module  # noqa: E402

CELL, CONFIG = "train-ouro-1chip-loop4-seq16k", "ouro-2.6b-train1"
SOURCE = "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
REDUCED = ["num_hidden_layers", "layer_types"]
NEW = {"loop.flash_ms_per_step": ("kernel", "ms", "lower", "device_trace"),
       "loop.head_ms_per_step": ("head", "ms", "lower", "device_trace"),
       "loop.exit_ms_per_step": ("looped stack", "ms", "lower", "device_trace"),
       "loop.recompute_ms_per_step": ("model step, training", "ms", "lower", "device_trace"),
       "loop.expected_exit_pass": ("looped stack", "passes", "higher", "program_counter")}
SHARED = ["step.mfu_pct", "device.idle_pct.train", "host.work_ms_per_step",
          "host.idle_unnamed_pct.train", "setup.engine_init_s", "setup.place_params_s",
          "setup.cost_analysis_s", "kernel.flash_fwd_roofline", "kernel.flash_bwd_roofline"]
# readers that would fit this cell with no edit, but whose lists accepted tests
# pin to their own cells (PERF.md 7)
PINNED_ELSEWHERE = ["flash.kernel_ms_per_step", "scope.fwd_ms_per_step",
                    "scope.recompute_ms_per_step", "scope.head_ms_per_step",
                    "setup.import_s", "setup.trace_s", "setup.first_call_s"]
SEQ, LAYERS, PASSES = 16384, 8, 4
# device events as a v5e's compiled step names them (1 row, 16 heads of 128, MHA)
FWD = ("%flash_fwd.3 = (bf16[16,1,16384,128]{3,2,1,0:T(8,128)(2,1)}, "
       "f32[16,1,16384,1]{3,2,1,0:T(8,128)}) custom-call(")
BWD = ("%flash_dkdv_dq.4 = (bf16[16,16384,128]{2,1,0:T(8,128)(2,1)}, "
       "bf16[16,16384,128]{2,1,0:T(8,128)(2,1)}, bf16[16,1,16384,128]{3,2,1,0}) custom-call(")


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
    assert len(cells) >= 11
    # the cells admitted before it keep their order; this one comes after them
    assert list(cells).index(CELL) > list(cells).index("train-qwen3next-1chip-gdn-longseq")
    assert cells[CELL]["chips"] == 1 and cells[CELL]["config"] == CONFIG
    assert cells[CELL]["traffic"] == "ouro-1chip-loop4-seq16k"
    assert [w["name"] for w in admitted["workloads"] if w["chips"] == 4] \
        == ["train-zero3-seq4k"]                               # still the one on four
    entry = next(c for c in admitted["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == REDUCED == config()["reduced"] and entry["source"] == SOURCE
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    manifest_checks.check_reduced(entry, config())
    cell = load_json("workloads", CELL + ".json")
    assert cell["traffic"] == {"global_batch": 1, "seq_len": SEQ,
                               "warmup_steps": 2, "trace_steps": 4}
    assert cell["runner"] == "train_steps_ouro" == config()["runner"]
    assert cell["why"] == cells[CELL]["why"] and "1 x 16,384 tokens" in cell["why"]
    assert "run 4 times over shared weights" in cell["why"] and len(cell["why"]) <= 200
    m = load_manifest()
    assert [x["name"] for x in cell_metrics(m, CELL, "end_to_end")] \
        == ["setup_s", "train_tok_s"] == cell["end_to_end"]
    layers = {x["name"]: x for x in cell_metrics(m, CELL, "per_layer")}
    assert set(layers) == set(SHARED) | set(NEW) | {"setup.compile_s", "setup.programs",
                                                    "setup.cache_misses"}
    for absent in PINNED_ELSEWHERE:
        assert absent not in layers
    names = [x["name"] for x in admitted["per_layer"]]
    for name, (layer, unit, better, source) in NEW.items():
        assert layers[name]["layer"] == layer and layers[name]["moves"] == "train_tok_s"
        assert layers[name]["workloads"] == [CELL] and layers[name]["unit"] == unit
        assert layers[name]["source"] == source and layers[name]["better"] == better
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layers", name + ".py"))
        # membership and relative order: after every metric the benchmark had
        assert names.index(name) > names.index("gdn.prep_ms_per_step")
    first = names.index("loop.flash_ms_per_step")
    assert names[first:first + 5] == list(NEW)
    for name in SHARED + ["train_tok_s"]:
        metric = next(x for x in admitted["per_layer"] + admitted["end_to_end"]
                      if x["name"] == name)
        cells_of = metric["workloads"]
        assert cells_of.count(CELL) == 1
        assert cells_of.index(CELL) > cells_of.index("train-qwen3next-1chip-gdn-longseq")


def test_parameters_bytes_and_flops_by_hand():
    cfg = config()
    matrices = 4 * 2048 * 2048 + 3 * 2048 * 5632
    layer = matrices + 4 * 2048
    assert (matrices, layer) == (51_380_224, 51_388_416) and ouro_cost.layer_matrices(cfg) == matrices
    total = LAYERS * layer + 2 * 49152 * 2048 + 2048 + 2049
    assert ouro_cost.param_count(cfg) == total == cfg["parameters"] == 612_438_017
    assert ouro_cost.bytes_at_rest(cfg) == 12 * total
    assert abs(ouro_cost.bytes_at_rest(cfg) / 1e9 - 7.35) < 0.005
    assert 100 * ouro_cost.bytes_at_rest(cfg) / 16_909_336_064 > 43
    stack = PASSES * LAYERS * 2 * matrices
    pairs = PASSES * LAYERS * 4 * 16 * 128 * (SEQ + 1) / 2
    head = PASSES * 2 * 2048 * 49152
    forward = stack + pairs + head + (PASSES - 1) * 2 * 2048
    assert ouro_cost.forward_flops_per_token(cfg, SEQ) == forward
    assert ouro_cost.train_flops_per_token(cfg, SEQ) == 3 * forward
    assert abs(3 * forward - 18.72e9) < 1e7             # a token; 306.7 TFLOP a step
    shares = [round(100 * part / forward, 1) for part in (stack, pairs, head)]
    assert shares == [52.7, 34.4, 12.9]
    # a parameter is counted once, its FLOPs once a pass
    once = ouro_cost.forward_flops_per_token({**cfg, "total_ut_steps": 1}, SEQ)
    assert forward == 4 * once + 3 * 2 * 2048
    assert ouro_cost.param_count({**cfg, "total_ut_steps": 1}) == total


def made_up_run(kernels) -> dict:
    return {"trace": {"kernels": kernels}, "config": config(), "trace_steps": 4,
            "device": {"kind": "TPU v5 lite", "count": 1}, "tokens_per_step": SEQ}


def test_readers_on_a_made_up_trace():
    """32 forwards and 32 backwards a step from ONE call site each a pass; the
    accepted flash rooflines read head 128 and group 1 off the event and stay
    under 100."""
    run = made_up_run({
        "%flash_fwd.3": {"hlo": FWD, "count": 4 * 32, "seconds": 4 * 32 * 0.0128},
        "%flash_dkdv_dq.4": {"hlo": BWD, "count": 4 * 32, "seconds": 4 * 32 * 0.0330},
        "%fusion.7": {"hlo": "%fusion.7 = bf16[1,16384,2048]", "count": 9, "seconds": 0.3}})
    np.testing.assert_allclose(read("loop.flash_ms_per_step", run), 32 * (12.8 + 33.0))
    least = 4.0 * 16 * 128 * (SEQ + 1) / 2 * SEQ
    assert flash_cost.call_flops(FWD, config()) == least == flash_cost.call_flops(BWD, config())
    np.testing.assert_allclose(read("kernel.flash_fwd_roofline", run),
                               100 * least / 197e12 / 0.0128, rtol=1e-3)
    assert 0 < read("kernel.flash_fwd_roofline", run) < 100
    assert 0 < read("kernel.flash_bwd_roofline", run) < 100
    run["loop_exit_mass_samples"] = [[.5, .25, .125, .125], [.4, .3, .2, .1]]
    np.testing.assert_allclose(read("loop.expected_exit_pass", run), (1.875 + 2.0) / 2)
    dead = dict(run, loop_exit_mass_samples=[[0., 0., 0., 1.]])
    assert read("loop.expected_exit_pass", dead) == 4.0


def test_readers_report_nothing_when_nothing_matched(monkeypatch):
    """A program without the kernels, the scopes or the family (the parent
    commit, a CPU rehearsal, another cell): every new reader returns None and
    raises nothing."""
    from benchmark import host_spans, scope_time
    monkeypatch.setattr(host_spans, "_xplane_path", lambda: None)
    for run in ({}, {"trace": None}, made_up_run({}),
                made_up_run({"%kda_chunk_fwd.3": {"hlo": "%kda_chunk_fwd.3 = bf16[1,2]",
                                                  "count": 4, "seconds": 1.0}})):
        for name in NEW:
            assert read(name, dict(run)) is None, name
    table = {"ds_ms": {("ds.loop.exit", "fwd"): 0.5, ("ds.loop.exit", "bwd"): 1.25,
                       ("ds.loop.pass0", "fwd"): 40.0, ("ds.head.loss", "fwd"): 9.0},
             "ms": {("fwd", "head"): 100.0, ("bwd", "head"): 3.0, ("recompute", "mixer"): 30.0,
                    ("recompute", "ffn"): 50.0, ("fwd", "ffn"): 70.0},
             "custom_ms": {}}
    monkeypatch.setattr(scope_time, "load", lambda run: table)
    assert read("loop.exit_ms_per_step", {}) == 1.75
    assert read("loop.head_ms_per_step", {}) == 103.0
    assert read("loop.recompute_ms_per_step", {}) == 80.0
    bare = {"ds_ms": {("ds.rope", "fwd"): 1.0}, "ms": {("fwd", "ffn"): 1.0}, "custom_ms": {}}
    monkeypatch.setattr(scope_time, "load", lambda run: bare)
    for name in ("loop.exit_ms_per_step", "loop.head_ms_per_step",
                 "loop.recompute_ms_per_step"):
        assert read(name, {}) is None, name


def made_up_readings(**over) -> dict:
    return dict({"loss_err": 1e-5, "loss_after_err": 2e-5, "descends": True,
                 "logit_median": [6e-3, 9e-3], "logit_p90": [7e-3, 1.1e-2],
                 "grad_worst": ("['q_proj']", 2e-2),
                 "grad_gate": {"kernel": 1e-2, "bias": 2e-3},
                 "update_worst": ("['embedding']", 7e-6),
                 "ce_pass": [[10.9, 10.9, 10.9, 10.9]] * 2,
                 "exit_mass": [[.5, .25, .125, .125]] * 2,
                 "exit_entropy": [1.21, 1.21]}, **over)


@pytest.mark.parametrize("fails,over", [
    (set(), {}),
    (set(), {"descends": False}),       # the reference itself may rise: decides nothing
    ({"loss"}, {"loss_after_err": 8e-3}),
    ({"logits"}, {"logit_median": [6e-3, 9e-2]}), ({"logits"}, {"logit_median": [9e-2, 6e-3]}),
    ({"logits"}, {"logit_p90": [float("nan"), 1e-2]}),
    ({"exits"}, {"ce_pass": [[10.9, 10.9, 10.9, 10.9], [10.9, 10.9, 10.897, 10.9]]}),
    ({"exits"}, {"ce_pass": [[10.9] * 4, [10.9, 10.9, 10.9, float("nan")]]}),
    ({"exits"}, {"exit_mass": [[.5, .25, .125, .125], [.5, .25, .25, 0.]]}),
    ({"exits"}, {"exit_mass": [[.5, .25, .25], [.5, .25, .25]], "ce_pass": [[10.9] * 3] * 2}),
    ({"exits"}, {"exit_entropy": [1.21, 1.25]}),
    ({"grads"}, {"grad_worst": ("['down_proj']", float("inf"))}),
    ({"grads"}, {"grad_gate": {"kernel": 1e-2, "bias": float("inf")}}),
    ({"grads"}, {"grad_gate": {"kernel": 1e-2}}),
    ({"grads"}, {"grad_gate": {"kernel": 1.2e-1, "bias": 2e-3}}),   # a leaf as any other
    (set(), {"grad_gate": {"kernel": 1e-2, "bias": 1.2e-1}}),   # the bias has its own limit
    ({"grads"}, {"grad_gate": {"kernel": 1e-2, "bias": 2e-1}}),
    ({"loss"}, {"loss_err": 3e-4}),     # three passes' reading: the harness's 1e-3 passes it
    ({"grads"}, {"update_worst": ("['weight']", 1.0)})])
def test_verdicts_by_hand(fails, over):
    from benchmark.runners import train_steps_ouro as runner
    ok = runner.verdicts(made_up_readings(**over), PASSES)
    assert {k for k, good in ok.items() if not good} == fails
    wide = runner.verdicts(made_up_readings(logit_median=[6e-3, 9e-2]), PASSES,
                           slack=runner.REHEARSAL_SLACK)
    assert all(wide.values())       # a rehearsal's slack widens the distances


def test_the_gates_two_readings_by_hand():
    """The kernel by relative L2; the bias over the kernel's gradient a lane,
    so a reference whose bias gradient is (near) zero still reads a finite,
    steady number; a gate that lacks a leaf reads nothing and fails."""
    from benchmark.runners import train_steps_ouro as runner
    want = {"kernel": np.asarray([[3.0], [4.0], [0.0], [0.0]]), "bias": np.asarray([0.0])}
    got = {"kernel": np.asarray([[3.0], [4.5], [0.0], [0.0]]), "bias": np.asarray([0.25])}
    errs = runner.gate_errors(got, want)        # |w| 5 over 4 lanes: 2.5 a lane
    assert errs == pytest.approx({"kernel": 0.1, "bias": 0.1})
    assert runner.gate_errors({"kernel": got["kernel"]}, want) == {}
    dead = runner.gate_errors(got, {"kernel": np.zeros((4, 1)), "bias": np.zeros(1)})
    assert not runner.verdicts(made_up_readings(grad_gate=dead), PASSES)["grads"]


def chip_readings(name: str = "ouro_calibration.jsonl") -> list:
    with open(os.path.join(ROOT, "benchmark", "readings", name)) as f:
        return [json.loads(ln) for ln in f if ln.startswith("{")]


@pytest.mark.parametrize("against", ["sound", "bf16", "fp8", "norm_last_only", "three_passes",
                                     "pre_norms_only", "no_survival", "beta_zero",
                                     "gate_no_bias"])
def test_the_limits_stand_between_what_the_chip_read(against):
    """The chip's readings of the timed step at 1 x 16,384 tokens against the
    reference sound and made wrong (``calibrate_ouro.py``, kept in
    ``benchmark/readings/``), through the runner's limits as they are now: each
    wrong way gives ``correct`` false, fp8 (the precision below the
    configuration's) by one limit at least and not by each; the sound program
    passes on every seed read. A limit moved past either reading fails here. A
    reference at bf16 operands is the configuration's own precision: required
    of nothing, and it reads as the sound one does."""
    from benchmark import calibrate_ouro
    from benchmark.runners import train_steps_ouro as runner
    from benchmark.reference import ouro as reference
    everything = chip_readings()
    assert set(reference.WRONG) | {"sound", reference.OWN_PRECISION} == {
        r["against"] for r in everything}
    rows = [r for r in everything if r["against"] == against]
    assert len({r["seed"] for r in rows}) >= (2 if against in ("sound", "fp8") else 1)
    for row in rows:
        ok = calibrate_ouro.verdicts_of(row)
        if against != reference.OWN_PRECISION:
            assert all(ok.values()) == (against == "sound"), (row["seed"], ok)
        assert ok == row["verdicts"] and row["lr"] == runner.LR
    for row in rows if against == "fp8" else []:
        ok = calibrate_ouro.verdicts_of(row)
        assert not all(ok.values()) and any(ok.values())


RUNS = chip_readings("ouro_cell_runs.jsonl")


@pytest.mark.parametrize("row", RUNS, ids=[r["run"] for r in RUNS])
def test_every_kept_run_of_the_cell_passes_the_limits_as_they_are(row):
    """The sound rows of the cell's own runs on the chip (``calibrate_ouro.py
    --keep`` off each run's ``notes``): the seeds that the limits' lower sides
    were set from beside the calibration's two. Each passes every limit as it
    is now, with room: a limit pulled down to a reading fails here."""
    from benchmark import calibrate_ouro
    from benchmark.runners import train_steps_ouro as runner
    assert row["against"] == "sound" and row["lr"] == runner.LR
    ok = calibrate_ouro.verdicts_of(row)
    assert all(ok.values()) and ok == row["verdicts"] and row["correct"], (row["seed"], ok)
    tight = runner.verdicts(row, PASSES, slack=0.5)
    assert all(tight.values()), (row["seed"], tight)    # twice of room on every seed read


def test_no_limit_stands_far_over_the_largest_of_every_seed_kept():
    """What the runner's comments call "the largest" is in the tree: over the
    calibration's sound rows and the cell's kept runs, a score of seeds or more;
    and no limit is an order of magnitude over it (PR 58's review: the first
    loss's stood 45 times over, above two wrong models)."""
    from benchmark.runners import train_steps_ouro as runner
    rows = [r for r in chip_readings() if r["against"] == "sound"] + RUNS
    assert len({r["seed"] for r in rows}) >= 20
    largest = {k: max(f(r) for r in rows) for k, f in {
        "loss": lambda r: r["loss_err"], "loss_after": lambda r: r["loss_after_err"],
        "median": lambda r: max(r["logit_median"]), "p90": lambda r: max(r["logit_p90"]),
        "grad": lambda r: r["grad_worst"][1], "kernel": lambda r: r["grad_gate"]["kernel"],
        "bias": lambda r: r["grad_gate"]["bias"]}.items()}
    for name, limit in {"loss": runner.LOSS_RTOL, "loss_after": runner.LOSS_AFTER_RTOL,
                        "median": runner.LOGIT_MEDIAN_RTOL, "p90": runner.LOGIT_P90_RTOL,
                        "grad": runner.GRAD_RTOL, "kernel": runner.GRAD_RTOL,
                        "bias": runner.GRAD_GATE_BIAS_LANES}.items():
        assert limit < 6 * largest[name], (name, largest[name], limit)


@pytest.mark.parametrize("against", ["three_passes", "pre_norms_only", "beta_zero",
                                     "no_survival"])
def test_the_loss_at_initialisation_alone_tells_these_wrong_models(against):
    """Each of the two losses has a limit under what these read (PR 58's review:
    at the harness's 1e-3 the first let three passes and the pre-norms alone by)."""
    from benchmark.runners import train_steps_ouro as runner
    rows = [r for r in chip_readings() if r["against"] == against]
    assert rows
    for row in rows:
        assert row["loss_err"] > 3 * runner.LOSS_RTOL, (row["seed"], row["loss_err"])
        assert row["loss_after_err"] > 3 * runner.LOSS_AFTER_RTOL, row["seed"]


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_of_the_cell_prints_the_contracts_last_line(trace):
    from deepspeed_tpu.utils.hostdev import force_host_devices_env
    env = force_host_devices_env(4 if trace else 1, extra={"PYTHONPATH": ROOT})
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 58), "--seconds", "2", "--trace", str(trace), "--rehearse"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    said = next(ln for ln in lines if ln.startswith("training:"))
    # the cell's one chip, however many the host has
    assert "'data': 1," in said and "depth 2 run 4 times (one scanned body" in said
    assert "batch 1 x 96" in said and "beta 0.05" in said
    check = next(ln for ln in lines if ln.startswith("correctness:"))
    assert "FAILED" not in check and "logits of pass 1 | pass 4" in check
    assert "mean exit mass" in check and "the exit gate's kernel" in check
    assert "the bias in the kernel's lanes" in check
    notes = json.loads(next(ln for ln in lines if ln.startswith("notes: "))[7:])
    assert all(notes["verdicts"].values()) and notes["scan_layers"] is True
    assert set(notes["grad_gate"]) == {"kernel", "bias"} and notes["seed"] == 2**31 + 58
    assert notes["step_programs"] == 1 and notes["n_params"] == ouro_cost.param_count(
        {**config(), **config()["rehearse"]})
    mass = notes["loop_first_batch"]["exit_mass"][0]
    assert len(mass) == 4 and abs(sum(mass) - 1) < 1e-5 and mass[0] > mass[-1] > 0.05
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in cell_metrics(load_manifest(), CELL, group)}
    assert set(line["metrics"]) <= set(declared)
    for name, got in line["metrics"].items():
        assert got["unit"] == declared[name]["unit"] and np.isfinite(got["value"])
    if trace:
        # no kernel events and no utilization on a CPU; the family is read
        for absent in ("loop.flash_ms_per_step", "step.mfu_pct", "kernel.flash_fwd_roofline",
                       "kernel.flash_bwd_roofline"):
            assert absent not in line["metrics"]
        assert {"setup.compile_s", "device.idle_pct.train",
                "loop.expected_exit_pass"} <= set(line["metrics"])
        assert 1.5 < line["metrics"]["loop.expected_exit_pass"]["value"] < 2.3
    else:
        assert set(line["metrics"]) == {"setup_s", "train_tok_s"}
        assert all(v["value"] > 0 for v in line["metrics"].values())
