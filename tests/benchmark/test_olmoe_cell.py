"""The OLMoE cell's own tests: its FLOP count by hand, its three readers on
hand-built runs, its manifest entries, and a rehearsal of the runner end to
end. All on the CPU; no number here is a measurement."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import moe_cost  # noqa: E402
from benchmark.run import cell_metrics, load_json, load_manifest, load_module  # noqa: E402

CELL, CONFIG = "train-olmoe-1chip-seq4k", "olmoe-1b-7b-0125-train1"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = {"kernel.moe_gmm_roofline": "kernel", "moe.gmm_ms_per_step": "MoE block",
       "moe.load_max_over_mean": "router"}
# device events as a traced run on the v5e names them (my chip run, PR 26)
FWD = ("%ragged-dot-none.7 = f32[131072,1024]{1,0:T(8,128)} custom-call("
       "s32[1]{0:T(128)} %get-tuple-element.16, s32[65]{0:T(128)S(1)} %copy-done.19")
DOWN = FWD.replace("none.7", "none.6").replace("[131072,1024]", "[131072,2048]")
DW = ("%ragged-dot-none.1 = f32[64,2048,1024]{2,1,0:T(8,128)} custom-call("
      "s32[1]{0:T(128)} %get-tuple-element.8, s32[65]{0:T(128)S(1)} %copy-done.24")
META = ("%ragged-dot-metadata.1 = (s32[65]{0:T(128)}, s32[319]{0:T(512)}, "
        "s32[319]{0:T(512)}, s32[1]{0:T(128)}) custom-call(s32[64]{0:T(128)S(1)}")


def config() -> dict:
    return load_json("configs", CONFIG + ".json")


def read(name, run):
    return load_module("layers", name).read(run)


def test_the_configuration_is_the_published_one_but_for_depth():
    cfg = config()
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog of public architectures is not on this machine")
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    published = next(r for r in rows if r["name"] == "OLMoE-1B-7B-0125-Instruct")
    assert cfg["source"] == published["source_url"]
    for key, value in published["config"].items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers"] and cfg["num_hidden_layers"] == 1
    assert cfg["published"] == {"num_hidden_layers": published["layers"]}
    assert set(cfg["assumed"]) >= {"head_dim", "qk_norm", "router_aux_loss_coef",
                                   "tokens_per_step"}
    assert cfg["vocab_size"] % cfg["ce_chunk_size"] == 0    # no padded head
    assert cfg["ds_config"] == {"zero_optimization": {"stage": 0}}
    assert "one v5e chip" in cfg["deployment"]


def test_manifest_entries_of_the_cell():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        admitted = json.load(f)
    cells = {w["name"]: w for w in admitted["workloads"]}
    assert list(cells) == ["train-zero3-seq4k", CELL]          # added at the end
    assert cells[CELL]["chips"] == 1 and cells[CELL]["config"] == CONFIG
    assert sum(w["chips"] == 4 for w in cells.values()) == 1
    entry = next(c for c in admitted["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    cell = load_json("workloads", CELL + ".json")
    assert cell["traffic"] == {"global_batch": 4, "seq_len": 4096,
                               "warmup_steps": 2, "trace_steps": 4}
    m = load_manifest()
    assert [x["name"] for x in cell_metrics(m, CELL, "end_to_end")] \
        == ["setup_s", "train_tok_s"] == cell["end_to_end"]
    layers = {x["name"]: x for x in cell_metrics(m, CELL, "per_layer")}
    assert set(layers) >= set(NEW) | {"step.mfu_pct", "device.idle_pct.train",
                                      "setup.compile_s", "setup.programs",
                                      "setup.cache_misses"}
    assert "coll.exposed_ms_per_step" not in layers            # one chip
    for name, layer in NEW.items():
        assert layers[name]["workloads"] == [CELL]
        assert layers[name]["layer"] == layer and layers[name]["moves"] == "train_tok_s"
    assert [x["name"] for x in admitted["per_layer"]][-3:] == list(NEW)


def test_moe_cost_arithmetic_by_hand():
    cfg = config()
    # attention 4 x 2048^2 + the q/k norms, router 2048 x 64, 64 experts of
    # 3 x 2048 x 1024, two norms; embedding and head 50304 x 2048 each
    layer = 4 * 2048 * 2048 + 2 * 2048 + 2048 * 64 + 64 * 3 * 2048 * 1024 + 2 * 2048
    assert moe_cost.param_count(cfg) == layer + 2 * 50304 * 2048 + 2048 == 625_616_896
    assert moe_cost.param_count(dict(cfg, num_hidden_layers=16)) \
        == 16 * layer + 2 * 50304 * 2048 + 2048            # 6.92B: "1B-7B"
    # forward MFLOP a token at 4,096 tokens: the ISSUE's 50 + 100.7 + 206
    attn = 2 * 4 * 2048 * 2048 + 4 * 16 * 128 * 2048.5
    experts = 2 * 8 * 3 * 2048 * 1024
    head = 2 * 2048 * 50304
    assert attn == pytest.approx(50.3e6, rel=2e-3)
    assert experts == pytest.approx(100.7e6, rel=1e-3) and head == pytest.approx(206e6, rel=1e-3)
    fwd = attn + 2 * 2048 * 64 + experts + head
    assert moe_cost.forward_flops_per_token(cfg, 4096) == fwd
    assert moe_cost.train_flops_per_token(cfg, 4096) == 3 * fwd
    # a token pays for 8 experts of 64, not for all of them
    dense = dict(cfg, num_experts_per_tok=64)
    assert moe_cost.forward_flops_per_token(dense, 4096) - fwd == 2 * 56 * 3 * 2048 * 1024
    # nine grouped matmuls a layer and step over 131,072 rows: 4.95 TFLOP
    one = moe_cost.gmm_flops(16384 * 8, 2048, 1024)
    assert one == 2 * 131072 * 2048 * 1024 and 9 * one == pytest.approx(4.95e12, rel=1e-3)
    for hlo in (FWD, DOWN, DW):
        assert moe_cost.call_flops(hlo, cfg, 131072) == one
    # rows from the event itself; the weights' gradient shows none
    assert moe_cost.call_flops(FWD.replace("[131072,", "[65536,"), cfg, 131072) == one / 2
    assert moe_cost.call_flops(DW, cfg, 65536) == one / 2
    for other in (META, FWD.replace("[131072,1024]", "[131072,512]"),
                  DW.replace("[64,", "[32,"), "%ragged-dot-none.9 = token[] custom-call("):
        assert moe_cost.call_flops(other, cfg, 131072) is None


def run_with(kernels, **over) -> dict:
    return dict({"config": config(), "tokens_per_step": 16384, "trace_steps": 4,
                 "device": {"kind": "TPU v5 lite"},
                 "trace": {"kernels": kernels}}, **over)


def test_readers_report_nothing_when_nothing_matched():
    for run in ({}, {"trace": {"kernels": {}}}, run_with({}),
                run_with({"%flash_fwd.1": {"count": 4, "seconds": 0.02, "hlo":
                                           "%flash_fwd.1 = bf16[64,1,4096,128] custom-call("},
                          "%ragged-dot-metadata.1": {"count": 4, "seconds": 1e-5, "hlo": META}})):
        for name in NEW:
            assert read(name, run) is None, (name, run)
    # the parent's program publishes no gauge: no samples, no value
    assert read("moe.load_max_over_mean", {"moe_load_samples": []}) is None
    assert read("moe.load_max_over_mean", {"moe_load_samples": [1.5, 2.0, 2.5, 2.0]}) == 2.0


def test_gmm_readers_on_a_hand_built_trace():
    one = 2 * 131072 * 2048 * 1024          # 5.5e11: 2.79 ms at 197 TFLOP/s
    kernels = {
        "%ragged-dot-none.7": {"count": 4, "seconds": 4 * 5.58e-3, "hlo": FWD},
        "%ragged-dot-none.6": {"count": 4, "seconds": 4 * 5.58e-3, "hlo": DOWN},
        "%ragged-dot-none.1": {"count": 4, "seconds": 4 * 8.37e-3, "hlo": DW},
        # not matched, so neither their time nor any FLOPs are counted
        "%ragged-dot-metadata.1": {"count": 4, "seconds": 1.0, "hlo": META},
        "%ragged-dot-none.9": {"count": 4, "seconds": 1.0, "hlo":
                               FWD.replace("[131072,1024]", "[131072,96]")},
        "%flash_fwd.1": {"count": 4, "seconds": 1.0, "hlo":
                         "%flash_fwd.1 = bf16[64,1,4096,128] custom-call("}}
    run = run_with(kernels)
    gmm = moe_cost.traced_gmm(run)
    assert gmm["calls"] == 12 and gmm["flops"] == 12 * one
    assert gmm["seconds"] == pytest.approx(4 * (2 * 5.58e-3 + 8.37e-3))
    assert read("moe.gmm_ms_per_step", run) == pytest.approx(2 * 5.58 + 8.37)
    least = 3 * one / 197e12
    assert read("kernel.moe_gmm_roofline", run) == pytest.approx(
        100 * least / (2 * 5.58e-3 + 8.37e-3))
    assert 42 < read("kernel.moe_gmm_roofline", run) < 43
    # a kernel at the peak reads 100, and matched calls alone can read no more:
    # time and FLOPs come from the same events
    at_peak = run_with({"%moe_gmm.2": {"count": 8, "seconds": 8 * one / 197e12, "hlo":
                                       FWD.replace("%ragged-dot-none.7", "%moe_gmm.2")}})
    assert read("kernel.moe_gmm_roofline", at_peak) == pytest.approx(100.0)
    with pytest.raises(KeyError):
        read("kernel.moe_gmm_roofline", dict(run, device={"kind": "TPU v9"}))


@pytest.mark.parametrize("trace,devices", [(0, 1), (1, 1), (0, 4)])
def test_rehearsal_of_the_cell_prints_the_contracts_last_line(trace, devices):
    from deepspeed_tpu.utils.hostdev import force_host_devices_env
    env = force_host_devices_env(devices, extra={"PYTHONPATH": ROOT})
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 26), "--seconds", "3", "--trace", str(trace), "--rehearse"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    # the cell's one chip, however many the host has
    assert "'data': 1," in next(ln for ln in lines if ln.startswith("training:"))
    check = next(ln for ln in lines if ln.startswith("correctness:"))
    assert "FAILED" not in check and "expert counts sum 1024 of 1024" in check
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m for m in cell_metrics(load_manifest(), CELL, group)}
    assert set(line["metrics"]) <= set(declared)
    for name, got in line["metrics"].items():
        assert got["unit"] == declared[name]["unit"] and np.isfinite(got["value"])
    if trace:
        # the gauge the program publishes; no grouped-matmul kernel on a CPU
        assert line["metrics"]["moe.load_max_over_mean"]["value"] >= 1.0
        assert "kernel.moe_gmm_roofline" not in line["metrics"]
        assert "step.mfu_pct" not in line["metrics"]
        assert {"setup.compile_s", "device.idle_pct.train"} <= set(line["metrics"])
    else:
        assert set(line["metrics"]) == {"setup_s", "train_tok_s"}
        assert all(v["value"] > 0 for v in line["metrics"].values())
