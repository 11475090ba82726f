"""The benchmark's own tests: all on the CPU, none slow. They check the
yardstick (arithmetic, trace reduction, FLOP functions, the plain reference)
and rehearse each runner end to end at a tiny size; no number here is a
measurement."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flops, reduce_trace, stats, traffic  # noqa: E402
from benchmark.run import (cell_metrics, load_json, load_manifest,  # noqa: E402
                           load_module)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_names_only_files_metrics_runners_and_readers_that_exist():
    admitted = manifest()
    assert set(admitted) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    for metric in admitted["end_to_end"]:
        assert 0 < metric["bound"] <= 0.1
    for metric in admitted["end_to_end"] + admitted["per_layer"]:
        if "roofline" in metric["name"]:
            assert metric["name"].endswith("_roofline") and metric["unit"] == "%"
    # the cells queued beside it (built, not admitted) are held to the same
    m = load_manifest()
    assert {w["name"] for w in m["workloads"]} >= {
        w["name"] for w in admitted["workloads"]} | {"serve-chat-closed32"}
    configs = {c["name"]: c for c in m["configs"]}
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e
    for c in m["configs"]:
        assert NAME.match(c["name"]) and c["file"].startswith("benchmark/configs/")
        body = load_json("configs", c["name"] + ".json")
        assert body["source"] == c["source"] and body["reduced"] == c["reduced"]
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in c["reduced"])
    for metric in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(metric["name"]) and UNIT.match(metric["unit"]), metric
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
    for metric in m["end_to_end"]:
        assert metric["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in m["workloads"]}
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(1, len(cells) // 4)
    for w in m["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and len(w["why"]) <= 200
        cell = load_json("workloads", w["name"] + ".json")
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert hasattr(load_module("runners", cell["runner"]), "run")
        reported = [x["name"] for x in cell_metrics(m, w["name"], "end_to_end")]
        assert reported == cell["end_to_end"]
        assert "setup_s" in reported and len(reported) >= 2
        layers = cell_metrics(m, w["name"], "per_layer")
        assert layers
        for metric in layers:
            assert callable(load_module("layers", metric["name"]).read)
            assert metric["moves"] in reported, (w["name"], metric)
            assert set(metric.get("workloads", cells)) <= cells


def test_percentiles_and_window_accounting_on_hand_made_samples():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(range(1, 101), 95) == pytest.approx(95.05)
    with pytest.raises(ValueError):
        stats.percentile([], 50)

    def rec(send, first, last, end, n, ok=True):
        return {"t_send": send, "t_first": first, "t_last": last,
                "t_end": end, "n_tokens": n, "ok": ok}
    records = [rec(9.0, 9.5, 10.5, 10.6, 11),     # sent before the window
               rec(10.0, 10.1, 11.1, 11.1, 11),   # ttft 100 ms, tpot 100 ms
               rec(11.0, 11.3, 12.3, 12.4, 6),    # ttft 300 ms, tpot 200 ms
               rec(12.0, None, None, 13.0, 0, ok=False),
               rec(19.0, 19.2, 20.5, 20.6, 9)]    # ends after the window
    got = stats.serving_metrics(records, 10.0, 20.0)
    assert (got["attempted"], got["failed"]) == (3, 1)
    # the request sent before the window completed in it: its tokens count
    assert got["out_tok_s"] == pytest.approx((11 + 11 + 6) / 10.0)
    assert got["ttft_p50_ms"] == pytest.approx(300.0)
    assert got["ttft_p95_ms"] == pytest.approx(300 + 0.9 * (10_000 - 300))
    assert got["tpot_p50_ms"] == pytest.approx(200.0)


def test_every_seed_sends_the_same_sizes_in_another_order():
    tr = load_json("workloads", "serve-chat-closed32.json")["traffic"]
    a, b = traffic.request_sizes(tr, 1), traffic.request_sizes(tr, 2**31 + 11)
    assert a != b and sorted(a) == sorted(b) and len(a) == tr["pool"]
    prompts, outs = [p for p, _ in a], [o for _, o in a]
    assert 128 <= min(prompts) and max(prompts) <= 2048
    assert 680 < np.mean(prompts) < 700                # (hi - lo) / ln(hi / lo)
    assert 64 <= min(outs) and max(outs) <= 192 and 127 < np.mean(outs) < 129
    fixed = traffic.request_sizes(dict(tr, prompt_len={
        "dist": "choice", "values": [256, 1024]}), 3)
    assert sorted(p for p, _ in fixed) == [256] * 256 + [1024] * 256
    assert (traffic.prompt_tokens(3, 5, 40, 32000)
            == traffic.prompt_tokens(3, 5, 40, 32000)).all()


def test_trace_reduction_on_a_hand_built_trace():
    # two overlapping ops (one of them a collective), one gap, one more op
    trace = {"/device:TPU:0": {
        "XLA Ops": [("%fusion.1 = bf16[8] fusion(...)", 0, 100),
                    ("%all-gather.2 = bf16[8] all-gather(...)", 50, 100),
                    ("%while.9 = (s32[]) while(...)", 300, 100),
                    ("%kern.3 = bf16[2,1,4,8] custom-call(...)", 300, 100)],
        "XLA Modules": [("jit_step(7)", 0, 400), ("jit_other(8)", 120, 150)]},
        "/host:CPU": {"thread": [("harvest", 140, 170), ("far", 900, 10)]}}
    red = reduce_trace.reduce(trace, 1)
    assert red["window_s"] == pytest.approx(400e-9)
    assert red["busy_s"] == pytest.approx(250e-9)        # [0,150] + [300,400]
    assert red["collective_s"] == pytest.approx(100e-9)
    assert red["exposed_collective_s"] == pytest.approx(50e-9)   # [100,150]
    # a module that holds a %while is marked; the holder itself is no op
    assert red["modules"]["jit_step[while]"]["busy_s"] == pytest.approx(250e-9)
    assert red["modules"]["jit_other"]["busy_s"] == pytest.approx(30e-9)
    assert set(red["op_seconds"]) == {"%fusion.1", "%all-gather.2", "%kern.3"}
    assert red["kernels"]["%kern.3"]["count"] == 1
    assert red["breakdown"]["idle_gaps"] == [["harvest", pytest.approx(150e-9)]]
    assert 1 - red["busy_s"] / red["window_s"] == pytest.approx(0.375)
    assert reduce_trace.subtract([(0, 10)], [(2, 3), (5, 20)]) == [(0, 2), (3, 5)]
    with pytest.raises(ValueError):
        reduce_trace.reduce({"/host:CPU": {}}, 1)


def test_flop_and_byte_functions_on_a_hand_worked_shape():
    cfg = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
           "num_attention_heads": 2, "num_key_value_heads": 1,
           "vocab_size": 10, "sliding_window": 3, "tie_word_embeddings": False}
    # head_dim 4; q 8x8, k and v 8x4, o 8x8 = 192; mlp 3*8*16 = 384
    assert flops.param_count(cfg) == 2 * (192 + 384 + 16) + 80 + 80 + 8
    # seq 4, window 3: queries see 1, 2, 3, 3 keys -> mean 2.25
    assert flops.mean_keys_per_query(4, 3) == pytest.approx(2.25)
    fwd = 2 * (2 * (192 + 384) + 4 * 2 * 4 * 2.25) + 2 * 8 * 10
    assert flops.forward_flops_per_token(cfg, 4) == pytest.approx(fwd)
    assert flops.train_flops_per_token(cfg, 4) == pytest.approx(3 * fwd)
    # one decode token after 5 cached, page 2: sees min(6, 3) = 3 keys;
    # positions 3..5 live on pages 1 and 2 -> 2 pages of 2 tokens
    cost = flops.paged_attention_cost(cfg, [(1, 5)], page_size=2)
    assert cost["flops"] == 4 * 2 * 4 * 3
    assert cost["bytes"] == 2 * 1 * 4 * 2 * (2 * 2) + 2 * 1 * 2 * 4 * 2
    peaks = {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1e9}
    assert flops.roofline_seconds(cost, peaks) == (96.0, "compute")


def test_reference_agrees_with_the_flax_model_through_the_window():
    import jax.numpy as jnp
    from benchmark.reference import mistral
    from deepspeed_tpu.models import LlamaConfig, init_llama
    cfg = LlamaConfig.tiny(num_key_value_heads=2, sliding_window=8,
                           dtype=jnp.float32)
    model, params = init_llama(cfg, seed=3, dtype=jnp.float32)
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 24)))
    plain = {k: getattr(cfg, k) for k in (
        "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "rms_norm_eps",
        "rope_theta", "sliding_window", "vocab_size")}
    want = np.asarray(model.apply({"params": params}, ids))
    # float32 on both sides: only the order of additions differs
    np.testing.assert_allclose(mistral.logits(params, ids, plain), want,
                               atol=2e-5)
    assert float(mistral.cross_entropy(params, ids, plain)) == pytest.approx(
        float(model.apply({"params": params}, ids, labels=ids)), rel=1e-5)
    # the 24-token sequence is longer than the window: dropping it shows
    no_window = mistral.logits(params, ids, dict(plain, sliding_window=None))
    assert np.abs(np.asarray(no_window) - want).max() > 0.1


def test_layer_readers_on_a_hand_made_run():
    run = {"counters": {"open": {"fused_dispatches": 2, "fused_k_sum": 20},
                        "close": {"fused_dispatches": 6, "fused_k_sum": 68},
                        "trace_open": {"fused_k_sum": 20},
                        "trace_close": {"fused_k_sum": 60},
                        "trace_host": (100.0, 108.0)},
           "spans": {1: [{"name": "prefill_overlap", "t0": 0.02, "t1": 0.1}],
                     2: [{"name": "fused_wave", "t0": 0.0, "t1": 1.0},
                         {"name": "prefill", "t0": 0.04, "t1": 0.2}]},
           "spans_all": {1: [{"name": "prefill", "t0_monotonic": 101.0,
                              "t1_monotonic": 101.5, "args": {"tokens": 500}}],
                         2: [{"name": "prefill", "t0_monotonic": 101.0,
                              "t1_monotonic": 101.5, "args": {"tokens": 500}},
                             {"name": "prefill", "t0_monotonic": 99.0,
                              "t1_monotonic": 100.5, "args": {"tokens": 64}}]},
           "trace": {"busy_s": 6.0, "window_s": 8.0, "exposed_collective_s": 0.3,
                     "modules": {"jit__unknown[while]": {"busy_s": 4.0},
                                 "jit__unknown": {"busy_s": 1.5}},
                     "kernels": {"%paged_attention.5": {
                         "count": 100, "seconds": 0.05, "hlo":
                         "%paged_attention.5 = bf16[32,1,32,128]{3,2,1,0} custom-call("},
                         "%paged_attention.7": {
                         "count": 9, "seconds": 9.0, "hlo":
                         "%paged_attention.7 = bf16[2,512,32,128]{3,2,1,0} custom-call("}}},
           "records": [{"ok": True, "n_prompt": 512, "n_tokens": 100,
                        "t_first": 90.0, "t_last": 190.0}] * 32,
           "page_size": 64,
           "config": {"hidden_size": 4096, "num_attention_heads": 32,
                      "num_key_value_heads": 8, "sliding_window": 4096},
           "kv_blocks": 720, "free_blocks_min": 180, "trace_steps": 3,
           "compiles": {"in_window": 0},
           "setup": {"compile_s": 12.5, "programs": 40, "cache_misses": 0},
           "end_to_end": {"train_tok_s": 20000.0}, "chips": 4,
           "train_flops_per_token": 1e10,
           "device": {"kind": "TPU v5 lite"}}
    want = {"sched.queue_wait_p50_ms": 30.0, "sched.mean_fused_k": 12.0,
            "sched.compiles_in_window": 0.0, "kv.pool_used_peak_pct": 75.0,
            "step.decode_dev_ms": 100.0, "step.prefill_dev_ms_per_ktok": 3000.0,
            "device.idle_pct.serve": 25.0, "device.idle_pct.train": 25.0,
            "coll.exposed_ms_per_step": 100.0, "setup.compile_s": 12.5,
            "setup.programs": 40.0, "setup.cache_misses": 0.0,
            "step.mfu_pct": 100 * 20000.0 * 1e10 / (4 * 197e12),
            # 32 rows at 526-534 cached tokens: 9 pages of 64 each, K and V
            # of 8 heads x 128 in bf16, plus the queries and the output
            "kernel.paged_attn_roofline": 100 * 100 * 32 * (
                2 * 8 * 128 * 2 * 9 * 64 + 2 * 32 * 128 * 2) / 819e9 / 0.05}
    for name, value in want.items():
        assert load_module("layers", name).read(run) == pytest.approx(value), name
    # a later PR adds readers and entries; it takes none of these away
    assert {m["name"] for m in load_manifest()["per_layer"]} >= set(want)
    assert load_module("layers", "step.decode_dev_ms").read({}) is None


def _run_cell(args, devices: int):
    from deepspeed_tpu.utils.hostdev import force_host_devices_env
    env = force_host_devices_env(devices, extra={"PYTHONPATH": ROOT})
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py")]
                          + args, env=env, cwd=ROOT, capture_output=True,
                          text=True, timeout=600)


def test_run_refuses_a_machine_with_no_tpu():
    proc = _run_cell(["--workload", "serve-chat-closed32", "--seed", "1",
                      "--seconds", "1", "--trace", "0"], 1)
    assert proc.returncode != 0 and "no TPU" in proc.stderr
    assert not proc.stdout.strip().endswith("}")


@pytest.mark.parametrize("workload,devices,trace", [
    ("serve-chat-closed32", 1, 0), ("serve-chat-closed32", 1, 1),
    ("train-zero3-seq4k", 4, 0), ("train-zero3-seq4k", 4, 1)])
def test_runner_rehearsal_prints_the_contracts_last_line(workload, devices, trace):
    proc = _run_cell(["--workload", workload, "--seed", str(2**31 + 11),
                      "--seconds", "3", "--trace", str(trace), "--rehearse"],
                     devices)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True and line["attempted"] > 0
    assert line["failed"] == 0
    assert line["device"]["platform"] == "cpu"       # a rehearsal says so
    assert line["device"]["count"] == devices
    group = "per_layer" if trace else "end_to_end"
    declared = {m["name"]: m
                for m in cell_metrics(load_manifest(), workload, group)}
    assert set(line["metrics"]) <= set(declared)
    for name, got in line["metrics"].items():
        assert got["unit"] == declared[name]["unit"]
        assert np.isfinite(got["value"])
    if trace:
        assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
        assert len(line["breakdown"]["device_ops"]) <= 10
        assert "setup.compile_s" in line["metrics"]
    else:
        assert set(line["metrics"]) == set(declared)
        assert all(v["value"] > 0 for v in line["metrics"].values())
