"""The readers of the program's own spans and of the named flash kernels, on
hand-built runs: all on the CPU, none a measurement."""

import glob
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import flash_cost, host_spans  # noqa: E402
from benchmark.run import cell_metrics, load_json, load_manifest, load_module  # noqa: E402

CELL = "train-zero3-seq4k"
NEW = ("kernel.flash_fwd_roofline", "kernel.flash_bwd_roofline",
       "host.work_ms_per_step", "host.idle_unnamed_pct.train",
       "setup.engine_init_s", "setup.place_params_s")
# one chip's sequence in the cell: 1 x 4,096 tokens, 32 query / 8 KV heads of
# 128, window 4,096; the kernels' layouts are [rows * kv, group, seq, d] and
# [rows * kv, seq, d]
FWD = ("%flash_fwd.3 = (bf16[8,4,4096,128]{3,2,1,0:T(8,128)(2,1)S(1)}, "
       "f32[8,4,4096,1]{3,2,1,0:T(8,128)}) custom-call(%bitcast.25, %copy")
DQ = ("%flash_dq.1 = bf16[8,4,4096,128]{3,2,1,0:T(8,128)(2,1)} "
      "custom-call(%bitcast.27, %copy_bitcast_fusion.1")
DKDV = ("%flash_dkdv.1 = (bf16[8,4096,128]{2,1,0:T(8,128)(2,1)}, "
        "bf16[8,4096,128]{2,1,0:T(8,128)(2,1)}) custom-call(%bitcast.26")


def read(name, run):
    return load_module("layers", name).read(run)


def test_the_new_metrics_are_entries_of_the_admitted_cell_only():
    by_name = {m["name"]: m for m in load_manifest()["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL]
    reported = {m["name"] for m in cell_metrics(load_manifest(), CELL, "per_layer")}
    assert set(NEW) <= reported
    serve = {m["name"] for m in cell_metrics(load_manifest(),
                                             "serve-chat-closed32", "per_layer")}
    assert not set(NEW) & serve


def test_flash_least_work_by_hand_on_the_cells_shape():
    config = load_json("configs", "mistral-7b-v0.1-zero3.json")
    # 4 FLOPs x 32 heads x 128 x mean keys a query x 4,096 queries; a causal
    # window as long as the sequence: query i sees i + 1 keys, mean 2,048.5
    by_hand = 4 * 32 * 128 * 2048.5 * 4096
    assert by_hand == pytest.approx(1.375e11, rel=1e-3)
    for hlo in (FWD, DQ, DKDV):
        assert flash_cost.call_flops(hlo, config) == by_hand
    # two sequences a chip double it; a window shorter than the sequence cuts it
    assert flash_cost.call_flops(FWD.replace("[8,4,", "[16,4,"), config) == 2 * by_hand
    short = dict(config, sliding_window=1024)
    keys = (1024 * 1025 / 2 + (4096 - 1024) * 1024) / 4096
    assert flash_cost.call_flops(DKDV, short) == 4 * 32 * 128 * keys * 4096
    assert flash_cost.call_flops("%flash_fwd.3 = token[] custom-call(", config) is None


def test_flash_roofline_readers_on_a_hand_built_trace():
    config = load_json("configs", "mistral-7b-v0.1-zero3.json")
    least = 4 * 32 * 128 * 2048.5 * 4096 / 197e12      # 0.698 ms a call
    kernels = {
        "%flash_fwd.3": {"count": 32, "seconds": 32 * 0.005, "hlo": FWD[:160]},
        "%flash_fwd.9": {"count": 32, "seconds": 32 * 0.007, "hlo": FWD[:160]},
        "%flash_dq.1": {"count": 64, "seconds": 64 * 0.008, "hlo": DQ[:160]},
        "%flash_dkdv.1": {"count": 64, "seconds": 64 * 0.006, "hlo": DKDV[:160]},
        # not the per-head kernels: other names are other kernels
        "%folded_flash_fwd.2": {"count": 5, "seconds": 9.0, "hlo": FWD[:160]},
        "%shard_map.7": {"count": 5, "seconds": 9.0, "hlo": FWD[:160]}}
    run = {"trace": {"kernels": kernels}, "config": config,
           "device": {"kind": "TPU v5 lite"}}
    assert read("kernel.flash_fwd_roofline", run) == pytest.approx(
        100 * 64 * least / (32 * 0.012))
    assert read("kernel.flash_bwd_roofline", run) == pytest.approx(
        100 * 128 * least / (64 * 0.014))
    assert 0 < read("kernel.flash_bwd_roofline", run) < 100
    # nothing to read: a CPU rehearsal has no custom calls, an untraced run no trace
    run["trace"]["kernels"] = {"%shard_map.7": kernels["%shard_map.7"]}
    assert read("kernel.flash_fwd_roofline", run) is None
    assert read("kernel.flash_bwd_roofline", run) is None
    assert read("kernel.flash_fwd_roofline", {"config": config}) is None


def test_spans_nest_by_containment_on_their_threads_line():
    spans = host_spans.nest([("ds.b", 10, 20), ("ds.a", 0, 100), ("ds.c", 30, 60),
                             ("ds.c.inner", 40, 50), ("ds.d", 100, 130)])
    got = {s["name"]: s for s in spans}
    names = [s["name"] for s in spans]
    assert names == ["ds.a", "ds.b", "ds.c", "ds.c.inner", "ds.d"]
    assert got["ds.a"]["parent"] is None and got["ds.d"]["parent"] is None
    assert got["ds.b"]["parent"] == got["ds.c"]["parent"] == names.index("ds.a")
    assert got["ds.c.inner"]["parent"] == names.index("ds.c")
    assert got["ds.a"]["self"] == 100 - 10 - 30      # direct children only
    assert got["ds.c"]["self"] == 20 and got["ds.d"]["self"] == 30
    trace = {"/host:CPU": {"python": [("ds.a", 0, 100), ("np.asarray", 5, 10),
                                      ("ds.b", 10, 10)],
                           "worker": [("ds.w", 50, 10)]},
             "/device:TPU:0": {"XLA Ops": [("ds.fake", 0, 5)]}}
    spans = host_spans.host_spans(trace)
    assert [(s["name"], s["line"], s["parent"]) for s in spans] == [
        ("ds.a", "python", None), ("ds.b", "python", 0), ("ds.w", "worker", None)]


def test_idle_and_host_work_readers_on_three_hand_made_intervals(monkeypatch):
    # (a rehearsal in another worker may have a trace on disk right now)
    monkeypatch.setattr(host_spans, "_xplane_path", lambda: None)
    # the first chip is idle in [100,200], [300,340] and [500,560] ns of a
    # window [0, 600]; the program's spans cover [90,150], [300,340] (twice:
    # a span and its child) and [520,530]
    trace = {"/device:TPU:0": {"XLA Ops": [("%a", 0, 100), ("%b", 200, 100),
                                           ("%c", 340, 160), ("%d", 560, 40)]},
             "/device:TPU:1": {"XLA Ops": [("%a", 10, 580)]},
             "/host:CPU": {"python": [
                 ("ds.train.loss_read", 90, 60), ("np.asarray(jax.Array)", 95, 50),
                 ("ds.train.publish", 300, 40), ("ds.train.checkpoint_save", 300, 40),
                 ("ds.train.dispatch", 520, 10), ("ds.train.batch_put", 700, 50)]}}
    idle, window = host_spans.first_chip_idle(trace, 2)
    assert idle == [(100, 200), (300, 340), (500, 560)] and window == (0, 600)
    run = {"trace": {}, "trace_steps": 2,
           "_host_spans": {"spans": host_spans.host_spans(trace), "idle": idle,
                           "window": window}}
    # unnamed: [150,200] + [500,520] + [530,560] = 100 of 200 ns idle
    assert read("host.idle_unnamed_pct.train", run) == pytest.approx(50.0)
    # work: publish's self time 0, its child 40, dispatch 10; the wait and the
    # span after the window are left out: 50 ns over 2 steps, in ms
    assert read("host.work_ms_per_step", run) == pytest.approx(50 * 1e-6 / 2)
    # a run of the parent's program has no ds.* span: nothing to report
    bare = {"trace": {}, "trace_steps": 2,
            "_host_spans": {"spans": [], "idle": idle, "window": window}}
    assert read("host.idle_unnamed_pct.train", bare) is None
    assert read("host.work_ms_per_step", bare) is None
    assert read("host.work_ms_per_step", {}) is None         # untraced
    assert read("host.idle_unnamed_pct.train", {}) is None


def test_load_reads_the_runs_one_trace_and_the_rehearsals_stand_in(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.observability.tracing import RequestTracer
    tracer = RequestTracer()
    step = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    float(step(x))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(3):
        with tracer.scope("ds.train.dispatch"):
            loss = step(x)
        with tracer.scope("ds.train.loss_read"):
            np.asarray(loss)
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    monkeypatch.setattr(host_spans, "_xplane_path", lambda: path)
    run = {"trace": {}, "trace_steps": 3, "chips": 1}
    hs = host_spans.load(run)
    assert run["_host_spans"] is hs                      # cached on the run
    assert [s["name"] for s in hs["spans"]].count("ds.train.dispatch") == 3
    assert hs["window"][1] > hs["window"][0]
    for name in ("host.work_ms_per_step", "host.idle_unnamed_pct.train"):
        value = read(name, run)
        assert value is None or value >= 0
    # no trace on disk (or more than one): the readers report nothing
    monkeypatch.setattr(host_spans, "_xplane_path", lambda: None)
    assert host_spans.load({}) is None
    assert read("host.work_ms_per_step", {"trace": {}, "trace_steps": 3}) is None


def test_set_up_readers_read_the_tracers_ring(monkeypatch):
    from deepspeed_tpu.observability.tracing import get_tracer
    tracer = get_tracer()
    tracer.reset()
    assert read("setup.engine_init_s", {}) is None
    assert read("setup.place_params_s", {}) is None
    with tracer.scope("ds.init", annotate=False):
        with tracer.scope("ds.init.mesh"):
            pass
        with tracer.scope("ds.init.place_params"):
            pass
        with tracer.scope("ds.init.opt_state"):
            pass
    for i in range(3000):           # the window's traffic cannot evict set-up
        with tracer.scope("ds.train.dispatch"):
            pass
    got = {s["name"]: s["dur_s"] for s in tracer.scopes("ds.init")}
    assert read("setup.engine_init_s", {}) == got["ds.init"]
    assert read("setup.place_params_s", {}) == pytest.approx(
        got["ds.init.place_params"] + got["ds.init.opt_state"])
    assert read("setup.place_params_s", {}) <= read("setup.engine_init_s", {})
    # a program without the span API (this PR's parent) gives nothing
    monkeypatch.setattr("deepspeed_tpu.observability.tracing.get_tracer", object)
    assert host_spans.ring_scopes("ds.init") == []
    assert read("setup.engine_init_s", {}) is None
    tracer.reset()
