"""The one span API (``RequestTracer.scope``): nesting and self time, the
ring's bounds with set-up spans kept, the profiler's sink, and the places
the program uses it (goodput ledger, compile watch, serving instruments,
the training engine, the serving scheduler). No test asserts a wall-clock
time."""

import glob
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.observability import (GoodputLedger, RequestTracer,
                                         ServingInstruments, get_tracer)
from deepspeed_tpu.observability.metrics import MetricsRegistry
from deepspeed_tpu.observability.xla import CompileWatch


def _by_name(scopes):
    return {s["name"]: s for s in scopes}


# ------------------------------------------------------------ nesting


def test_scope_records_parent_and_self_time():
    tr = RequestTracer()
    with tr.scope("ds.tick.outer", annotate=False, rows=3) as outer:
        with tr.scope("ds.tick.a"):
            pass
        with tr.scope("ds.tick.b"):
            with tr.scope("ds.tick.b.inner"):
                pass
        outer.args["late"] = 7      # filled in while the scope is open
    got = _by_name(tr.scopes("ds.tick."))
    assert got["ds.tick.outer"]["parent"] is None
    assert got["ds.tick.a"]["parent"] == got["ds.tick.outer"]["sid"]
    assert got["ds.tick.b"]["parent"] == got["ds.tick.outer"]["sid"]
    assert got["ds.tick.b.inner"]["parent"] == got["ds.tick.b"]["sid"]
    assert got["ds.tick.outer"]["args"] == {"rows": 3, "late": 7}
    # self time = duration less the DIRECT children's, from the recorded times
    assert got["ds.tick.outer"]["self_s"] == pytest.approx(
        got["ds.tick.outer"]["dur_s"] - got["ds.tick.a"]["dur_s"]
        - got["ds.tick.b"]["dur_s"])
    assert got["ds.tick.b"]["self_s"] == pytest.approx(
        got["ds.tick.b"]["dur_s"] - got["ds.tick.b.inner"]["dur_s"])
    assert got["ds.tick.a"]["self_s"] == got["ds.tick.a"]["dur_s"]
    for s in got.values():
        assert s["t0_monotonic"] <= s["t1_monotonic"] and s["self_s"] >= 0
    # a scope is closed when its block raises, and the stack unwinds
    with pytest.raises(KeyError):
        with tr.scope("ds.tick.raises"):
            raise KeyError("x")
    with tr.scope("ds.tick.after"):
        pass
    assert _by_name(tr.scopes("ds.tick.after"))["ds.tick.after"]["parent"] is None


def test_scope_nesting_is_per_thread():
    tr = RequestTracer()
    inside = threading.Event()
    release = threading.Event()

    def other():
        inside.wait(5)
        with tr.scope("ds.tick.other_thread"):
            pass
        release.set()

    t = threading.Thread(target=other)
    t.start()
    with tr.scope("ds.tick.main"):
        inside.set()
        assert release.wait(5)      # the other thread's scope ran inside ours
    t.join()
    got = _by_name(tr.scopes("ds.tick."))
    assert got["ds.tick.other_thread"]["parent"] is None
    assert got["ds.tick.main"]["self_s"] == got["ds.tick.main"]["dur_s"]


# -------------------------------------------------------------- rings


def test_ring_is_bounded_and_set_up_spans_are_kept():
    tr = RequestTracer(max_waves=4, max_kept=3)
    with tr.scope("ds.init", annotate=False):
        with tr.scope("ds.init.mesh"):
            pass
    with tr.scope("ds.compile.cost_analysis", key="serve:x"):
        pass
    for i in range(20):
        with tr.scope("ds.tick.admit", tick=i):
            pass
    names = [s["name"] for s in tr.scopes()]
    assert names.count("ds.tick.admit") == 4
    assert [s["args"]["tick"] for s in tr.scopes("ds.tick.")] == [16, 17, 18, 19]
    assert {"ds.init", "ds.init.mesh", "ds.compile.cost_analysis"} <= set(names)
    # ... and the kept ring is itself bounded
    for _ in range(5):
        with tr.scope("ds.init.resume"):
            pass
    assert len(tr.scopes("ds.init")) + len(tr.scopes("ds.compile.")) == 3
    # scopes(since=) filters by end time; the Chrome export holds both rings
    last = tr.scopes("ds.tick.")[-1]
    assert tr.scopes("ds.tick.", since=last["t1_monotonic"]) == [last]
    chrome = [e["name"] for e in tr.chrome_trace()["traceEvents"]]
    assert "ds.init.resume" in chrome and chrome.count("ds.tick.admit") == 4
    tr.reset()
    assert tr.scopes() == []


def test_scope_with_uid_is_on_that_requests_timeline():
    tr = RequestTracer()
    tr.begin("7", t_submit=0.0)
    with tr.scope("ds.tick.finish", uid=7, outcome="ok"):
        pass
    with tr.scope("ds.tick.finish", uid=8):     # unknown uid: ring only
        pass
    spans = tr.timeline("7")["spans"]
    assert [s["name"] for s in spans] == ["ds.tick.finish"]
    assert spans[0]["args"] == {"outcome": "ok"}
    assert [s["uid"] for s in tr.scopes("ds.tick.finish")] == ["7", "8"]


# ------------------------------------------------- the profiler's sink


def test_scopes_are_host_events_of_a_profiler_capture(tmp_path):
    tr = RequestTracer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            with tr.scope("ds.tick.wrapper", annotate=False):
                with tr.scope("ds.tick.assemble", rows=2):
                    x = jnp.ones((8, 8))
                with tr.scope("ds.tick.harvest"):
                    np.asarray(x @ x)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    names = [e.name for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("ds.")]
    # literal names (args stay off the event), leaf phases only
    assert names.count("ds.tick.assemble") == 3
    assert names.count("ds.tick.harvest") == 3
    assert "ds.tick.wrapper" not in names
    assert len(tr.scopes("ds.tick.wrapper")) == 3      # the ring has it


# ------------------------------------------------------- where it is used


def test_goodput_span_is_a_ds_train_scope():
    get_tracer().reset()
    ledger = GoodputLedger(registry=MetricsRegistry())
    with ledger.span("checkpoint_save"):
        with ledger.span("checkpoint_load"):    # nested: the outermost wins
            pass
    got = _by_name(get_tracer().scopes("ds.train."))
    assert set(got) == {"ds.train.checkpoint_save", "ds.train.checkpoint_load"}
    assert (got["ds.train.checkpoint_load"]["parent"]
            == got["ds.train.checkpoint_save"]["sid"])


def test_program_flops_relowering_is_a_kept_scope():
    get_tracer().reset()
    watch = CompileWatch(registry=MetricsRegistry())
    fn = watch.wrap(jax.jit(lambda x: x @ x), "unit:matmul")
    fn(jnp.ones((4, 4)))
    # lazy for tiny programs: the compiling call left its own span alone
    assert get_tracer().scopes("ds.compile.cost_analysis") == []
    assert fn.program_flops() > 0
    assert fn.program_flops() > 0                      # cached: lowered once
    scope, = get_tracer().scopes("ds.compile.cost_analysis")
    assert scope["args"] == {"key": "unit:matmul"}


def test_wave_and_prefill_spans_carry_rows_and_contexts():
    obs = ServingInstruments(registry=MetricsRegistry(), tracer=RequestTracer())
    for uid in (1, 2):
        obs.request_submitted(uid, 0.0)
    obs.prefill_span([1, 2], 1.0, 1.5, tokens=300, ctx_tokens=640)
    obs.wave_span([1, 2], 2.0, 2.5, K=4, size=2, kind="greedy", ctx_tokens=940)
    # ONE global span per tick beside the per-request copies
    ring = [e for e in obs.tracer.chrome_trace()["traceEvents"]
            if e.get("tid") == 0 and e["ph"] == "X"]
    assert [e["name"] for e in ring] == ["prefill", "fused_wave[greedy]"]
    assert ring[0]["args"] == {"tokens": 300, "rows": 2, "ctx_tokens": 640}
    assert ring[1]["args"] == {"K": 4, "size": 2, "kind": "greedy", "rows": 2,
                               "ctx_tokens": 940}
    for uid in ("1", "2"):
        spans = obs.tracer.timeline(uid)["spans"]
        assert [s["name"] for s in spans] == ["prefill", "fused_wave[greedy]"]
        assert spans[0]["args"]["tokens"] == 300
        assert (spans[0]["t0_monotonic"], spans[0]["t1_monotonic"]) == (1.0, 1.5)


def _tiny_engine(observability=True):
    import deepspeed_tpu
    from deepspeed_tpu.comm import reset_mesh_context
    from deepspeed_tpu.models import LlamaConfig, init_llama
    reset_mesh_context()
    cfg = LlamaConfig.tiny(num_key_value_heads=2)
    model, params = init_llama(cfg, seed=1, dtype=jnp.float32)
    ds = {"train_batch_size": len(jax.devices()),
          "optimizer": {"type": "AdamW", "params": {"lr": 1e-3}},
          "steps_per_print": 0, "observability": {"enabled": observability}}
    engine, *_ = deepspeed_tpu.initialize(model=model, model_parameters=params,
                                          config=ds)
    ids = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (len(jax.devices()), 16)))
    return engine, ids


def test_training_engine_spans_its_construction_and_its_step():
    get_tracer().reset()
    engine, ids = _tiny_engine()
    init = _by_name(get_tracer().scopes("ds.init"))
    assert set(init) == {"ds.init", "ds.init.mesh", "ds.init.zero_plan",
                         "ds.init.place_params", "ds.init.opt_state",
                         "ds.init.build_step", "ds.init.resume"}
    assert all(s["parent"] == init["ds.init"]["sid"]
               for n, s in init.items() if n != "ds.init")
    assert 0 <= init["ds.init"]["self_s"] <= init["ds.init"]["dur_s"]
    for _ in range(2):
        assert np.isfinite(engine.train_batch(iter([(ids, ids)])))
    step = [s["name"] for s in get_tracer().scopes("ds.train.")]
    for name in ("ds.train.data_wait", "ds.train.batch_put",
                 "ds.train.dispatch", "ds.train.publish", "ds.train.loss_read"):
        assert step.count(name) >= 2, name
    assert all(n.startswith("ds.train.") for n in step)
    # every step-loop span is a leaf or holds only a wait: nothing nests a
    # dispatch, so each is fit for the profiler's sink
    by_sid = {s["sid"]: s for s in get_tracer().scopes("ds.train.")}
    assert all(by_sid[s["parent"]]["name"] == "ds.train.publish"
               for s in by_sid.values() if s["parent"] is not None)


def test_training_engine_records_nothing_with_observability_off():
    get_tracer().reset()
    engine, ids = _tiny_engine(observability=False)
    assert np.isfinite(engine.train_batch(iter([(ids, ids)])))
    assert get_tracer().scopes("ds.") == []


@pytest.fixture(scope="module")
def served():
    """A tiny engine behind a scheduler that served three requests."""
    from deepspeed_tpu.inference.v2 import (RaggedInferenceEngineConfig,
                                            ServingScheduler)
    from deepspeed_tpu.inference.v2.engine_v2 import build_llama_engine
    from deepspeed_tpu.models import LlamaConfig, init_llama
    cfg = LlamaConfig.tiny(num_key_value_heads=2)
    _, params = init_llama(cfg, seed=5)
    eng = build_llama_engine(cfg, params=params, dtype=jnp.float32,
                             kv_block_size=16,
                             engine_config=RaggedInferenceEngineConfig(
                                 num_kv_blocks=96))
    sched = ServingScheduler(eng, fused_decode_window=4).start()
    rng = np.random.default_rng(3)
    handles = [sched.submit([int(t) for t in rng.integers(0, cfg.vocab_size, n)],
                            max_new_tokens=9) for n in (20, 33, 7)]
    outs = [h.result(timeout=300) for h in handles]
    sched.stop()
    return sched, eng, handles, outs


def test_scheduler_tick_phases_are_spans_of_its_own_tracer(served):
    sched, eng, handles, outs = served
    assert all(len(o) == 9 for o in outs)
    tracer = sched.observability.tracer
    assert eng.tracer is tracer
    names = {s["name"] for s in tracer.scopes("ds.tick.")}
    assert {"ds.tick.admit", "ds.tick.assemble", "ds.tick.dispatch",
            "ds.tick.harvest", "ds.tick.emit", "ds.tick.idle_wait"} <= names
    assert names <= {"ds.tick.admit", "ds.tick.assemble", "ds.tick.dispatch",
                     "ds.tick.overlap_fill", "ds.tick.harvest",
                     "ds.tick.sample", "ds.tick.emit", "ds.tick.idle_wait"}
    # leaf rule: only the overlap-fill wrapper holds other scopes
    by_sid = {s["sid"]: s for s in tracer.scopes("ds.tick.")}
    parents = {by_sid[s["parent"]]["name"] for s in by_sid.values()
               if s["parent"] in by_sid}
    assert parents <= {"ds.tick.overlap_fill"}
    # GET /debug/trace renders them with no new endpoint
    chrome = {e["name"] for e in tracer.chrome_trace()["traceEvents"]}
    assert "ds.tick.dispatch" in chrome
    # the names the benchmark's readers key on are as they were
    spans = sched.trace_timeline(handles[0].uid)["spans"]
    kinds = {s["name"] for s in spans}
    assert "queue" in kinds and any(k.startswith("prefill") for k in kinds)
    for s in spans:
        if s["name"].startswith(("prefill", "fused_wave")):
            assert s["args"]["rows"] >= 1 and s["args"]["ctx_tokens"] >= 0
            assert {"t0_monotonic", "t1_monotonic"} <= set(s)
        if s["name"].startswith("prefill"):
            assert s["args"]["tokens"] > 0
    waves = [s for s in spans if s["name"].startswith("fused_wave")]
    if waves:   # a wave's rows had their prompts cached when it was dispatched
        assert all(w["args"]["ctx_tokens"] >= 20 for w in waves)
    assert {"fused_dispatches", "fused_k_sum"} <= set(sched.trace)


def test_serving_programs_lower_under_their_own_module_names(served):
    _, eng, _, _ = served
    model = eng.model()
    # the prefix cache's copy-on-write and the speculative wave did not run
    # above: dispatch each once, through the engine's own entry points
    model.cow_copy_block(0, 1)
    S, B, W = 2, 2, 16
    model.fused_spec_decode(
        np.zeros(S, np.int32), np.ones(S, np.int32), np.zeros(S, np.int32),
        np.zeros((S, B), np.int32), np.zeros((S, W), np.int32),
        np.ones(S, np.int32), np.full(S, 2, np.int32), np.full(S, 2, np.int32),
        n_steps=2, draft_width=2, max_ngram=4)
    want = {"fused_spec": "jit_ds_fused_spec_decode", "fused": "jit_ds_fused_decode",
            "cow_copy": "jit_ds_kv_cow"}
    seen = set()
    for key, fn in model._fwd_cache.items():
        kind = key if isinstance(key, str) else key[0]
        name = want.get(kind, "jit_ds_ragged_forward")
        a, k = fn._flops_spec        # the shapes of the call that compiled it
        text = fn._fn.lower(*a, **k).as_text()
        assert f"module @{name} " in text, (key, text[:200])
        assert "jit__unknown" not in text
        seen.add(name)
    assert seen == {"jit_ds_ragged_forward", "jit_ds_fused_decode",
                    "jit_ds_fused_spec_decode", "jit_ds_kv_cow"}
