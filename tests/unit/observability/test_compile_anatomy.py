"""A compiling call taken apart: the tap on ``jax.monitoring`` and what a
watched call claims of it (``observability/xla.py``), on small jitted
functions of the CPU here. No engine is built."""

import importlib
import itertools
import threading
import time

import pytest

jax = pytest.importorskip("jax")
import jax.monitoring  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from deepspeed_tpu.observability import (CompileWatch, MetricsRegistry,
                                         get_registry, get_tracer,
                                         install_backend_compile_listener)
from deepspeed_tpu.observability import xla

PIECES = ("ds.compile.trace", "ds.compile.lower", "ds.compile.backend")
SECONDS = ("trace_seconds", "lower_seconds", "backend_seconds", "load_seconds")


@pytest.fixture
def ring():
    """The tap installed, nothing held from earlier tests, an empty ring."""
    assert install_backend_compile_listener()
    xla.flush_compile_events()
    tracer = get_tracer()
    tracer.reset()
    yield tracer
    xla.flush_compile_events()
    tracer.reset()


def _unclaimed(name: str) -> float:
    c = get_registry().get(name, labels={"key": xla.UNCLAIMED})
    return c.value if c is not None else 0.0


def _children(tracer, call):
    return [s for s in tracer.scopes("ds.compile.") if s["parent"] == call["sid"]]


def test_a_compiling_call_leaves_one_call_span_with_its_pieces(ring):
    watch = CompileWatch(registry=MetricsRegistry())
    fn = watch.wrap(jax.jit(lambda x: jnp.tanh(x) @ x), "toy")
    x = jnp.ones((16, 16), jnp.float32)
    fn(x)
    call, = ring.scopes("ds.compile.call")
    assert call["args"] == {"key": "toy", "retrace": False, "programs": 1,
                            "persistent": "off"}
    kids = _children(ring, call)
    assert sorted(s["name"] for s in kids) == sorted(PIECES)
    for s in kids:
        assert s["args"]["key"] == "toy" and s["args"]["fun_name"]
        assert call["t0_monotonic"] <= s["t0_monotonic"]
        assert s["t1_monotonic"] <= call["t1_monotonic"]
    backend, = [s for s in kids if s["name"] == "ds.compile.backend"]
    assert backend["args"]["cache"] == "off" and backend["args"]["load_s"] == 0.0
    assert 0 < sum(s["dur_s"] for s in kids) <= call["dur_s"]
    assert call["self_s"] == pytest.approx(
        call["dur_s"] - sum(s["dur_s"] for s in kids))
    c = watch.counts("toy")
    # the counters are the spans' own lengths; the call is ds_compile_seconds
    for s in kids:
        assert c[s["name"].rsplit(".", 1)[1] + "_seconds"] == pytest.approx(s["dur_s"])
    assert c["compile_seconds"] <= call["dur_s"]

    # a second call hits jit's cache: no span, no second, one hit
    before = {k: c[k] for k in SECONDS}
    fn(x)
    assert len(ring.scopes("ds.compile.")) == 1 + len(kids)
    c = watch.counts("toy")
    assert {k: c[k] for k in SECONDS} == before and c["hits"] == 1


def test_a_retrace_is_marked(ring):
    watch = CompileWatch(registry=MetricsRegistry())
    fn = watch.wrap(jax.jit(lambda x: x * 2.0), "toy")
    fn(jnp.ones((4,), jnp.float32))
    fn(jnp.ones((8,), jnp.float32))
    first, second = ring.scopes("ds.compile.call")
    assert (first["args"]["retrace"], second["args"]["retrace"]) == (False, True)
    assert [len(_children(ring, c)) for c in (first, second)] == [3, 3]
    assert watch.counts("toy")["recompiles"] == 1


def test_the_cost_analysis_is_a_child_and_the_call_ends_after_it(ring, monkeypatch):
    watch = CompileWatch(registry=MetricsRegistry())
    fn = watch.wrap(jax.jit(lambda x: x @ x), "toy")
    # as for a real program, whose compile takes longer than half a second
    real, skew = time.monotonic, itertools.count(1)
    monkeypatch.setattr(xla.time, "monotonic", lambda: real() + 0.3 * next(skew))
    fn(jnp.ones((4, 4), jnp.float32))
    monkeypatch.undo()
    call, = ring.scopes("ds.compile.call")
    cost, = ring.scopes("ds.compile.cost_analysis")
    assert cost["parent"] == call["sid"]
    assert cost["t1_monotonic"] <= call["t1_monotonic"]


def test_an_inner_jit_is_counted_once(ring):
    inner = jax.jit(lambda x: jnp.sin(x) * 2.0)
    seen = []

    def listen(name, secs, **kw):
        if name.endswith("jaxpr_trace_duration"):
            seen.append(kw.get("fun_name"))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        watch = CompileWatch(registry=MetricsRegistry())
        fn = watch.wrap(jax.jit(lambda x: inner(x) + inner(x * 3.0)[:2].sum()),
                        "outer")
        fn(jnp.ones((8,), jnp.float32))
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert len(seen) >= 2           # jax reported the inner traces too
    call, = ring.scopes("ds.compile.call")
    trace, = [s for s in _children(ring, call) if s["name"] == "ds.compile.trace"]
    assert watch.counts("outer")["trace_seconds"] == pytest.approx(trace["dur_s"])
    assert trace["dur_s"] <= call["dur_s"]
    xla.flush_compile_events()      # and nothing of it was left for "-"
    assert len(ring.scopes("ds.compile.trace")) == 1


def test_a_piece_inside_another_is_that_piece_s_but_a_backend_compile_its_own(ring):
    trace, lower, backend = (f"/jax/core/compile/{n}_duration" for n in (
        "jaxpr_trace", "jaxpr_to_mlir_module", "backend_compile"))
    before = {n: _unclaimed(f"ds_compile_{n}_seconds_total")
              for n in ("trace", "lower", "backend")}
    begin, end = jax.monitoring.record_scalar, jax.monitoring.record_event_duration_secs
    # a lowering rule traces a helper; an eager op compiles while a program
    # is traced: as jax reports them, each begin before its end
    begin(lower, time.time(), fun_name="jit_step")
    begin(trace, time.time(), fun_name="greater")
    end(trace, 0.125, fun_name="greater")
    end(lower, 2.0, fun_name="jit_step")
    begin(trace, time.time(), fun_name="step")
    begin(trace, time.time(), fun_name="inner")
    begin(backend, time.time(), fun_name="jit_arange")
    end(backend, 0.5, fun_name="jit_arange")
    end(trace, 0.75, fun_name="inner")
    end(trace, 3.0, fun_name="step")
    xla.flush_compile_events()
    got = {n: _unclaimed(f"ds_compile_{n}_seconds_total") - v
           for n, v in before.items()}
    assert got == pytest.approx({"trace": 3.0, "lower": 2.0, "backend": 0.5})
    assert sorted(s["args"]["fun_name"] for s in ring.scopes("ds.compile.")) == [
        "jit_arange", "jit_step", "step"]


def test_an_unwatched_jit_lands_under_the_unclaimed_key(ring):
    before = {n: _unclaimed(n) for n in ("ds_compile_trace_seconds_total",
                                         "ds_compile_backend_seconds_total")}
    jax.jit(lambda x: x - 1.0)(jnp.ones((3,), jnp.float32))
    # held on its thread until a watched call claims, or a publish
    assert all(_unclaimed(n) == v for n, v in before.items())
    watch = CompileWatch(registry=MetricsRegistry())
    watch.wrap(jax.jit(lambda x: x + 1.0), "toy")(jnp.ones((3,), jnp.float32))
    assert all(_unclaimed(n) > v for n, v in before.items())
    # a small unclaimed piece leaves no span; the watched call's three do
    for s in ring.scopes("ds.compile."):
        assert s["args"]["key"] == "toy" or s["dur_s"] >= xla.SPAN_FLOOR_S
    assert watch.counts("toy")["backend_seconds"] > 0


def test_a_long_unclaimed_piece_gets_a_span_and_small_ones_spare_the_ring(ring):
    with ring.scope("ds.init", annotate=False):
        pass
    name = "/jax/core/compile/backend_compile_duration"
    before = _unclaimed("ds_compile_backend_seconds_total")
    for i in range(600):            # as jax reports them: a begin, then the end
        jax.monitoring.record_scalar(name, time.time(), fun_name=f"jit_small_{i}")
        jax.monitoring.record_event_duration_secs(name, 0.002, fun_name=f"jit_small_{i}")
    jax.monitoring.record_scalar(name, time.time(), fun_name="jit_reference")
    jax.monitoring.record_event_duration_secs(name, 0.5, fun_name="jit_reference")
    xla.flush_compile_events()
    assert _unclaimed("ds_compile_backend_seconds_total") - before == pytest.approx(
        600 * 0.002 + 0.5)
    assert [s["name"] for s in ring.scopes("ds.init")] == ["ds.init"]
    span, = ring.scopes("ds.compile.backend")
    assert span["args"] == {"key": "-", "fun_name": "jit_reference",
                            "cache": "off", "load_s": 0.0, "saved_s": 0.0}
    assert span["dur_s"] == pytest.approx(0.5) and span["parent"] is None


def test_the_persistent_cache_is_told_apart_a_miss_then_a_hit(ring, tmp_path):
    from jax.experimental.compilation_cache import compilation_cache
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    try:
        watch = CompileWatch(registry=MetricsRegistry())
        jitted = jax.jit(lambda x: jnp.cos(x) @ x + 50.0)
        fn = watch.wrap(jitted, "toy")
        x = jnp.ones((16, 16), jnp.float32)
        fn(x)
        c = watch.counts("toy")
        assert (c["persistent_misses"], c["persistent_hits"]) == (1, 0)
        assert c["load_seconds"] == 0.0
        jitted.clear_cache()        # jit's own caches: the next call compiles
        fn(x)
        c = watch.counts("toy")
        assert (c["persistent_misses"], c["persistent_hits"]) == (1, 1)
        assert 0 < c["load_seconds"] <= c["backend_seconds"]
        miss, hit = ring.scopes("ds.compile.call")
        assert (miss["args"]["persistent"], hit["args"]["persistent"]) == ("miss", "hit")
        loaded, = [s for s in _children(ring, hit) if s["name"] == "ds.compile.backend"]
        assert loaded["args"]["cache"] == "hit" and loaded["args"]["load_s"] > 0
        assert loaded["args"]["load_s"] == pytest.approx(c["load_seconds"])
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def test_a_publish_charges_what_another_thread_left(ring):
    before = _unclaimed("ds_compile_lower_seconds_total")
    worker = threading.Thread(
        target=lambda: jax.jit(lambda x: x / 3.0)(jnp.ones((5,), jnp.float32)))
    worker.start()
    worker.join(timeout=60)
    assert not worker.is_alive()
    assert _unclaimed("ds_compile_lower_seconds_total") == before
    xla.flush_compile_events()
    assert _unclaimed("ds_compile_lower_seconds_total") > before
    assert get_registry().get("ds_compile_tap_seconds_total").value > 0


def test_the_package_records_its_own_import(ring):
    import deepspeed_tpu
    t_before = time.monotonic()
    importlib.reload(deepspeed_tpu)     # its first line to its last, again
    span, = ring.scopes("ds.import")
    assert t_before <= span["t0_monotonic"] == deepspeed_tpu._IMPORT_T0
    assert span["t1_monotonic"] <= time.monotonic() and span["parent"] is None
    for _ in range(3000):           # kept: the window's traffic cannot evict it
        with ring.scope("ds.train.dispatch", annotate=False):
            pass
    assert [s["sid"] for s in ring.scopes("ds.import")] == [span["sid"]]
