"""The six ``setup.*`` readers that take a compiling call apart
(``benchmark/compile_anatomy.py``), on a hand-made tracer ring and metrics
registry and on one watched program of the CPU here: none a measurement."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import compile_anatomy  # noqa: E402
from benchmark.run import cell_metrics, load_manifest, load_module  # noqa: E402

# name -> (unit, source), in the manifest's order
METRICS = {"setup.import_s": ("s", "program_span"),
           "setup.trace_s": ("s", "program_counter"),
           "setup.lower_s": ("s", "program_counter"),
           "setup.cache_load_s": ("s", "program_counter"),
           "setup.first_call_s": ("s", "program_span"),
           "setup.first_call_unnamed_pct": ("%", "program_span")}
# the cells whose tests take a metric by addition (the Granite, SDAR, Kimi-VL
# and Keye-VL cells' tests pin their cells' sets of metrics: ROADMAP D12)
CELLS = ["train-zero3-seq4k", "train-olmoe-1chip-seq4k",
         "train-lfm2moe-1chip-seq8k", "train-ling3flash-1chip-kda-longseq"]
COUNTERS = {"setup.trace_s": "ds_compile_trace_seconds_total",
            "setup.lower_s": "ds_compile_lower_seconds_total",
            "setup.cache_load_s": "ds_compile_cache_load_seconds_total"}


def read(name):
    return load_module("layers", name).read({})


@pytest.fixture
def program(monkeypatch):
    """A ring and a registry of the test's own in the process-wide ones'
    place, as the readers find them."""
    from deepspeed_tpu import observability
    from deepspeed_tpu.observability import tracing
    tracer, registry = tracing.RequestTracer(), observability.MetricsRegistry()
    monkeypatch.setattr(tracing, "get_tracer", lambda: tracer)
    monkeypatch.setattr(observability, "get_registry", lambda: registry)
    return tracer, registry


def test_the_six_are_set_up_entries_of_the_four_cells_in_order():
    m = load_manifest()
    names = [x["name"] for x in m["per_layer"]]
    at = [names.index(n) for n in METRICS]      # each there, once
    assert at == sorted(at) and all(names.count(n) == 1 for n in METRICS)
    init, = [x for x in m["per_layer"] if x["name"] == "setup.engine_init_s"]
    assert names.index(init["name"]) < at[0]    # appended after what was there
    for name, (unit, source) in METRICS.items():
        entry = m["per_layer"][names.index(name)]
        assert entry == {"name": name, "unit": unit, "better": "lower",
                         "source": source, "layer": init["layer"],
                         "moves": "setup_s", "workloads": CELLS}
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "layers", name + ".py"))
    for cell in (w["name"] for w in m["workloads"]):
        listed = {x["name"] for x in cell_metrics(m, cell, "per_layer")}
        assert (set(METRICS) <= listed) == (cell in CELLS)
        assert (set(METRICS) & listed) in (set(), set(METRICS))
        assert "setup_s" in {x["name"] for x in cell_metrics(m, cell, "end_to_end")}


def test_a_program_without_the_spans_and_counters_reads_nothing(program, monkeypatch):
    tracer, _ = program
    assert {n: read(n) for n in METRICS} == dict.fromkeys(METRICS)
    with tracer.scope("ds.init", annotate=False):       # other set-up spans
        with tracer.scope("ds.compile.cost_analysis", key="a"):
            pass
    assert {n: read(n) for n in METRICS} == dict.fromkeys(METRICS)
    # nor does one without the span API at all, and nothing is raised
    from deepspeed_tpu.observability import tracing
    monkeypatch.setattr(tracing, "get_tracer", object)
    assert [read(n) for n in METRICS if n not in COUNTERS] == [None] * 3


def test_the_readers_sum_a_hand_made_ring_and_registry(program):
    tracer, registry = program
    tracer.closed_scope("ds.import", 0.0, 4.0)
    tracer.closed_scope("ds.importer", 0.0, 9.0)        # another's: by name
    # two compiling calls: 10 s with 9 s covered, 30 s with 29 s covered,
    # one piece of no call (a reference's program) beside them
    a = tracer.closed_scope("ds.compile.call", 10.0, 20.0, args={"key": "a"})
    tracer.closed_scope("ds.compile.trace", 10.0, 14.0, a)
    tracer.closed_scope("ds.compile.lower", 14.5, 17.5, a)
    tracer.closed_scope("ds.compile.backend", 17.5, 19.5, a)
    b = tracer.closed_scope("ds.compile.call", 30.0, 60.0, args={"key": "b"})
    tracer.closed_scope("ds.compile.backend", 30.0, 50.0, b)
    tracer.closed_scope("ds.compile.cost_analysis", 50.0, 59.0, b)
    tracer.closed_scope("ds.compile.backend", 70.0, 75.0, None, {"key": "-"})
    for family, by_key in (
            ("ds_compile_trace_seconds_total", {"a": 4.0, "-": 0.5}),
            ("ds_compile_lower_seconds_total", {"a": 3.0, "b": 0.25, "-": 0.125}),
            ("ds_compile_cache_load_seconds_total", {"a": 0.0, "b": 18.0})):
        for key, seconds in by_key.items():
            registry.counter(family, labels={"key": key}).inc(seconds)
    assert {n: read(n) for n in METRICS} == {
        "setup.import_s": 4.0, "setup.trace_s": 4.5, "setup.lower_s": 3.375,
        "setup.cache_load_s": 18.0, "setup.first_call_s": 40.0,
        "setup.first_call_unnamed_pct": pytest.approx(100.0 * (1.0 + 1.0) / 40.0)}
    # by self time: children that overlap their call's length twice over
    # leave it no negative share
    tracer.closed_scope("ds.compile.trace", 30.0, 59.0, b)
    assert read("setup.first_call_unnamed_pct") == pytest.approx(100.0 * 1.0 / 40.0)


def test_the_readers_read_what_a_watched_program_left():
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.observability import (CompileWatch, get_registry,
                                             get_tracer,
                                             install_backend_compile_listener)
    assert install_backend_compile_listener()
    jax.jit(lambda a: a + 2.0)(jnp.ones((8,), jnp.float32))     # no call's: "-"
    assert compile_anatomy.counter_sum("ds_compile_trace_seconds_total") > 0
    get_tracer().reset()
    x = jnp.ones((8, 8), jnp.float32)       # an eager program of its own
    before = {n: read(n) for n in COUNTERS}
    watch = CompileWatch(registry=get_registry())
    watch.wrap(jax.jit(lambda a: jnp.tanh(a) @ a), "anatomy:toy")(x)
    c = watch.counts("anatomy:toy")
    assert read("setup.trace_s") - before["setup.trace_s"] == pytest.approx(
        c["trace_seconds"])
    assert read("setup.lower_s") - before["setup.lower_s"] == pytest.approx(
        c["lower_seconds"])
    assert read("setup.cache_load_s") == before["setup.cache_load_s"]
    call, = get_tracer().scopes("ds.compile.call")
    assert c["compile_seconds"] <= read("setup.first_call_s") == call["dur_s"]
    assert 0.0 <= read("setup.first_call_unnamed_pct") < 100.0
    assert read("setup.first_call_unnamed_pct") == pytest.approx(
        100.0 * call["self_s"] / call["dur_s"])
    get_tracer().reset()
