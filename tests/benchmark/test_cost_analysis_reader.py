"""``setup.cost_analysis_s``: the reader of the program's
``ds.compile.cost_analysis`` spans, on a hand-filled tracer ring and on a
watched program of the CPU here: none a measurement."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import host_spans  # noqa: E402
from benchmark.run import cell_metrics, load_manifest, load_module  # noqa: E402

NAME = "setup.cost_analysis_s"
# the cells whose tests take a metric by addition (the Granite, SDAR and
# Kimi-VL cells' tests pin their cells' sets of metrics: PERF.md §7)
CELLS = ("train-zero3-seq4k", "train-olmoe-1chip-seq4k", "train-lfm2moe-1chip-seq8k")


def read(run):
    return load_module("layers", NAME).read(run)


def test_the_metric_is_a_set_up_entry_of_the_training_cells():
    m = load_manifest()
    entry, = [x for x in m["per_layer"] if x["name"] == NAME]
    assert {k: entry[k] for k in ("unit", "better", "source", "layer", "moves")} == {
        "unit": "s", "better": "lower", "source": "program_span",
        "layer": "set-up", "moves": "setup_s"}
    init, = [x for x in m["per_layer"] if x["name"] == "setup.engine_init_s"]
    assert entry["layer"] == init["layer"]
    assert set(CELLS) <= set(entry["workloads"]) <= set(init["workloads"])
    for cell in entry["workloads"]:
        assert NAME in {x["name"] for x in cell_metrics(m, cell, "per_layer")}
        assert "setup_s" in {x["name"] for x in cell_metrics(m, cell, "end_to_end")}


def test_the_reader_sums_the_cost_analysis_spans_of_the_ring(monkeypatch):
    from deepspeed_tpu.observability.tracing import get_tracer
    tracer = get_tracer()
    tracer.reset()
    assert read({}) is None
    with tracer.scope("ds.init", annotate=False):
        pass
    assert read({}) is None             # other set-up spans are not its own
    for key in ("eval_fwd", "train_step_fused"):
        with tracer.scope("ds.compile.cost_analysis", key=key):
            with tracer.scope("ds.compile.cost_analysis.inner"):
                pass
    for i in range(3000):               # the window's traffic cannot evict set-up
        with tracer.scope("ds.train.dispatch"):
            pass
    got = [s for s in tracer.scopes("ds.compile.") if s["name"].endswith("analysis")]
    assert [s["args"]["key"] for s in got] == ["eval_fwd", "train_step_fused"]
    assert read({}) == pytest.approx(sum(s["dur_s"] for s in got))
    # a program without the span API gives nothing, and nothing is raised
    monkeypatch.setattr("deepspeed_tpu.observability.tracing.get_tracer", object)
    assert host_spans.ring_scopes("ds.compile.cost_analysis") == []
    assert read({}) is None
    tracer.reset()


def test_the_reader_reads_what_a_watched_program_spent():
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.observability import CompileWatch, MetricsRegistry
    from deepspeed_tpu.observability.tracing import get_tracer
    get_tracer().reset()
    reg = MetricsRegistry()
    fn = CompileWatch(registry=reg).wrap(jax.jit(lambda a: jnp.tanh(a) @ a), "toy")
    fn(jnp.ones((8, 8), jnp.float32))
    assert fn.program_flops() > 0
    spent = reg.get("ds_cost_analysis_seconds_total", labels={"key": "toy"}).value
    assert 0 < read({}) == pytest.approx(spent)     # one clock: the span's
    get_tracer().reset()
