"""Seconds the program spent reading its compiled programs' FLOPs and named
residual bytes (what the MFU and recomputation gauges report): the
``ds.compile.cost_analysis`` spans of the program's tracer ring, summed over
the run's programs (set-up runs before the profiler starts). A program that
gets its trace and lowering back from jit's caches spends milliseconds here;
one that asks with another key traces and lowers the whole step again."""

from benchmark import host_spans


def read(run):
    spans = [s for s in host_spans.ring_scopes("ds.compile.cost_analysis")
             if s["name"] == "ds.compile.cost_analysis"]
    return sum(s["dur_s"] for s in spans) if spans else None
