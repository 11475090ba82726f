"""Seconds inside ``deepspeed_tpu.initialize``'s engine construction: the
``ds.init`` span of the program's tracer ring (set-up runs before the
profiler starts). A span ends when the host call returns, so transfers still
in flight are not in it."""

from benchmark import host_spans


def read(run):
    spans = [s for s in host_spans.ring_scopes("ds.init")
             if s["name"] == "ds.init"]
    return sum(s["dur_s"] for s in spans) if spans else None
