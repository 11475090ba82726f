"""Seconds the engine's construction spends placing the parameters on the
mesh and building the optimizer state over them: the ``ds.init.place_params``
and ``ds.init.opt_state`` spans of the program's tracer ring."""

from benchmark import host_spans

NAMES = ("ds.init.place_params", "ds.init.opt_state")


def read(run):
    spans = [s for s in host_spans.ring_scopes("ds.init.")
             if s["name"] in NAMES]
    return sum(s["dur_s"] for s in spans) if spans else None
