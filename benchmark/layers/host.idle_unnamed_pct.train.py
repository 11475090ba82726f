"""Share of the first chip's idle time in the traced window that no ``ds.*``
span of the program covers: how complete the program's tracing is. What the
caller does between steps (the runner makes each batch outside the program)
has no span by design and stays in this share."""

from benchmark import host_spans, reduce_trace


def read(run):
    hs = host_spans.load(run)
    if not hs or not hs["spans"]:
        return None
    idle = reduce_trace.measure(hs["idle"])
    if not idle:
        return None
    named = [(s["start"], s["end"]) for s in hs["spans"]]
    return 100.0 * reduce_trace.measure(
        reduce_trace.subtract(hs["idle"], named)) / idle
