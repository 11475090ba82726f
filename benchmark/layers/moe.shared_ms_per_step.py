"""Device time of the shared expert per traced step and chip, every phase
(forward, recomputation, backward), in milliseconds: the ops whose innermost
``ds.*`` scope is ``ds.moe.shared`` (``benchmark/scope_time.py``'s
``ds_ms``): the dense SwiGLU every token passes beside the routed experts. A
program without the scope reports nothing."""

from benchmark import scope_time

SCOPE = "ds.moe.shared"


def read(run):
    table = scope_time.load(run)
    if table is None:
        return None
    ms = sum(v for (scope, _), v in table["ds_ms"].items() if scope == SCOPE)
    return ms or None
