"""What the program says of its own compiling calls, for the six ``setup.*``
readers that take set-up apart (PR 50).

The program charges what jax reports of each compile (the trace, the lowering,
the backend's compile or its load from the persistent cache) to the watched
call that compiled, or to ``key="-"`` (``deepspeed_tpu/observability/xla.py``):
counters a compile key in its metrics registry, and the spans
``ds.compile.call`` > ``ds.compile.trace`` / ``.lower`` / ``.backend`` /
``.cost_analysis`` in its tracer's kept ring. Both are read when the run's
line is made, after the window: a program that compiled inside the window
(``setup.programs`` is read at its open) counts those compiles here too.

A program without them (the parent of the PR that brought them) gives
``None`` and raises nothing.
"""

from typing import List, Optional

from benchmark import host_spans

CALL = "ds.compile.call"


def counter_sum(family: str) -> Optional[float]:
    """The counter ``family`` of the program's registry summed over its
    compile keys, what no watched call claimed (``key="-"``) included;
    ``None`` where the program has no such family."""
    from deepspeed_tpu.observability import get_registry, xla
    flush = getattr(xla, "flush_compile_events", None)
    if flush is not None:
        flush()     # what is still held for a call that never came
    series = get_registry().series(family)
    return sum(c.value for c in series) if series else None


def spans(name: str) -> List[dict]:
    """The ring's spans named exactly ``name``."""
    return [s for s in host_spans.ring_scopes(name) if s["name"] == name]
