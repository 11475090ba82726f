"""The program's own spans, for the per-layer readers that read them.

The program times its phases through one API (``deepspeed_tpu/observability/
tracing.py``: ``RequestTracer.scope``) with two sinks, and this file reads
both:

- the profiler's trace of the run (``ds.*`` events on a ``/host:`` plane, on
  the device trace's clock): :func:`load` finds the run's one xplane file,
  which is still on disk when the readers run, and gives the ``ds.*`` host
  spans with their parent (by containment on their thread's line) and self
  time, and the first chip's idle intervals in the traced window;
- the tracer's ring (``time.monotonic()``), which also holds what ran before
  the profiler started: :func:`ring_scopes`.

A program that has no such spans (the parent of the PR that brought them)
gives empty lists and ``None``: the readers then report nothing.
"""

import glob
import os
from typing import List, Optional

from benchmark import reduce_trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PREFIX = "ds."


def _xplane_path() -> Optional[str]:
    paths = glob.glob(os.path.join(ROOT, ".bench_out", "*", "plugins",
                                   "profile", "*", "*.xplane.pb"))
    return paths[0] if len(paths) == 1 else None


def nest(events) -> List[dict]:
    """``(name, start, end)`` events of ONE thread's line, as spans with
    ``parent`` (index of the innermost span that contains it, or None) and
    ``self`` (its length less its direct children's), in start order."""
    spans = [{"name": n, "start": s, "end": e, "parent": None, "self": e - s}
             for n, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2]))]
    stack = []
    for i, sp in enumerate(spans):
        while stack and spans[stack[-1]]["end"] < sp["end"]:
            stack.pop()     # not contained: a sibling, or it only overlaps
        if stack:
            sp["parent"] = stack[-1]
            spans[stack[-1]]["self"] -= sp["end"] - sp["start"]
        stack.append(i)
    return spans


def host_spans(trace: dict) -> List[dict]:
    """Every ``ds.*`` event of the ``/host:`` planes, nested per line. Times
    in nanoseconds on the profiler's clock."""
    out = []
    for plane, lines in trace.items():
        if not plane.startswith("/host:"):
            continue
        for line, events in lines.items():
            mine = [(n, s, s + d) for n, s, d in events if n.startswith(PREFIX)]
            base = len(out)
            for sp in nest(mine):
                if sp["parent"] is not None:
                    sp["parent"] += base
                sp["line"] = line
                out.append(sp)
    return out


def first_chip_idle(trace: dict, chips: int):
    """``(idle intervals of the first chip, (window start, window end))``:
    the window runs from the first to the last device event over the chips
    used, as ``reduce_trace.reduce`` takes it."""
    planes = reduce_trace.device_planes(trace, chips)
    if not planes:      # a rehearsal on a CPU: the same stand-in as the reducer's
        trace = reduce_trace._rehearsal_view(trace)
        planes = reduce_trace.device_planes(trace, 1)
    ops = {p: [(s, s + d) for _, s, d in trace[p][reduce_trace.OPS_LINE]]
           for p in planes}
    if not any(ops.values()):
        return [], (0.0, 0.0)
    lo = min(s for ivs in ops.values() for s, _ in ivs)
    hi = max(e for ivs in ops.values() for _, e in ivs)
    return reduce_trace.subtract([(lo, hi)], ops[planes[0]]), (lo, hi)


def load(run: dict) -> Optional[dict]:
    """``{"spans", "idle", "window"}`` of the run's trace (nanoseconds),
    cached on ``run``; ``None`` when the run left no one trace to read."""
    if "_host_spans" not in run:
        path = _xplane_path()
        if path is None:
            run["_host_spans"] = None
        else:
            trace = reduce_trace.load_xplane(path)
            chips = run.get("chips") or run.get("device", {}).get("count", 1)
            idle, window = first_chip_idle(trace, chips)
            run["_host_spans"] = {"spans": host_spans(trace), "idle": idle,
                                  "window": window}
    return run["_host_spans"]


def ring_scopes(prefix: str) -> List[dict]:
    """The scopes of the process-wide tracer's ring whose name starts with
    ``prefix``; empty where the program has no span API."""
    from deepspeed_tpu.observability.tracing import get_tracer
    scopes = getattr(get_tracer(), "scopes", None)
    return scopes(prefix) if scopes is not None else []
