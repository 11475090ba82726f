"""Device-busy milliseconds per decode step: busy time inside the fused
decode programs over the decode steps the scheduler counted while the trace
ran. The program gives its modules no name today (``jit__unknown``), so a
fused decode program is told by what the trace does show: it is the module
that holds a ``%while`` (the scan over K steps). A wave in flight at either
edge of the trace is counted on one side only."""


def read(run):
    c, trace = run.get("counters", {}), run.get("trace")
    if not trace or "trace_close" not in c:
        return None
    busy = sum(m["busy_s"] for n, m in trace["modules"].items()
               if n.endswith("[while]"))
    steps = c["trace_close"]["fused_k_sum"] - c["trace_open"]["fused_k_sum"]
    return busy * 1e3 / steps if busy and steps else None
