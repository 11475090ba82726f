"""Device-busy milliseconds per 1,000 prompt tokens fed: busy time inside
the ragged forward programs over the tokens of the server's ``prefill*``
spans that lie inside the traced interval. The program gives its modules no
name today (``jit__unknown``), so a ragged forward is told as the module
that holds no ``%while``. One tick's span is shared by the requests it fed,
so spans are counted once by their times. The per-token remainder pass runs
the same program and is in the numerator."""


def read(run):
    c, trace = run.get("counters", {}), run.get("trace")
    if not trace or "trace_host" not in c:
        return None
    t0, t1 = c["trace_host"]
    ticks = {(s["t0_monotonic"], s["t1_monotonic"]): s["args"]["tokens"]
             for spans in run.get("spans_all", {}).values() for s in spans
             if s["name"].startswith("prefill")
             and s["t0_monotonic"] >= t0 and s["t1_monotonic"] <= t1}
    busy = sum(m["busy_s"] for n, m in trace["modules"].items()
               if not n.endswith("[while]"))
    tokens = sum(ticks.values())
    return busy * 1e3 / (tokens / 1e3) if busy and tokens else None
