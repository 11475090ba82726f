"""Median wait from a request's arrival at the scheduler to the start of its
first ``prefill*`` span (the server's own per-request spans, host clock)."""

from benchmark import stats


def read(run):
    waits = [min(s["t0"] for s in spans if s["name"].startswith("prefill")) * 1e3
             for spans in run.get("spans", {}).values()
             if any(s["name"].startswith("prefill") for s in spans)]
    return stats.percentile(waits, 50) if waits else None
