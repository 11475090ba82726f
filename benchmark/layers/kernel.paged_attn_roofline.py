"""The paged attention kernel's share of its roofline at N=1 (decode).

Kernel time and calls: the device trace's ``%paged_attention*`` custom calls
whose output is ``[S, 1, heads, d]``. Least time: ``flops.paged_attention_
cost`` of one layer's call over the rows decoding at that moment, by the
published peaks. The trace holds the call's padded shapes, not each row's
context, so the rows' contexts come from the client's records (prompt length
plus tokens streamed so far), sampled over the traced interval. The bound
that applies at N=1 is memory (the K and V pages are read once)."""

import re

from benchmark import device, flops


def read(run):
    trace, c = run.get("trace"), run.get("counters", {})
    if not trace or "trace_host" not in c or "records" not in run:
        return None
    calls = [k for k in trace["kernels"].values()
             if re.search(r"^%paged_attention\S* = \w+\[\d+,1,\d+,\d+\]", k["hlo"])]
    seconds, n_calls = sum(k["seconds"] for k in calls), sum(k["count"] for k in calls)
    if not n_calls:
        return None
    peaks = device.load_peaks(run["device"]["kind"])
    t0, t1 = c["trace_host"]
    least, samples = 0.0, 32
    for i in range(samples):
        t = t0 + (t1 - t0) * (i + 0.5) / samples
        rows = [(1, int(r["n_prompt"] + r["n_tokens"] * (t - r["t_first"])
                        / max(r["t_last"] - r["t_first"], 1e-9)))
                for r in run["records"]
                if r["ok"] and r["t_first"] <= t <= r["t_last"]]
        cost = flops.paged_attention_cost(run["config"], rows, run["page_size"])
        least += flops.roofline_seconds(cost, peaks)[0] / samples
    return 100.0 * least * n_calls / seconds
