"""End-to-end arithmetic shared by every runner: percentiles and what counts
as inside a measured window. No later non-benchmark PR edits this file."""

import math
from typing import Iterable, List, Sequence


def percentile(values: Iterable[float], q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between the
    two nearest order statistics (numpy's default rule). An empty sample has
    no percentile: that is an error, not 0."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def in_window(records: Sequence[dict], t_open: float,
              t_close: float) -> List[dict]:
    """Requests that were sent and ended inside ``[t_open, t_close]``."""
    return [r for r in records
            if r["t_send"] >= t_open and r["t_end"] <= t_close]


def serving_metrics(records: Sequence[dict], t_open: float,
                    t_close: float) -> dict:
    """TTFT and per-request TPOT tails over the requests that started and
    ended in the window, and the output tokens of every request that
    completed in it (whenever it started: the loop was already running) per
    second of window. A failed request misses every limit: it counts with the
    whole window as its TTFT and TPOT and adds no tokens. Each record holds ``t_send``, ``t_first``, ``t_last``,
    ``t_end`` (client clock, seconds), ``n_tokens`` and ``ok``."""
    window = t_close - t_open
    reqs = in_window(records, t_open, t_close)
    if not reqs:
        raise ValueError("no request started and ended inside the window")
    miss_ms = window * 1e3
    tokens = sum(r["n_tokens"] for r in records
                 if r["ok"] and t_open <= r["t_end"] <= t_close)
    ttft, tpot = [], []
    for r in reqs:
        if not r["ok"]:
            ttft.append(miss_ms)
            tpot.append(miss_ms)
            continue
        ttft.append((r["t_first"] - r["t_send"]) * 1e3)
        if r["n_tokens"] > 1:
            tpot.append((r["t_last"] - r["t_first"]) * 1e3
                        / (r["n_tokens"] - 1))
    return {
        "attempted": len(reqs),
        "failed": sum(1 for r in reqs if not r["ok"]),
        "ttft_p95_ms": percentile(ttft, 95),
        "tpot_p95_ms": percentile(tpot, 95),
        "out_tok_s": tokens / window,
        "ttft_p50_ms": percentile(ttft, 50),
        "tpot_p50_ms": percentile(tpot, 50),
    }
