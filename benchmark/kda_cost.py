"""Least operations and bytes of the Kimi Delta Attention scan's kernels, from
the shapes the device trace itself shows, and their shares of the roofline.

A device event is named by its HLO instruction. ``%kda_chunk_fwd.3 =
(bf16[1,32768,4096]{...}, f32[1,512,128,4096]{...}, ...) custom-call(...``
writes ``o [batch, seq, heads * d]`` first; ``%kda_chunk_bwd.2 =
(bf16[1,32768,4096]{...}, ...`` writes ``dq`` first. The head width ``d``
(``d_k = d_v``) and the chunk ``Q`` are the configuration's (``head_dim``,
``kda_chunk_size``).

Operations of the MATHEMATICS, whatever implements it, a chunk and head,
forward: the two triangular products ``K+ K-^T`` and ``Q+ K-^T`` (``2 * 2 Q^2
d_k``), ``T`` applied to ``[K | V]`` (``2 Q^2 (d_k + d_v)``), ``P U`` (``2 Q^2
d_v``): ``2 Q^2 (3 d_k + 2 d_v)``; and ``W S``, ``Q S``, ``K^T U`` (``6 Q d_k
d_v``). Building ``T = (I + A)^{-1}`` is not credited (the kernel's float32
matmuls for it, its four-fold triangular products around four references and
the exponentials are where its time goes), so a share can only be understated.
The backward: twice the forward; what it makes again (``A``, ``P``, ``T``,
``U``) is not counted.

Bytes, of the mathematics too: forward q, k, v, the decay's pre-activation
(the gate ``g`` and its running sum are a function of it that the kernels make
in VMEM: neither exists in HBM) and o, 5 values a channel and token in the
event's type, ``beta`` one a head and token, and the float32 states written,
``d_k d_v`` a chunk and head; backward: those four, ``do`` and the four
gradients (9 values), ``beta`` and its gradient, the states read. The kernels
move more (they read ``beta k`` and ``beta v`` beside k, 6 and 11 values): a
share can only be understated. The least time is the larger of the operations
over the bf16 peak and the bytes over the HBM bandwidth: at Q = 64 and d = 128
both are bound by memory (a layer at 16,384 tokens: forward 1.21 GB, 1.48 ms,
against 94.5 GFLOP, 0.48 ms; backward 1.75 GB, 2.13 ms, against 189 GFLOP,
0.96 ms).

A forward call that a recomputed layer makes again adds time and no work: the
forward's share credits as many calls as the backward kernel made (one useful
forward a backward), so a step that scans once a layer reads higher than one
that scans twice.
"""

from typing import Optional

from benchmark import ssd_cost

KDA_FWD, KDA_BWD = "%kda_chunk_fwd", "%kda_chunk_bwd"
KDA_ALL = "%kda_"


def token_flops(chunk: int, d_k: int, d_v: int) -> float:
    """Forward FLOPs a head and token of the chunk algebra."""
    return 2.0 * chunk * (3 * d_k + 2 * d_v) + 6.0 * d_k * d_v


def call_cost(hlo: str, config: dict) -> Optional[dict]:
    """Least ``flops`` and ``bytes`` of one call of the scan kernel whose
    event reads ``hlo``; ``None`` when it is not one of them."""
    name, itemsize, dims = ssd_cost._first_result(hlo)
    if dims is None or len(dims) != 3 or not name.startswith((KDA_FWD, KDA_BWD)):
        return None
    try:
        q, d = config["kda_chunk_size"], config["head_dim"]
    except KeyError:
        return None
    batch, seq, width = dims
    heads, chunks = width // d, -(-seq // q)
    fwd = batch * seq * heads * token_flops(q, d, d)
    states = 4.0 * batch * chunks * heads * d * d
    values, betas = float(batch * seq * width), float(batch * seq * heads)
    if name.startswith(KDA_FWD):
        return {"flops": fwd, "bytes": itemsize * (5 * values + betas) + states}
    return {"flops": 2.0 * fwd, "bytes": itemsize * (9 * values + 2 * betas) + states}


def traced(run: dict, prefixes) -> Optional[dict]:
    """The traced ``%kda_*`` calls under ``prefixes`` (``ssd_cost._traced``:
    ``calls``, ``seconds``, ``least`` seconds); ``None`` when none matched (a
    CPU rehearsal, a program without the kernels)."""
    config = run.get("config", {})

    def least_of(hlo, peaks):
        cost = call_cost(hlo, config)
        return None if cost is None else ssd_cost.least_seconds(cost, peaks)
    return ssd_cost._traced(run, prefixes, least_of)


def roofline_pct(run: dict, prefix: str) -> Optional[float]:
    """The share of the calls under ``prefix``; of the forward's, only as many
    as the backward kernel's calls are credited (the rest are recomputed)."""
    found = traced(run, (prefix, ))
    made = traced(run, (KDA_BWD, )) if found and prefix == KDA_FWD else None
    if made and made["calls"] < found["calls"]:
        found["least"] *= made["calls"] / found["calls"]
    return ssd_cost.roofline_pct(found)


def kernel_ms_per_step(run: dict) -> Optional[float]:
    """Device time of every ``%kda_*`` call a traced step, a recomputed
    forward included."""
    trace = run.get("trace")
    if not trace or not run.get("trace_steps"):
        return None
    seconds = sum(k["seconds"] for name, k in trace.get("kernels", {}).items()
                  if name.startswith(KDA_ALL))
    return 1e3 * seconds / run["trace_steps"] if seconds else None
