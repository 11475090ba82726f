"""Least operations and bytes of the Gated DeltaNet scan's kernels, from the
shapes the device trace itself shows, and their shares of the roofline.

A device event is named by its HLO instruction. ``%gdn_chunk_fwd.3 =
(bf16[1,32768,4096]{...}, f32[1,512,128,4096]{...}, ...) custom-call(...``
writes the output ``[batch, seq, value heads * d_v]`` first;
``%gdn_chunk_bwd.2 = (bf16[1,32768,2048]{...}, ...`` writes ``dq [batch, seq,
key heads * d_k]`` first. The head counts and widths are the configuration's
(``linear_num_key_heads``, ``linear_num_value_heads``, ``linear_key_head_dim``,
``linear_value_head_dim``: not ``head_dim``, which is the softmax layers'),
the chunk ``Q`` its ``gdn_chunk_size``; an event whose width is not the
configuration's heads times their width is not counted.

Operations of the MATHEMATICS, whatever implements it, a chunk, forward: a KEY
head's two triangular products ``K K^T`` and ``Q K^T`` (``2 * 2 Q^2 d_k``:
the value heads over it scale them by their own ``beta`` and decay); a VALUE
head's ``T`` applied to ``[K | V]`` (``2 Q^2 (d_k + d_v)``), ``P U`` (``2 Q^2
d_v``) and ``W S``, ``Q S``, ``K^T U`` (``6 Q d_k d_v``). Building ``T = (I +
A)^{-1}``, the decay matrix and the exponentials are not credited, so a share
can only be understated. The backward: twice the forward; what it makes again
is not counted.

Bytes, of the scan alone: forward q, k (the key heads), v and o (the value
heads) in the event's type, ``g`` and ``beta`` one float32 a value head and
token, the float32 states written (``d_k d_v`` a chunk and value head);
backward: q, k, v, ``do``, the three gradients, ``g``, ``beta`` and their
gradients, the states read. The kernels move more (the output gate ``z`` and
its gradient: the gated norm rides inside them): a share can only be
understated. The least time is the larger of the operations over the bf16
peak and the bytes over the HBM bandwidth; at Q = 64 and heads of 128 the
states make it memory's (a layer at 32,768 tokens: forward 1.89 GB, 2.3 ms,
against 172 GFLOP, 0.87 ms).

A forward call that a recomputed layer makes again adds time and no work: the
forward's share credits as many calls as the backward kernel made, as
``kda_cost.py``. A share over 100 means the count is too high or the time
leaves work out: it is refused (``None``), never capped.
"""

from typing import Optional

from benchmark import ssd_cost

GDN_FWD, GDN_BWD = "%gdn_chunk_fwd", "%gdn_chunk_bwd"
GDN_ALL = "%gdn_"
KEYS = ("gdn_chunk_size", "linear_num_key_heads", "linear_num_value_heads",
        "linear_key_head_dim", "linear_value_head_dim")


def token_flops(chunk: int, k_heads: int, v_heads: int, d_k: int, d_v: int) -> float:
    """Forward FLOPs a token of the chunk algebra, all heads of a layer."""
    return (k_heads * 4.0 * chunk * d_k
            + v_heads * (2.0 * chunk * (d_k + 2 * d_v) + 6.0 * d_k * d_v))


def call_cost(hlo: str, config: dict) -> Optional[dict]:
    """Least ``flops`` and ``bytes`` of one call of the scan kernel whose
    event reads ``hlo``; ``None`` when it is not one of them or its width is
    not the configuration's."""
    name, itemsize, dims = ssd_cost._first_result(hlo)
    if dims is None or len(dims) != 3 or not name.startswith((GDN_FWD, GDN_BWD)):
        return None
    try:
        q, hk, hv, dk, dv = (int(config[key]) for key in KEYS)
    except KeyError:
        return None
    batch, seq, width = dims
    forward = name.startswith(GDN_FWD)
    if width != (hv * dv if forward else hk * dk):
        return None
    tokens, chunks = float(batch * seq), -(-seq // q)
    fwd = tokens * token_flops(q, hk, hv, dk, dv)
    states = 4.0 * batch * chunks * hv * dk * dv
    keys, values, gates = tokens * hk * dk, tokens * hv * dv, 4.0 * tokens * hv
    if forward:
        return {"flops": fwd,
                "bytes": itemsize * (2 * keys + 2 * values) + 2 * gates + states}
    return {"flops": 2.0 * fwd,
            "bytes": itemsize * (4 * keys + 3 * values) + 4 * gates + states}


def traced(run: dict, prefixes) -> Optional[dict]:
    """The traced ``%gdn_*`` calls under ``prefixes`` (``ssd_cost._traced``:
    ``calls``, ``seconds``, ``least`` seconds); ``None`` when none matched (a
    CPU rehearsal, a program without the kernels)."""
    config = run.get("config", {})

    def least_of(hlo, peaks):
        cost = call_cost(hlo, config)
        return None if cost is None else ssd_cost.least_seconds(cost, peaks)
    return ssd_cost._traced(run, prefixes, least_of)


def roofline_pct(run: dict, prefix: str) -> Optional[float]:
    """The share of the calls under ``prefix``; of the forward's, only as many
    as the backward kernel's calls are credited (the rest are recomputed). A
    share over 100 is refused."""
    found = traced(run, (prefix, ))
    made = traced(run, (GDN_BWD, )) if found and prefix == GDN_FWD else None
    if made and made["calls"] < found["calls"]:
        found["least"] *= made["calls"] / found["calls"]
    share = ssd_cost.roofline_pct(found)
    return None if share is None or share > 100.0 else share


def kernel_ms_per_step(run: dict) -> Optional[float]:
    """Device time of every ``%gdn_*`` call a traced step, a recomputed
    forward included."""
    trace = run.get("trace")
    if not trace or not run.get("trace_steps"):
        return None
    seconds = sum(k["seconds"] for name, k in trace.get("kernels", {}).items()
                  if name.startswith(GDN_ALL))
    return 1e3 * seconds / run["trace_steps"] if seconds else None
