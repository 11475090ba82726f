"""Least operations and bytes of the state-space scan's and the ungated
convolution's kernels, from the shapes the device trace itself shows, and
their shares of the roofline.

A device event is named by its HLO instruction. ``%ssd_chunk_fwd.3 =
(bf16[1,16384,4096]{...}, f32[1,64,128,4096]{...}) custom-call(...`` writes
``y [batch, seq, heads * P]`` and the chunks' states ``[batch, chunks, N,
heads * P]``; ``%ssd_chunk_bwd.2 = (bf16[1,16384,4096]{...}, ...`` writes
``dx`` first. ``N`` and the chunk ``Q`` are the configuration's
(``mamba_d_state``, ``mamba_chunk_size``): the backward's event does not
show them within the characters the trace keeps.

Operations, a chunk, forward: ``2 Q^2 N`` for ``C B^T`` (shared by the
heads), ``2 Q^2 HP`` for the intra-chunk products, ``4 Q HP N`` for the
chunk's state and what the carried state adds (``HP = heads * P``). Backward:
twice that (two matmuls for each of the forward's); what it recomputes (the
decay matrices, ``B C^T``, the carried state's product) is not counted, and
neither are the exponentials and the masks (6 to 10 VPU passes over ``[Q,
Q]`` a head, which is where the kernels' time goes). The kernels zero the
other head's lanes to multiply a 64-wide head at the MXU's width, so they
execute twice the intra-chunk FLOPs counted here.

Bytes, forward: ``x`` read and ``y`` written (``2 tokens HP`` values), the
states written in float32, ``B`` and ``C`` read once; backward: ``x`` and
``dy`` read and ``dx`` written, the states read, ``B`` and ``C`` read twice
(each with its transpose). ``dt`` and the running sums (three float32 a
head and token), the re-reads of ``B`` and ``C`` by each block of heads and
the float32 partial gradients of ``B`` and ``C`` are left out: the share can
only be understated. The least time is the larger of the operations over the
bf16 peak and the bytes over the HBM bandwidth: at the published sizes the
forward is bound by memory (0.40 GB, 0.49 ms, against 70 GFLOP, 0.36 ms), the
backward by compute (140 GFLOP, 0.71 ms, against 0.54 GB, 0.66 ms).

``%causal_conv_fwd.1 = bf16[1,16384,4352]{...}`` writes ``y [batch, seq,
C]`` and has read ``x`` and, a block of ``CONV_BLOCK_ROWS`` rows,
``CONV_HALO`` rows before it; ``%causal_conv_bwd.1 = (bf16[1,16384,4352],
f32[...])`` writes ``dx`` and has read ``x`` with a halo on either side and
``dy`` with one after: ``2 + 1/16`` and ``3 + 3/16`` values a channel and
token. The taps, the bias and their gradient are left out. Memory-bound: 10
to 20 FLOP a value.
"""

from typing import Optional

from benchmark import device
from benchmark.conv_cost import _ITEMSIZE, _RESULT     # how an event's result is read

SSD_FWD, SSD_BWD = "%ssd_chunk_fwd", "%ssd_chunk_bwd"
CONV_FWD, CONV_BWD = "%causal_conv_fwd", "%causal_conv_bwd"
CONV_BLOCK_ROWS, CONV_HALO = 256, 16      # ops/short_conv.py's BLOCK_ROWS, HALO


def _first_result(hlo: str):
    """(instruction name, itemsize, dims) of the first array the event's
    instruction writes; dims None when it is no array of a known type."""
    name, _, rest = hlo.partition(" = ")
    m = _RESULT.search(rest)
    if m is None or m.group(1) not in _ITEMSIZE:
        return name, 0, None
    return name, _ITEMSIZE[m.group(1)], [int(x) for x in m.group(2).split(",")]


def ssd_call_cost(hlo: str, config: dict) -> Optional[dict]:
    """Least ``flops`` and ``bytes`` of one call of the scan kernel whose
    event reads ``hlo``; ``None`` when it is not one of them."""
    name, itemsize, dims = _first_result(hlo)
    if dims is None or len(dims) != 3 or not name.startswith((SSD_FWD, SSD_BWD)):
        return None
    batch, seq, hp = dims
    q, n = config["mamba_chunk_size"], config["mamba_d_state"]
    chunks = -(-seq // q)
    fwd = batch * chunks * (2.0 * q * q * n + 2.0 * q * q * hp + 4.0 * q * hp * n)
    states = 4.0 * batch * chunks * n * hp
    if name.startswith(SSD_FWD):
        return {"flops": fwd, "bytes": itemsize * batch * seq * (2.0 * hp + 2 * n) + states}
    return {"flops": 2.0 * fwd,
            "bytes": itemsize * batch * seq * (3.0 * hp + 4 * n) + states}


def conv_call_bytes(hlo: str) -> Optional[float]:
    name, itemsize, dims = _first_result(hlo)
    if dims is None or len(dims) != 3:
        return None
    values = float(dims[0] * dims[1] * dims[2])
    halo = CONV_HALO / min(CONV_BLOCK_ROWS, -(-dims[1] // CONV_HALO) * CONV_HALO)
    if name.startswith(CONV_FWD):
        return itemsize * values * (2.0 + halo)
    if name.startswith(CONV_BWD):
        return itemsize * values * (3.0 + 3.0 * halo)
    return None


def least_seconds(cost: dict, peaks: dict) -> float:
    return max(cost["flops"] / peaks["bf16_flops_per_s"],
               cost["bytes"] / peaks["hbm_bytes_per_s"])


def _traced(run: dict, prefixes, least_of) -> Optional[dict]:
    """The traced calls whose instruction starts with one of ``prefixes``:
    ``calls``, ``seconds`` and the sum of ``least_of(hlo, peaks)`` (seconds);
    ``None`` when none matched (a CPU rehearsal, a program without them)."""
    trace = run.get("trace")
    if not trace:
        return None
    out, peaks = {"calls": 0, "seconds": 0.0, "least": 0.0}, None
    for name, k in trace.get("kernels", {}).items():
        if not name.startswith(tuple(prefixes)):
            continue
        peaks = peaks or device.load_peaks(run["device"]["kind"])
        need = least_of(k["hlo"], peaks)
        if need is None:
            continue
        out["calls"] += k["count"]
        out["seconds"] += k["seconds"]
        out["least"] += need * k["count"]
    return out if out["seconds"] else None


def traced_ssd(run: dict, prefixes) -> Optional[dict]:
    config = run.get("config", {})
    if "mamba_chunk_size" not in config:
        return None

    def least_of(hlo, peaks):
        cost = ssd_call_cost(hlo, config)
        return None if cost is None else least_seconds(cost, peaks)
    return _traced(run, prefixes, least_of)


def traced_conv(run: dict) -> Optional[dict]:
    def least_of(hlo, peaks):
        need = conv_call_bytes(hlo)
        return None if need is None else need / peaks["hbm_bytes_per_s"]
    return _traced(run, (CONV_FWD, CONV_BWD), least_of)


def roofline_pct(found: Optional[dict]) -> Optional[float]:
    return None if found is None else 100.0 * found["least"] / found["seconds"]


def kernel_ms_per_step(run: dict) -> Optional[float]:
    """Device time of every ``%ssd_*`` and ``%causal_conv_*`` call a traced
    step, a recomputed forward included."""
    found = [f for f in (traced_ssd(run, (SSD_FWD, SSD_BWD)), traced_conv(run)) if f]
    if not found or not run.get("trace_steps"):
        return None
    return 1e3 * sum(f["seconds"] for f in found) / run["trace_steps"]
