"""Least bytes of the Mamba-1 selective scan's kernels, from the shapes the
device trace itself shows, and their share of the roofline.

A device event is named by its HLO instruction. ``%selscan_fwd.1 =
(bf16[1,16384,40,128]{...}, f32[1,128,16,40,128]{...}, ...) custom-call(...``
writes ``y [batch, seq, channels / 128, 128]`` first; ``%selscan_bwd.1 =
(bf16[1,16384,40,128]{...}, ...`` writes ``dx`` first. The states a channel
``N`` are the configuration's (``mamba_d_state``; the family's 16 where the
file has no key), the tokens between two kept states ``BLOCK`` = 128.

Work of the MATHEMATICS, whatever implements it. A (token, channel, state)
costs about six multiply-adds and one exponential, all on the vector unit:
``benchmark/peaks.json`` has no vector peak, so the share is held against
memory ALONE and can only be understated (at 5,120 channels of 16 states a
token's states are 82K multiply-adds a value moved: the kernels are bound by
the vector unit, not by memory, and a share of 30% may be a kernel at its
vector peak). Bytes, forward: ``x`` and ``y`` in the event's type and ``dt`` in
float32 a (token, channel), ``B`` and ``C`` in float32 a (token, state), and
the float32 state entering every block of ``BLOCK`` tokens written out (``N``
a channel and block); backward: ``x``, ``dt``, ``dy`` read, ``dx``, ``d dt``
written, ``B``, ``C`` and their gradients, the states read; ``A``'s and ``D``'s
gradients are a token's worth. At 16,384 tokens and 5,120 channels: forward
0.716 GB, 0.87 ms at 819 GB/s; backward 1.220 GB, 1.49 ms.

A forward call that a recomputed layer makes again adds time and no work: the
forward's share credits as many calls as the backward kernel made (one useful
forward a backward).
"""

from typing import Optional

from benchmark import ssd_cost

FWD, BWD = "%selscan_fwd", "%selscan_bwd"
ALL = "%selscan_"
BLOCK = 128             # ops/selective_scan.py's BLOCK: tokens between kept states
FAMILY_D_STATE = 16     # the configuration's ``assumed``: the file has no key


def call_bytes(hlo: str, config: dict) -> Optional[float]:
    """Least bytes of one call of the scan kernel whose event reads ``hlo``;
    ``None`` when it is not one of them."""
    name, itemsize, dims = ssd_cost._first_result(hlo)
    if dims is None or len(dims) != 4 or not name.startswith((FWD, BWD)):
        return None
    batch, seq, groups, lanes = dims
    n = int(config.get("mamba_d_state", FAMILY_D_STATE))
    values = float(batch * seq * groups * lanes)
    states = 4.0 * batch * -(-seq // BLOCK) * groups * lanes * n
    if name.startswith(FWD):
        return values * (2 * itemsize + 4) + 4.0 * batch * seq * 2 * n + states
    return values * (3 * itemsize + 2 * 4) + 4.0 * batch * seq * 4 * n + states


def traced(run: dict, prefixes) -> Optional[dict]:
    """The traced ``%selscan_*`` calls under ``prefixes`` (``ssd_cost._traced``:
    ``calls``, ``seconds``, ``least`` seconds); ``None`` when none matched (a
    CPU rehearsal, a program without the kernels)."""
    config = run.get("config", {})

    def least_of(hlo, peaks):
        need = call_bytes(hlo, config)
        return None if need is None else need / peaks["hbm_bytes_per_s"]
    return ssd_cost._traced(run, prefixes, least_of)


def roofline_pct(run: dict, prefix: str) -> Optional[float]:
    """The share of the calls under ``prefix``; of the forward's, only as many
    as the backward kernel's calls are credited (the rest are recomputed)."""
    found = traced(run, (prefix, ))
    made = traced(run, (BWD, )) if found and prefix == FWD else None
    if made and made["calls"] < found["calls"]:
        found["least"] *= made["calls"] / found["calls"]
    return ssd_cost.roofline_pct(found)


def kernel_ms_per_step(run: dict) -> Optional[float]:
    """Device time of every ``%selscan_*`` call a traced step, a recomputed
    forward included."""
    trace = run.get("trace")
    if not trace or not run.get("trace_steps"):
        return None
    seconds = sum(k["seconds"] for name, k in trace.get("kernels", {}).items()
                  if name.startswith(ALL))
    return 1e3 * seconds / run["trace_steps"] if seconds else None
