"""The Kimi Delta Attention scan's forward kernel's share of its roofline:
the least time of the traced ``%kda_chunk_fwd*`` calls whose result a
backward call used (as many as ``%kda_chunk_bwd*`` calls; ``benchmark/
kda_cost.py``: the larger of the chunk algebra's FLOPs over the bf16 peak and
the bytes of q, k, v, the decay's pre-activation, o and the float32 chunk
states over the HBM bandwidth, from the event's own shape and the file's
``kda_chunk_size``; building ``(I + A)^{-1}`` uncredited; memory-bound at the
published sizes) over the time the device trace gives ALL of them: a forward
that a recomputed layer runs again adds time and no work."""

from benchmark import kda_cost


def read(run):
    return kda_cost.roofline_pct(run, kda_cost.KDA_FWD)
