"""The Kimi Delta Attention scan's backward kernel's share of its roofline:
the least time of the traced ``%kda_chunk_bwd*`` calls (``benchmark/
kda_cost.py``: twice the forward's FLOPs; the bytes of q, k, v, the decay's
pre-activation, ``do``, the four gradients and the float32 states read; what
the kernel makes again is not counted) over the time the device trace gives
them."""

from benchmark import kda_cost


def read(run):
    return kda_cost.roofline_pct(run, kda_cost.KDA_BWD)
