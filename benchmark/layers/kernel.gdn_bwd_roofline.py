"""The Gated DeltaNet scan's backward kernel's share of its roofline: the
least time of the traced ``%gdn_chunk_bwd*`` calls (``benchmark/gdn_cost.py``:
twice the forward's FLOPs; the bytes of q, k, v, ``do``, the three gradients,
``g``, ``beta`` and their gradients and the float32 states read; what the
kernel makes again is not counted) over the time the device trace gives them.
A program without the kernels reports nothing; a share over 100 is refused."""

from benchmark import gdn_cost


def read(run):
    return gdn_cost.roofline_pct(run, gdn_cost.GDN_BWD)
