"""The block-diffusion attention forward kernel's share of its roofline: the
least time of the traced ``%bdattn_fwd*`` custom calls (``benchmark/
bdattn_cost.py``: ``4 * heads * d * (L^2 + L * B) * rows`` FLOP a call, the
pairs the mask leaves live, over the published bf16 peak; compute-bound)
over the time the device trace gives them, a recomputed forward included."""

from benchmark import bdattn_cost


def read(run):
    return bdattn_cost.roofline_pct(run, bdattn_cost.FWD)
