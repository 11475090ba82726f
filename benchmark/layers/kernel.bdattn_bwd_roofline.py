"""The block-diffusion attention backward kernel's share of its roofline:
the least time of the traced ``%bdattn_bwd*`` custom calls (``benchmark/
bdattn_cost.py``: four matmuls over the live pairs, twice the forward's
count; the second pass over the scores is not counted, so the share can only
be understated) over the time the device trace gives them."""

from benchmark import bdattn_cost


def read(run):
    return bdattn_cost.roofline_pct(run, bdattn_cost.BWD)
