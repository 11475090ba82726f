"""The ungated causal-convolution kernels' share of their roofline: the
least time of the traced ``%causal_conv_fwd*`` and ``%causal_conv_bwd*``
calls (``benchmark/ssd_cost.py``: 2 values a channel and token forward, 3
backward, and the halo rows, from the event's own shape, over the published
HBM bandwidth; memory-bound) over the time the device trace gives them."""

from benchmark import ssd_cost


def read(run):
    return ssd_cost.roofline_pct(ssd_cost.traced_conv(run))
