"""The gated short-convolution kernels' share of their roofline: the least
time of the traced ``%short_conv_fwd*`` and ``%short_conv_bwd*`` calls
(``benchmark/conv_cost.py``: 4 values a channel and token forward, 7
backward, from the event's own shape, over the published HBM bandwidth;
memory-bound) over the time the device trace gives them."""

from benchmark import conv_cost


def read(run):
    return conv_cost.roofline_pct(run)
