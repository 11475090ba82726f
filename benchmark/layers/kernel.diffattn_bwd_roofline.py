"""The differential attention backward calls' share of their roofline: twice
the forward's least work (``benchmark/diffattn_cost.py``: the four matmuls of
each softmax map's gradient; the scores the kernels make again are not
credited) a traced step, over the time the device trace gives the
``%mla_bwd*`` calls."""

from benchmark import diffattn_cost


def read(run):
    return diffattn_cost.roofline_pct(run, diffattn_cost.BWD)
